"""Prefill engine and model step: useful operations of the prefill
batches that ran wholly inside the traced window, over their device time
times the chip's peak.  Useful: the prompt tokens not served from the
store, through every layer with the top-k experts only, each attending
over its causal context, and the head once per prompt.  Device time: the
operations of the programs those batches' wave spans dispatched.  Moves
ttft_p95_ms."""
from benchmarks.chip import flops, work


def read(run):
    batches = work.prefill_batches(run)
    if not batches:
        return None
    useful = sum(flops.prefill_flops(run.cfg, r.prompt_len,
                                     r.req.cached_tokens)
                 for r in work.batch_requests(run, batches))
    seconds = sum(work.device_seconds(run, w) for w in batches.values())
    if seconds <= 0:
        return None
    return 100.0 * useful / (seconds * run.peaks["flops"])
