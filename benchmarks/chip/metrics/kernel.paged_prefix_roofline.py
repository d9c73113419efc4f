"""Kernels: the page-fused attention kernel inside prefill waves (store
hits and chunk resumes read their held prefix through it).  Its least
time for the held prefix tokens' keys and values and the new queries
(``flops.paged_attention_cost``), over its device time in the wave spans
of the prefill batches that ran wholly inside the traced window.  Moves
ttft_p95_ms."""
from benchmarks.chip import work


def read(run):
    batches = work.prefill_batches(run)
    if not batches:
        return None
    seconds = sum(work.device_seconds(run, w, kernel=True)
                  for w in batches.values())
    return work.roofline_share(
        run, work.prefix_attention_work(run, work.batch_requests(run,
                                                                 batches)),
        seconds)
