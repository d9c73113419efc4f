"""Global KV Store: prompt tokens served from store-resident pages over
all prompt tokens, for the requests due in the window that reached a
prefill wave (exact counts, the program's per-request ``cached_tokens``).
Moves ttft_p95_ms."""


def read(run):
    recs = [r for r in run.window_records() if r.first_wave is not None]
    total = sum(r.prompt_len for r in recs)
    if not total:
        return None
    return 100.0 * sum(r.req.cached_tokens for r in recs) / total
