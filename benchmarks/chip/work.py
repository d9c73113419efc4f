"""Device time and useful work of the traced window, by host span, for the
per-layer readers.

A prefill batch counts when all of its waves ran inside the traced
window.  Each driver span is matched to its profiler annotation (same
kind, start within a millisecond once both clocks are aligned on the
window's start).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from . import flops

MATCH_S = 1e-3


def _matched(run, kind: str) -> List[Tuple[object, tuple]]:
    """(driver span, trace span) of ``kind`` inside the traced window."""
    tr = run.trace
    if tr is None:
        return []
    offset = tr.lo - run.window[0]
    cands = sorted((s for s in tr.spans if s[0] == kind), key=lambda s: s[1])
    starts = [s[1] for s in cands]
    import bisect
    out = []
    for sp in run.spans:
        if sp.kind != kind or not run.in_window(sp.start):
            continue
        t = sp.start + offset
        i = bisect.bisect_left(starts, t)
        best = min((j for j in (i - 1, i) if 0 <= j < len(cands)),
                   key=lambda j: abs(starts[j] - t), default=None)
        if best is not None and abs(starts[best] - t) <= MATCH_S:
            out.append((sp, cands[best]))
    return out


def prefill_batches(run) -> Dict[int, List[tuple]]:
    """batch -> its (driver, trace) wave spans, for batches whose every
    wave ran inside the traced window."""
    all_waves: Dict[int, int] = {}
    for sp in run.spans:
        if sp.kind == "prefill_wave":
            all_waves[sp.batch] = all_waves.get(sp.batch, 0) + 1
    traced: Dict[int, List[tuple]] = {}
    for sp, ts in _matched(run, "prefill_wave"):
        if sp.end <= run.window[1]:
            traced.setdefault(sp.batch, []).append((sp, ts))
    return {b: w for b, w in traced.items() if len(w) == all_waves[b]}


def batch_requests(run, batches) -> list:
    return [r for r in run.records.values() if r.batch in batches]


def device_seconds(run, pairs, kernel: bool = False) -> float:
    """Device seconds of the programs these spans dispatched (of the
    page-fused kernel alone with ``kernel``)."""
    return run.trace.span_ops([ts for _, ts in pairs], kernel)


def roofline_share(run, work: Dict[str, float], seconds: float
                   ) -> Optional[float]:
    """Least time the chip could take for ``work`` over ``seconds``, as a
    percentage; None when nothing was measured."""
    if seconds <= 0 or work["flops"] <= 0:
        return None
    least = max(work["flops"] / run.peaks["flops"],
                work["bytes"] / run.peaks["hbm_bw"])
    return 100.0 * least / seconds


def add(a: Dict[str, float], b: Dict[str, float]) -> Dict[str, float]:
    return {k: a.get(k, 0.0) + b[k] for k in b}


def prefix_attention_work(run, recs) -> Dict[str, float]:
    """Page-held prefix attention of the prompts ``recs``: every wave
    whose prefix (store hit or earlier chunk) the paged kernel reads."""
    chunk = run.mix["serving"]["chunk_tokens"]
    total = {"flops": 0.0, "bytes": 0.0}
    for r in recs:
        for held, queries in flops.chunked_prefix_work(
                r.prompt_len, r.req.cached_tokens, chunk):
            total = add(total, flops.paged_attention_cost(run.cfg, queries,
                                                          held))
    return total
