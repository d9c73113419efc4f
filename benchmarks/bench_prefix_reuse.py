"""Prefix-reuse A/B: zero-copy page sharing vs copy vs full recompute.

One prefix-skewed workload (every prompt opens with the same hot prefix)
through the live orchestrator three ways:

* **shared** — the Global KV Store registers the prefix's pages in the
  decode pool and later hand-offs bind them by reference (refcounted,
  copy-on-write): the hot prefix is HBM-resident ONCE.
* **copy** — ``prefix_sharing=False``: the store still dedupes prefill
  compute, but every hand-off materializes its own page copies.
* **recompute** — no store at all: every request prefills from token 0.

All three arms must produce identical token streams (sharing changes
bytes moved and pages resident, never math).  The printed rows / JSON
artifact cover the paper-motivating deltas: peak HBM pages holding the
hot prefix, hand-off bytes skipped by binds, prefill tokens actually
computed, and the Eq. 19 prefill FLOPs the cache hits saved.

    PYTHONPATH=src python -m benchmarks.run --only prefix_reuse
"""
import os
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).parent.parent / "src"))

import numpy as np

from repro.core import analytical as A
from repro.core.kvstore import chain_hashes
from repro.models import transformer as T
from repro.models.config import Family, ModelConfig
from repro.serving.api import Server
from repro.serving.engine import EngineConfig
from repro.serving.orchestrator import Orchestrator, OrchestratorConfig
from repro.serving.workload import WorkloadConfig, generate

CFG = ModelConfig(name="bench-pfx", family=Family.DENSE, n_layers=4,
                  d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                  vocab_size=128)
ECFG = EngineConfig(max_len=96, max_batch=3, block_size=8)
BS = ECFG.block_size


def _workload(n: int):
    return generate(WorkloadConfig(
        kind="synthetic", rps=1e7, n_requests=n, vocab_size=CFG.vocab_size,
        max_new_tokens=4, prefix_share=1.0, n_prefix_groups=1, seed=5,
        prompt_len_lo=40, prompt_len_hi=64))


def _hot_prefix_keys(reqs):
    """Chain keys of the workload's common hot prefix (full blocks)."""
    hot = [r for r in reqs if r.prefix_id == 0]
    n_common = min(r.prefix_len for r in hot)
    n_full = n_common // BS
    return set(chain_hashes(hot[0].prompt[:n_full * BS], BS)), n_full


def _prefix_resident_pages(orch, keys, n_full) -> int:
    """Distinct HBM pages currently holding a copy of the hot prefix:
    each decode slot's first ``n_full`` blocks for prefix-carrying
    requests, unioned (shared binds collapse) with the store's page holds
    for the prefix keys."""
    total = 0
    for u in orch.decode_units():
        for e in getattr(u, "engines", [u]):
            if not getattr(e, "paged", False):
                continue
            pages = set()
            for i, r in enumerate(e.slots):
                if r is not None and r.prefix_id == 0:
                    pages.update(e.slot_pages(i)[:n_full])
            if orch.store is not None:
                pages.update(p for k, p in
                             orch.store.pool_pages(e.name).items()
                             if k in keys)
            total += len(pages)
    return total


def _run_arm(mode: str, n_requests: int) -> dict:
    reqs = _workload(n_requests)
    keys, n_full = _hot_prefix_keys(reqs)
    params = T.init(CFG, __import__("jax").random.PRNGKey(0))
    orch = Orchestrator(CFG, params, OrchestratorConfig(
        hw=A.TPU_V5E,
        n_prefill=1, n_decode=1, migration=False, engine=ECFG,
        global_store=(mode != "recompute"),
        prefix_sharing=(mode == "shared")))
    if mode == "recompute":
        for m in orch.prefill_members():     # no cache anywhere: token 0
            m.prefill.store = None
    server = Server(orch)
    for r in sorted(reqs, key=lambda r: r.arrival):
        server.submit(r, at=r.arrival)
    peak_prefix = 0
    while server.in_flight():
        server.step()
        peak_prefix = max(peak_prefix,
                          _prefix_resident_pages(orch, keys, n_full))
    server.drain()
    s = orch.summary()
    flops_saved = sum(
        A.prefix_reuse_flops_saved(CFG, r.prompt_len, r.cached_tokens)
        for r in reqs)
    return {
        "tokens": {r.rid: list(r.generated) for r in reqs},
        "prefix_pages_peak": peak_prefix,
        "hbm_pages_peak": sum(
            m.decode.pool.peak_used for m in orch.decode_members()
            if m.decode is not None and m.decode.paged),
        "prefill_tokens": sum(m.tokens_prefilled
                              for m in orch.prefill_members()),
        "cached_tokens": sum(r.cached_tokens for r in reqs),
        "prefill_flops_saved": flops_saved,
        "pages_bound": s.get("pages_bound", 0),
        "bound_bytes_saved": s.get("bound_bytes_saved", 0.0),
        "cow_forks": s.get("cow_forks", 0),
        "handoff_overlap_s": s["handoff_overlap_s"],
    }


def main() -> dict:
    smoke = bool(int(os.environ.get("BENCH_SMOKE", "0")))
    n = 6 if smoke else 12
    arms = {mode: _run_arm(mode, n)
            for mode in ("shared", "copy", "recompute")}

    # exactness: sharing / copying / recomputing never change the math
    assert arms["shared"]["tokens"] == arms["copy"]["tokens"] \
        == arms["recompute"]["tokens"], "token streams diverged across arms"
    sh, cp, rc = arms["shared"], arms["copy"], arms["recompute"]
    assert sh["pages_bound"] > 0 and sh["bound_bytes_saved"] > 0
    # the hot prefix is HBM-resident once, not once per slot
    assert cp["prefix_pages_peak"] >= 2 * sh["prefix_pages_peak"] > 0, \
        (cp["prefix_pages_peak"], sh["prefix_pages_peak"])
    # store hits skip prefix recompute entirely
    assert sh["prefill_tokens"] < rc["prefill_tokens"]
    assert sh["prefill_flops_saved"] > 0 and rc["prefill_flops_saved"] == 0

    print("prefix_reuse,mode,prefix_pages_peak,hbm_pages_peak,"
          "prefill_tokens,pages_bound,bound_bytes_saved,cow_forks,"
          "prefill_flops_saved")
    out = {}
    for mode, r in arms.items():
        print(f"prefix_reuse,{mode},{r['prefix_pages_peak']},"
              f"{r['hbm_pages_peak']},{r['prefill_tokens']},"
              f"{r['pages_bound']},{r['bound_bytes_saved']:.0f},"
              f"{r['cow_forks']},{r['prefill_flops_saved']:.3e}")
        out[mode] = {k: v for k, v in r.items() if k != "tokens"}
    out["prefix_pages_ratio_copy_over_shared"] = (
        cp["prefix_pages_peak"] / max(sh["prefix_pages_peak"], 1))
    out["prefill_tokens_saved_vs_recompute"] = (
        rc["prefill_tokens"] - sh["prefill_tokens"])
    return out


if __name__ == "__main__":
    main()
