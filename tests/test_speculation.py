"""Speculative decoding suite: exact multi-token verification on the
paged decode path.

The load-bearing claim is that speculation is INVISIBLE in token space:
greedy decode with the n-gram proposer or a draft model commits exactly
the tokens plain greedy decode commits — for every cache variant (paged
kernel, dense gather-then-attend reference, int8 pages, shared-prefix /
copy-on-write pages), across preemption (swap & sacrifice), aborts, and
span-partitioned fleets (where the ``_spec_ok`` gate forces plain
decode).  The rollback machinery must also conserve the paged pool:
every rejected proposal's freshly-allocated page goes back on the free
list, under arbitrary accept/reject patterns (a mismatched draft model
makes them effectively random).
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TINY, TINY_ECFG, assert_pools_restored
from repro.core.analytical import TPU_V5E
from repro.models import transformer as T
from repro.models.config import Family, ModelConfig
from repro.serving.api import Server
from repro.serving.engine import (DecodeEngine, EngineConfig, PrefillEngine,
                                  ngram_propose)
from repro.serving.orchestrator import Orchestrator, OrchestratorConfig
from repro.serving.request import Outcome, Request

try:
    from hypothesis import given, settings
    from hypothesis import strategies as hst
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

MQA_CAP = ModelConfig(name="spec-cap", family=Family.DENSE, n_layers=2,
                      d_model=64, n_heads=4, n_kv_heads=1, d_ff=128,
                      vocab_size=128, logit_soft_cap=30.0)
SWA = ModelConfig(name="spec-swa", family=Family.DENSE, n_layers=2,
                  d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                  vocab_size=128, sliding_window=16)


def _prompts(rng, n, lo=10, hi=30, vocab=128):
    return [np.asarray(rng.integers(0, vocab, int(rng.integers(lo, hi))),
                       np.int32) for _ in range(n)]


def _run_engine(cfg, params, ecfg, prompts, max_new=8, draft=None,
                abort_rid=None, abort_after=3):
    """Prefill + decode to completion on a fresh engine pair; optionally
    abort one request (release its slot) a few iterations in.  Returns
    (engine, requests)."""
    pe = PrefillEngine(cfg, params, ecfg, None)
    de = DecodeEngine(cfg, params, ecfg, draft=draft)
    reqs = []
    for rid, prompt in enumerate(prompts):
        r = Request(rid=rid, arrival=0.0, prompt=prompt.copy(),
                    max_new_tokens=max_new)
        st, lg = pe.run(r)
        de.insert(r, st, int(jnp.argmax(lg)))
        reqs.append(r)
    it = 0
    while de.active:
        de.step()
        it += 1
        if abort_rid is not None and it == abort_after:
            for slot, r in enumerate(de.slots):
                if r is not None and r.rid == abort_rid:
                    de.release_slot(slot)
                    break
    return de, reqs


def _assert_engine_pool_clean(de):
    """Bare-engine version of ``assert_pools_restored``: no live slots,
    refcounts match holders, and the free list holds the whole pool."""
    assert de.active == 0
    if not de.paged:
        return
    holders = [de.slot_pages(i) for i in range(de.ecfg.max_batch)]
    de.pool.check(holders=holders)
    assert len(de._free) == de.ecfg.max_batch * de._nb_slot, "leaked pages"


# ---------------------------------------------------------------------------
# n-gram proposer semantics
# ---------------------------------------------------------------------------

def test_ngram_propose_prefers_longest_most_recent_match():
    #         0  1  2  3  4  5  6  7  8
    ctx = [5, 6, 7, 1, 5, 6, 7, 2, 5, 6]
    # suffix [5, 6] matches at 4 (-> 7, 2) and 0 (-> 7, 1); most recent wins
    assert ngram_propose(ctx, 2) == [7, 2]
    assert ngram_propose(ctx, 4) == [7, 2, 5, 6]      # runs past the match
    assert ngram_propose([1, 2, 3], 3) == []          # no repeated suffix
    assert ngram_propose([7], 3) == []                # too short to match
    assert ngram_propose([3, 3], 2) == [3]            # 1-gram self-match


def test_ngram_propose_caps_at_k():
    ctx = [1, 2, 3, 4, 1, 2]
    assert ngram_propose(ctx, 1) == [3]
    assert ngram_propose(ctx, 10) == [3, 4, 1, 2]     # exhausts the stream


# ---------------------------------------------------------------------------
# Bit-identity matrix: every cache variant x both proposers
# ---------------------------------------------------------------------------

_MATRIX = [
    pytest.param(TINY, None, id="paged-gqa-kernel"),
    pytest.param(TINY, False, id="dense-reference"),
    pytest.param(TINY.with_kv_quant(), None, id="int8-pages"),
    pytest.param(MQA_CAP, None, id="mqa-softcap"),
]


@pytest.mark.parametrize("cfg,decode_kernel", _MATRIX)
@pytest.mark.parametrize("prop", ["ngram", "draft"])
def test_speculation_bit_identical(cfg, decode_kernel, prop, model_zoo):
    params = model_zoo(cfg)
    rng = np.random.default_rng(21)
    prompts = _prompts(rng, 3, vocab=cfg.vocab_size)
    base = EngineConfig(max_len=64, max_batch=3, block_size=8,
                        decode_kernel=decode_kernel)
    de0, plain = _run_engine(cfg, params, base, prompts)
    spec_ecfg = dataclasses.replace(base, speculation=prop, spec_len=4)
    draft = (cfg, params) if prop == "draft" else None
    de1, spec = _run_engine(cfg, params, spec_ecfg, prompts, draft=draft)
    assert [r.generated for r in spec] == [r.generated for r in plain]
    assert de1._spec_ok and de1.decode_iters > 0
    if prop == "draft":        # self-draft: every proposal must accept
        assert de1.spec_proposed > 0
        assert de1.spec_accepted == de1.spec_proposed
        assert de1.decode_iters < de0.decode_iters
    _assert_engine_pool_clean(de0)
    _assert_engine_pool_clean(de1)


def test_speculation_matches_monolithic_reference(model_zoo,
                                                  greedy_reference):
    """Against the un-jitted monolithic rollout, not just the plain
    engine — the chain engine == plain == speculative is anchored."""
    params = model_zoo(TINY)
    rng = np.random.default_rng(22)
    prompts = _prompts(rng, 2)
    ecfg = EngineConfig(max_len=64, max_batch=2, block_size=8,
                        speculation="draft", spec_len=4)
    _, reqs = _run_engine(TINY, params, ecfg, prompts, max_new=10,
                          draft=(TINY, params))
    for r, p in zip(reqs, prompts):
        assert r.generated == greedy_reference(TINY, params, p, 10), r.rid


def test_sliding_window_gates_speculation_off(model_zoo):
    """Windowed stacks must decode plain (the S>1 ring scatter would
    overwrite live in-window keys): the gate trips, streams still match."""
    params = model_zoo(SWA)
    rng = np.random.default_rng(23)
    prompts = _prompts(rng, 2)
    base = EngineConfig(max_len=64, max_batch=2, block_size=8)
    _, plain = _run_engine(SWA, params, base, prompts)
    spec_ecfg = dataclasses.replace(base, speculation="ngram")
    de, spec = _run_engine(SWA, params, spec_ecfg, prompts)
    assert not de._spec_ok
    assert de.spec_proposed == 0
    assert [r.generated for r in spec] == [r.generated for r in plain]


# ---------------------------------------------------------------------------
# Rollback property: pool conservation + exactness under random
# accept/reject patterns (mismatched draft), interleaved with aborts
# ---------------------------------------------------------------------------

def _random_accept_trial(model_zoo, seed):
    params = model_zoo(TINY)
    other = model_zoo(TINY, seed=1)     # mismatched draft: random verdicts
    rng = np.random.default_rng(seed)
    prompts = _prompts(rng, 3)
    max_new = int(rng.integers(4, 12))
    base = EngineConfig(max_len=64, max_batch=3, block_size=8)
    _, plain = _run_engine(TINY, params, base, prompts, max_new=max_new)
    spec_ecfg = dataclasses.replace(base, speculation="draft",
                                    spec_len=int(rng.integers(2, 6)))
    abort_rid = int(rng.integers(0, 3)) if rng.random() < 0.5 else None
    de, spec = _run_engine(TINY, params, spec_ecfg, prompts,
                           max_new=max_new, draft=(TINY, other),
                           abort_rid=abort_rid,
                           abort_after=int(rng.integers(1, 4)))
    for r0, r1 in zip(plain, spec):
        if abort_rid is not None and r1.rid == abort_rid:
            # aborted mid-decode: whatever committed must be a prefix
            assert r1.generated == r0.generated[:len(r1.generated)]
        else:
            assert r1.generated == r0.generated
    assert de.spec_accepted <= de.spec_proposed
    _assert_engine_pool_clean(de)


@pytest.mark.parametrize("seed", range(6))
def test_random_accept_reject_rollback_seeded(model_zoo, seed):
    _random_accept_trial(model_zoo, seed)


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(hst.integers(0, 2 ** 31 - 1))
    def test_random_accept_reject_rollback_hypothesis(model_zoo, seed):
        _random_accept_trial(model_zoo, seed)


def test_adaptive_speculation_length_tracks_acceptance(model_zoo):
    """Per-slot speculation length adapts: a mismatched draft (low
    acceptance) drags the EMA and k down; a self-draft keeps both at the
    optimistic ceiling."""
    params = model_zoo(TINY)
    other = model_zoo(TINY, seed=1)
    rng = np.random.default_rng(31)
    prompts = _prompts(rng, 2)
    ecfg = EngineConfig(max_len=96, max_batch=2, block_size=8,
                        speculation="draft", spec_len=4,
                        spec_adaptive=True)
    de_bad, _ = _run_engine(TINY, params, ecfg, prompts, max_new=16,
                            draft=(TINY, other))
    de_good, _ = _run_engine(TINY, params, ecfg, prompts, max_new=16,
                             draft=(TINY, params))
    rate = de_bad.spec_accepted / max(de_bad.spec_proposed, 1)
    if rate < 0.5:          # mismatched draft rejected enough to adapt
        assert de_bad._spec_ema.min() < 1.0
    assert de_good.spec_accepted == de_good.spec_proposed


# ---------------------------------------------------------------------------
# Orchestrated: preemption, shared-prefix/COW, span fleets, counters
# ---------------------------------------------------------------------------

def _orch(tiny_params, speculation="off", **kw):
    ecfg = dataclasses.replace(TINY_ECFG, speculation=speculation,
                               spec_len=3)
    return Orchestrator(TINY, tiny_params, OrchestratorConfig(
        hw=TPU_V5E,
        n_prefill=1, n_decode=2, engine=ecfg, chunk_tokens=8, **kw))


def _ref_tokens(tiny_params, make_workload, **wl_kw):
    srv = Server(_orch(tiny_params))
    handles = [srv.submit(r, at=r.arrival) for r in make_workload(**wl_kw)]
    srv.drain()
    assert all(h.outcome == Outcome.COMPLETED for h in handles)
    return {h.rid: h.tokens for h in handles}


@pytest.mark.parametrize("mode", ["swap", "sacrifice"])
def test_speculation_survives_preemption(tiny_params, make_workload, mode):
    """Preempt speculating residents mid-run: swap must carry the pending
    token and the proposer state rebuilds from the stream, so resumed
    requests finish bit-identically to the plain uninterrupted run."""
    wl_kw = dict(n=5, seed=13, max_new=8)
    ref = _ref_tokens(tiny_params, make_workload, **wl_kw)
    orch = _orch(tiny_params, speculation="ngram")
    srv = Server(orch)
    handles = [srv.submit(r, at=r.arrival)
               for r in make_workload(**wl_kw)]
    hit = set()
    for _ in range(500):
        if not srv.step() and srv.in_flight() == 0:
            break
        for u in orch.decode_units():
            for r in u.slots:
                if r is not None and r.rid not in hit \
                        and len(r.generated) >= 2:
                    assert orch.preempt(r.rid, mode)
                    hit.add(r.rid)
                    break
    srv.drain()
    assert hit, "nothing was ever decode-resident long enough"
    for h in handles:
        assert h.outcome == Outcome.COMPLETED
        assert h.tokens == ref[h.rid], f"rid {h.rid} diverged after {mode}"
    assert_pools_restored(orch)


def test_speculation_with_shared_prefix_cow(tiny_params, make_workload):
    """Speculation over zero-copy shared-prefix pages: COW forks keep
    rollback away from shared blocks; streams match the plain arm and
    the pools balance with the store's holds."""
    outs = []
    for spec in ("off", "ngram"):
        reqs = make_workload(n=6, seed=17, max_new=6, prefix_share=0.9,
                             n_prefix_groups=1)
        orch = _orch(tiny_params, speculation=spec, prefix_sharing=True)
        s = orch.run(reqs)
        assert s["pages_bound"] > 0
        outs.append({r.rid: list(r.generated) for r in reqs})
        assert_pools_restored(orch)
    assert outs[0] == outs[1]


def test_speculation_gated_on_span_pipelines(tiny_params, make_workload):
    """A span-partitioned decode fleet (move_span territory) never
    speculates — the full-stack gate trips per engine — and the run stays
    exact with migration live."""
    outs = []
    for spec in ("off", "ngram"):
        reqs = make_workload(n=5, seed=19, max_new=6)
        ecfg = dataclasses.replace(TINY_ECFG, speculation=spec)
        orch = Orchestrator(TINY, tiny_params, OrchestratorConfig(
            hw=TPU_V5E,
            n_prefill=1, n_decode=1, decode_split=2, engine=ecfg,
            chunk_tokens=8))
        for pipe in orch.decode_pipes:
            for e in pipe.engines:
                assert not e._spec_ok
        orch.run(reqs)
        # a live span move mid-fleet must stay safe with speculation
        # configured (and gated): force one, then keep serving
        outs.append({r.rid: list(r.generated) for r in reqs})
        assert_pools_restored(orch)
    assert outs[0] == outs[1]


def test_spec_metrics_summary_counters(tiny_params, make_workload):
    """``tokens_per_decode_iter`` and the acceptance counters are wired
    through the orchestrator summary, NaN-free, and sliced per tenant."""
    orch = _orch(tiny_params, speculation="ngram")
    s = orch.run(make_workload(n=6, seed=23, max_new=8))
    assert s["decode_iters"] > 0
    assert s["tokens_per_decode_iter"] is not None
    assert s["tokens_per_decode_iter"] >= 1.0
    assert s["spec_accepted"] <= s["spec_proposed"]
    acc = s["acceptance_rate"]
    assert acc is None or 0.0 <= acc <= 1.0
    assert s["speculation"] == "ngram"
    assert s["spec_iters"] + s["spec_plain_iters"] >= s["decode_iters"]
    for ts in s["tenants"].values():
        assert ts["spec_accepted"] <= ts["spec_proposed"]
        assert ts["acceptance_rate"] is None \
            or 0.0 <= ts["acceptance_rate"] <= 1.0
    assert sum(ts["spec_proposed"] for ts in s["tenants"].values()) \
        == s["spec_proposed"]
    # speculation off: every spec stat reads zero/None, never NaN
    s0 = _orch(tiny_params).run(make_workload(n=3, seed=23, max_new=4))
    assert s0["spec_proposed"] == 0 and s0["acceptance_rate"] is None
    assert s0["tokens_per_decode_iter"] is not None


def test_per_token_timestamps_match_streams(tiny_params, make_workload):
    """A speculative iteration commits several tokens at one virtual
    instant: the per-token timestamp vector must still be one stamp per
    token and monotonic (the SLO clock and streaming replay depend on
    it)."""
    orch = _orch(tiny_params, speculation="ngram")
    reqs = make_workload(n=5, seed=29, max_new=8)
    orch.run(reqs)
    for r in reqs:
        assert len(r.t_tokens) == len(r.generated), r.rid
        assert all(b >= a for a, b in zip(r.t_tokens, r.t_tokens[1:]))
