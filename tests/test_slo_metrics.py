"""SLO accounting, the shared virtual clock, and chunked prefill.

The metrics half pins EXACT values on a tiny hand-computed trace (two
decode instances, three requests) — TTFT/TPOT percentiles, SLO attainment
and goodput must come out to hand arithmetic, not just "a number".  The
chunked-prefill half asserts bit-level equivalence between micro-chunked
and one-shot prefill, and the clock half pins the event-ordering contract
both serving paths rely on.
"""
import numpy as np
import pytest

from conftest import TINY, TINY_ECFG
from repro.core.analytical import TPU_V5E
from repro.core.scheduling import InstanceLoad, LoadAwareRouter, RequestInfo
from repro.serving.clock import VirtualClock
from repro.serving.engine import PrefillEngine
from repro.serving.request import SLO, Metrics, Request


# ---------------------------------------------------------------------------
# Hand-computed SLO trace: 2 instances, 3 requests
# ---------------------------------------------------------------------------

def _req(rid, arrival, instance, t_tokens):
    r = Request(rid=rid, arrival=arrival,
                prompt=np.arange(4, dtype=np.int32),
                max_new_tokens=len(t_tokens))
    r.decode_instance = instance
    r.generated = list(range(len(t_tokens)))
    r.t_tokens = list(t_tokens)
    r.t_first_token = t_tokens[0]
    r.t_done = t_tokens[-1]
    return r


def test_slo_metrics_hand_computed_trace():
    slo = SLO(ttft_s=1.0, tpot_s=0.5)
    m = Metrics(slo=slo)
    # r1 on decode0: ttft 0.5 OK, tpot (1.5-0.5)/2 = 0.5 OK -> attained
    r1 = _req(1, 0.0, "decode0", [0.5, 1.0, 1.5])
    # r2 on decode1: ttft 2.0 violates; tpot 0.5 OK -> missed
    r2 = _req(2, 1.0, "decode1", [3.0, 3.5])
    # r3 on decode0: ttft 0.5 OK; tpot (5.5-2.5)/2 = 1.5 violates -> missed
    r3 = _req(3, 2.0, "decode0", [2.5, 4.0, 5.5])
    for r in (r1, r2, r3):
        m.record(r)

    assert slo.attained(r1) and not slo.attained(r2) and not slo.attained(r3)
    s = m.summary()
    assert s["n_requests"] == 3
    assert s["total_time_s"] == pytest.approx(5.5)
    assert s["throughput_tok_s"] == pytest.approx(8 / 5.5)
    assert s["mean_ttft_s"] == pytest.approx((0.5 + 2.0 + 0.5) / 3)
    assert s["p50_ttft_s"] == pytest.approx(0.5)
    assert s["mean_tpot_s"] == pytest.approx((0.5 + 0.5 + 1.5) / 3)
    assert s["p50_tpot_s"] == pytest.approx(0.5)
    # tbt stream: [0.5, 0.5] + [0.5] + [1.5, 1.5]
    assert s["p99_tbt_s"] == pytest.approx(
        float(np.percentile([0.5, 0.5, 0.5, 1.5, 1.5], 99)))
    assert s["slo_attainment"] == pytest.approx(1 / 3)
    # goodput counts ONLY the attaining request's 3 tokens
    assert s["goodput_tok_s"] == pytest.approx(3 / 5.5)
    assert s["slo_ttft_s"] == 1.0 and s["slo_tpot_s"] == 0.5


def test_metrics_without_slo_reports_nan_attainment():
    m = Metrics()
    m.record(_req(1, 0.0, "decode0", [0.5, 1.0]))
    s = m.summary()
    assert np.isnan(s["slo_attainment"]) and np.isnan(s["goodput_tok_s"])


# ---------------------------------------------------------------------------
# Virtual clock contract
# ---------------------------------------------------------------------------

def test_clock_orders_by_time_then_fifo():
    ck = VirtualClock()
    ck.push(2.0, "b")
    ck.push(1.0, "a1")
    ck.push(1.0, "a2")        # same timestamp: FIFO
    ck.push_in(0.5, "first")  # now=0 -> t=0.5
    kinds = []
    while ck:
        kinds.append(ck.pop().kind)
    assert kinds == ["first", "a1", "a2", "b"]
    assert ck.now == 2.0
    assert ck.n_processed == 4


def test_clock_rejects_past_events():
    ck = VirtualClock()
    ck.push(1.0, "x")
    ck.pop()
    with pytest.raises(ValueError):
        ck.push(0.5, "too_late")


# ---------------------------------------------------------------------------
# Queue-delay-aware routing
# ---------------------------------------------------------------------------

def test_router_prefers_lower_queue_delay_at_equal_load():
    loads = [InstanceLoad("slow", load=0.5, queue_len=1, queue_delay_s=2.0),
             InstanceLoad("fast", load=0.5, queue_len=1, queue_delay_s=0.1)]
    plan = LoadAwareRouter().dispatch(
        [RequestInfo(0, 32, est_load=0.1, est_time_s=0.5)], loads)
    assert plan[0] == "fast"
    # the dispatch bumped the target's modelled backlog
    assert loads[1].queue_delay_s == pytest.approx(0.6)


def test_router_spreads_saturated_burst_by_delay():
    """Past delta_L every instance is 'full'; requests then spread by
    modelled queue seconds, so one short-prompt instance absorbs more."""
    loads = [InstanceLoad("a", load=2.0, queue_len=3, queue_delay_s=1.0),
             InstanceLoad("b", load=2.0, queue_len=3, queue_delay_s=0.0)]
    reqs = [RequestInfo(i, 32, est_load=0.0, est_time_s=0.25)
            for i in range(4)]
    plan = LoadAwareRouter().dispatch(reqs, loads)
    assert sum(1 for v in plan.values() if v == "b") == 4  # fills to parity


# ---------------------------------------------------------------------------
# Chunked prefill == one-shot prefill, bit for bit
# ---------------------------------------------------------------------------

def _prompts(rng, shared=None):
    ps = [rng.integers(0, TINY.vocab_size, size=(n,), dtype=np.int32)
          for n in (37, 61, 18)]
    if shared is not None:
        ps = [np.concatenate([shared, p]) for p in ps]
    return ps


@pytest.mark.parametrize("chunk", [8, 10, 16])   # 10: non-block-aligned
@pytest.mark.parametrize("with_store", [False, True])
def test_chunked_prefill_matches_one_shot(tiny_params, chunk, with_store):
    from repro.core.kvstore import GlobalKVStore
    import jax

    rng = np.random.default_rng(5)
    shared = (rng.integers(0, TINY.vocab_size, 16, dtype=np.int32)
              if with_store else None)

    def run(chunk_tokens):
        store = (GlobalKVStore(block_size=TINY_ECFG.block_size)
                 if with_store else None)
        pe = PrefillEngine(TINY, tiny_params, TINY_ECFG, store)
        reqs = [Request(rid=i, arrival=0.0, prompt=p, max_new_tokens=4)
                for i, p in enumerate(_prompts(np.random.default_rng(5),
                                               shared))]
        return pe.run_batch(reqs, chunk_tokens=chunk_tokens), reqs, pe

    from repro.models import kvcache as KC
    from repro.serving.engine import serving_page_len

    plen = serving_page_len(TINY, TINY_ECFG.max_len)
    one_shot, reqs_a, _ = run(None)
    chunked, reqs_b, pe = run(chunk)
    for (st_a, lg_a), (st_b, lg_b) in zip(one_shot, chunked):
        np.testing.assert_allclose(np.asarray(lg_b), np.asarray(lg_a),
                                   rtol=1e-5, atol=1e-5)
        assert int(st_a["length"]) == int(st_b["length"])
        # compare the LIVE token range only: beyond ``length`` both layouts
        # hold masked pad junk the decoder overwrites before attending,
        # and one-shot vs chunked waves pad differently there
        n = int(st_a["length"])
        live_a = KC.slice_prefix_kv(
            KC.paged_state_to_dense(st_a, TINY_ECFG.block_size, plen), 0, n)
        live_b = KC.slice_prefix_kv(
            KC.paged_state_to_dense(st_b, TINY_ECFG.block_size, plen), 0, n)
        leaves_a = jax.tree.leaves(live_a)
        leaves_b = jax.tree.leaves(live_b)
        assert len(leaves_a) == len(leaves_b)
        for a, b in zip(leaves_a, leaves_b):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       rtol=1e-5, atol=1e-5)
    # store bookkeeping: a chunked run can never hit MORE than one-shot,
    # and every hit is a block-aligned prefix; once the chunk covers the
    # whole shared prefix the hit pattern is identical (blocks publish at
    # every chunk boundary, so siblings see partial prefixes early)
    for ra, rb in zip(reqs_a, reqs_b):
        assert rb.cached_tokens <= ra.cached_tokens
        assert rb.cached_tokens % TINY_ECFG.block_size == 0
    if shared is not None and chunk >= len(shared):
        assert [r.cached_tokens for r in reqs_b] == \
            [r.cached_tokens for r in reqs_a]
    # every request really was split: more waves ran than requests
    assert pe.tokens_prefilled == sum(r.prompt_len - r.cached_tokens
                                      for r in reqs_b)


def test_chunked_prefill_through_span_pipeline(tiny_params):
    """Micro-chunked prefill through a chained span pipeline: partial
    states split/merge across stage boundaries each wave, and logits
    still equal the monolithic one-shot engine's."""
    from repro.serving.span import PrefillPipeline

    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, TINY.vocab_size, size=(n,), dtype=np.int32)
               for n in (45, 29)]

    def reqs():
        return [Request(rid=i, arrival=0.0, prompt=p, max_new_tokens=4)
                for i, p in enumerate(prompts)]

    ref = PrefillEngine(TINY, tiny_params, TINY_ECFG, None).run_batch(reqs())
    pp = PrefillPipeline(TINY, tiny_params, TINY_ECFG, [(0, 2), (2, 4)])
    out = pp.run_batch(reqs(), chunk_tokens=16)
    for (st_a, lg_a), (_st_b, lg_b) in zip(ref, out):
        np.testing.assert_allclose(np.asarray(lg_b), np.asarray(lg_a),
                                   rtol=1e-5, atol=1e-5)


def test_chunked_rollout_token_exact_under_orchestrator(tiny_params,
                                                        make_workload,
                                                        greedy_reference):
    """End to end: micro-chunked prefill + event-driven loop + migration
    produce the reference greedy stream, and virtual timestamps are
    monotone per request."""
    from repro.serving.orchestrator import Orchestrator, OrchestratorConfig
    orch = Orchestrator(TINY, tiny_params, OrchestratorConfig(
        hw=TPU_V5E,
        n_prefill=2, n_decode=2, engine=TINY_ECFG, chunk_tokens=8))
    reqs = make_workload(6, seed=23, max_new=6, rps=1e7,
                         prompt_len_lo=24, prompt_len_hi=64)
    s = orch.run(reqs)
    assert s["n_requests"] == 6
    for r in reqs:
        assert r.generated == greedy_reference(TINY, tiny_params, r.prompt,
                                               r.max_new_tokens), r.rid
        assert r.arrival <= r.t_first_token <= r.t_done
        assert r.t_tokens == sorted(r.t_tokens)
        assert len(r.t_tokens) == len(r.generated)


def test_virtual_clock_runs_are_deterministic(tiny_params, make_workload):
    """Same seed, same config -> identical summaries and identical
    per-token timestamp streams (the wall clock is out of the loop)."""
    from repro.serving.orchestrator import Orchestrator, OrchestratorConfig

    def once():
        orch = Orchestrator(TINY, tiny_params, OrchestratorConfig(
            hw=TPU_V5E,
            n_prefill=2, n_decode=2, engine=TINY_ECFG, chunk_tokens=8,
            slo=SLO(ttft_s=5e-6, tpot_s=2e-6)))
        reqs = make_workload(8, seed=7, max_new=5, rps=1e7)
        s = orch.run(reqs)
        return s, [r.t_tokens for r in reqs]

    s1, t1 = once()
    s2, t2 = once()
    assert s1 == s2
    assert t1 == t2
