"""Layer-span migration (§4.1, live): span-partitioned pipelines must be
invisible to the math — pipelined greedy decode is token-identical to the
monolithic engine, before and after live boundary moves, and span states
interoperate with full-stack instances through the universal wire format."""
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TINY, TINY_ECFG
from repro.core.analytical import TPU_V5E
from repro.core.layer_migration import even_spans
from repro.core.migration import MigrationAction, MigrationKind
from repro.models.config import BlockKind, Family, ModelConfig
from repro.serving.engine import DecodeEngine, EngineConfig, PrefillEngine
from repro.serving.orchestrator import Orchestrator, OrchestratorConfig
from repro.serving.request import Request
from repro.serving.span import DecodePipeline, PrefillPipeline


def _mk_requests(n, rng, max_new=8, lo=12, hi=40, vocab=128):
    return [Request(rid=i, arrival=0.0,
                    prompt=rng.integers(0, vocab,
                                        int(rng.integers(lo, hi)),
                                        dtype=np.int32),
                    max_new_tokens=max_new) for i in range(n)]


# ---------------------------------------------------------------------------
# Span-partitioned fleet == monolithic engine (the Eq. 5 contract)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bounds", [even_spans(TINY.n_layers, 2),
                                    [(0, 1), (1, TINY.n_layers)],
                                    even_spans(TINY.n_layers, 4)])
def test_pipelined_fleet_token_exact(tiny_params, greedy_reference, bounds):
    """Prefill + decode pipelines split 2- and 4-way produce greedy tokens
    bit-identical to the monolithic stack, for even and skewed cuts."""
    pp = PrefillPipeline(TINY, tiny_params, TINY_ECFG, bounds)
    dp = DecodePipeline(TINY, tiny_params, TINY_ECFG, bounds)
    reqs = _mk_requests(3, np.random.default_rng(1))
    for r, (st, lg) in zip(reqs, pp.run_batch(reqs)):
        dp.insert(r, st, int(jnp.argmax(lg)))
    while dp.active:
        dp.step()
    for r in reqs:
        assert r.generated == greedy_reference(TINY, tiny_params, r.prompt,
                                               r.max_new_tokens), r.rid


def test_span_wire_interop_with_monolithic_engines(tiny_params,
                                                   greedy_reference):
    """Mid-flight slots move pipeline -> monolithic engine and back: every
    edge speaks the full-stack wire format."""
    bounds = even_spans(TINY.n_layers, 2)
    pp = PrefillPipeline(TINY, tiny_params, TINY_ECFG, bounds)
    dp = DecodePipeline(TINY, tiny_params, TINY_ECFG, bounds)
    mono = DecodeEngine(TINY, tiny_params, TINY_ECFG, name="mono")
    reqs = _mk_requests(2, np.random.default_rng(2))
    for r, (st, lg) in zip(reqs, pp.run_batch(reqs)):
        dp.insert(r, st, int(jnp.argmax(lg)))
    for _ in range(2):
        dp.step()
    # pipeline -> monolith
    req, st, tok = dp.extract_slot(0)
    mono.adopt(req, st, tok)
    for _ in range(2):
        dp.step()
        mono.step()
    # monolith -> pipeline
    req, st, tok = mono.extract_slot(0)
    dp.adopt(req, st, tok)
    while dp.active:
        dp.step()
    for r in reqs:
        assert r.generated == greedy_reference(TINY, tiny_params, r.prompt,
                                               r.max_new_tokens), r.rid


# ---------------------------------------------------------------------------
# Migration under load: live boundary moves between decode steps
# ---------------------------------------------------------------------------

def test_span_move_under_load_token_exact(tiny_params, greedy_reference):
    """Greedy decode stays token-identical when layer spans migrate
    mid-stream — forward, backward, and with slot churn after the move
    (mirrors test_paged.py's migration-under-load pattern)."""
    bounds = even_spans(TINY.n_layers, 2)
    pp = PrefillPipeline(TINY, tiny_params, TINY_ECFG, bounds)
    dp = DecodePipeline(TINY, tiny_params, TINY_ECFG, bounds)
    rng = np.random.default_rng(7)
    reqs = _mk_requests(2, rng, max_new=10)
    for r, (st, lg) in zip(reqs, pp.run_batch(reqs)):
        dp.insert(r, st, int(jnp.argmax(lg)))
    for _ in range(3):
        dp.step()
    rec = dp.move_span(0, 1, 1)          # hot stage sheds a boundary layer
    assert rec is not None and rec["layers"] == 1
    assert dp.bounds == [(0, 1), (1, 4)]
    for _ in range(2):
        dp.step()
    # a request inserted AFTER the move lands on the new partitioning
    late = _mk_requests(1, rng, max_new=6)[0]
    late.rid = 99
    st, lg = pp.run(late)
    dp.insert(late, st, int(jnp.argmax(lg)))
    dp.step()
    assert dp.move_span(1, 0, 2)["layers"] == 2   # and back, larger span
    assert dp.bounds == [(0, 3), (3, 4)]
    while dp.active:
        dp.step()
    for r in reqs + [late]:
        assert r.generated == greedy_reference(TINY, tiny_params, r.prompt,
                                               r.max_new_tokens), r.rid


def test_span_move_payload_scales_with_span(tiny_params):
    """The migrated payload is the moved span's weights + KV — k layers
    cost ~k times one layer, never the whole stack."""
    def payload(k):
        dp = DecodePipeline(TINY, tiny_params, TINY_ECFG,
                            [(0, 3), (3, 4)])
        pe = PrefillEngine(TINY, tiny_params, TINY_ECFG, None)
        r = Request(rid=0, arrival=0.0,
                    prompt=np.arange(24, dtype=np.int32),
                    max_new_tokens=100)
        st, lg = pe.run(r)
        dp.insert(r, st, int(jnp.argmax(lg)))
        dp.step()
        rec = dp.move_span(0, 1, k)
        assert rec["layers"] == k
        return rec["weight_bytes"] + rec["kv_bytes"]

    one, two = payload(1), payload(2)
    assert 1.8 * one <= two <= 2.2 * one


def test_span_move_schedule_is_per_moved_layer(tiny_params):
    """The move's ordered schedule names exactly the moved layers (absolute
    indices) and its bytes add up to the billed payload."""
    from repro.core import analytical as A
    dp = DecodePipeline(TINY, tiny_params, TINY_ECFG, [(0, 3), (3, 4)])
    pe = PrefillEngine(TINY, tiny_params, TINY_ECFG, None)
    r = Request(rid=0, arrival=0.0, prompt=np.arange(20, dtype=np.int32),
                max_new_tokens=100)
    st, lg = pe.run(r)
    dp.insert(r, st, int(jnp.argmax(lg)))
    dp.step()
    rec = dp.move_span(0, 1, 2)
    assert [l for l, _ in rec["schedule"]] == [1, 2]   # layers [1, 3)
    assert sum(b for _, b in rec["schedule"]) == \
        rec["weight_bytes"] + rec["kv_bytes"]
    nbytes = [b for _, b in rec["schedule"]]
    bw = A.TPU_V5E.net_bw
    assert A.overlapped_schedule_time(nbytes, bw, 1e-4, t_sync=0.0) <= \
        A.serial_schedule_time(nbytes, bw, 1e-4, t_sync=0.0) + 1e-12


def test_prefill_pipeline_span_move(tiny_params, greedy_reference):
    """Prefill stages re-slice live too (no resident state): requests
    prefilled across the new cut still match the monolith, both move
    directions, and emptying a stage is refused."""
    pp = PrefillPipeline(TINY, tiny_params, TINY_ECFG,
                         even_spans(TINY.n_layers, 2))
    dp = DecodePipeline(TINY, tiny_params, TINY_ECFG,
                        even_spans(TINY.n_layers, 2))
    rng = np.random.default_rng(3)

    def serve(rid):
        r = _mk_requests(1, rng, max_new=5)[0]
        r.rid = rid
        st, lg = pp.run(r)
        dp.insert(r, st, int(jnp.argmax(lg)))
        while dp.active:
            dp.step()
        assert r.generated == greedy_reference(TINY, tiny_params, r.prompt,
                                               r.max_new_tokens), r.rid

    serve(0)
    assert pp.move_span(0, 1, 1) == 1
    assert pp.bounds == [(0, 1), (1, 4)]
    serve(1)
    assert pp.move_span(0, 1, 1) is None         # would empty stage 0
    assert pp.move_span(1, 0, 2) == 2            # and back the other way
    assert pp.bounds == [(0, 3), (3, 4)]
    serve(2)


def test_controller_never_prices_stage_reroll(tiny_params, make_workload):
    """A hot pipeline stage paired with a cold full-stack member prices at
    benefit 0 (apply_action would refuse it), so the controller never
    plans phantom actions that burn its per-cycle budget; and any LAYER
    action applied on a split fleet is a same-pipeline span move."""
    from repro.core.migration import DeviceLoad
    orch = Orchestrator(TINY, tiny_params, OrchestratorConfig(
        hw=TPU_V5E,
        n_prefill=2, n_decode=1, engine=TINY_ECFG, migration=True,
        decode_split=2))
    hot = DeviceLoad(device="decode0.0", compute_frac=1.0, memory_frac=1.0)
    cold = DeviceLoad(device="prefill0", compute_frac=0.0, memory_frac=0.0)
    benefit, _cost = orch._migration_cost(MigrationKind.LAYER, hot, cold, 2)
    assert benefit == 0.0
    for r in make_workload(6, seed=17, max_new=8):
        orch.submit(r)
    while orch.metrics.n_requests < 6:
        orch.step()
    for act in orch.migration_log:
        if act.kind == MigrationKind.LAYER:
            src = orch._by_name[act.src]
            dst = orch._by_name[act.dst]
            assert src.pipe is not None and src.pipe is dst.pipe


def test_span_move_refuses_to_empty_a_stage(tiny_params):
    dp = DecodePipeline(TINY, tiny_params, TINY_ECFG, [(0, 1), (1, 4)])
    assert dp.move_span(0, 1, 1) is None          # would leave 0 layers
    assert dp.move_span(1, 0, 99)["layers"] == 2  # clamped to span - 1
    assert dp.bounds == [(0, 3), (3, 4)]


# ---------------------------------------------------------------------------
# Mixed stacks: ring-only and recurrent spans cross boundaries exactly
# ---------------------------------------------------------------------------

MIXED = ModelConfig(name="mix-span", family=Family.DENSE, n_layers=4,
                    d_model=32, n_heads=2, n_kv_heads=2, d_ff=64,
                    vocab_size=64, local_window=16,
                    block_pattern=(BlockKind.ATTENTION,
                                   BlockKind.LOCAL_ATTENTION))
MIXED_ECFG = EngineConfig(max_len=64, max_batch=2, block_size=8)


def test_mixed_arch_span_pipeline_token_exact(model_zoo, greedy_reference):
    """A ring-only stage pages at its own window and de-pages at the wire
    (the canonical-form contract); tokens still match the monolith across
    a live span move."""
    params = model_zoo(MIXED)
    bounds = [(0, 3), (3, 4)]        # stage 1 hosts a lone windowed layer
    pp = PrefillPipeline(MIXED, params, MIXED_ECFG, bounds)
    dp = DecodePipeline(MIXED, params, MIXED_ECFG, bounds)
    reqs = _mk_requests(2, np.random.default_rng(5), max_new=8,
                        lo=10, hi=30, vocab=64)
    for r, (st, lg) in zip(reqs, pp.run_batch(reqs)):
        dp.insert(r, st, int(jnp.argmax(lg)))
    for _ in range(3):
        dp.step()
    assert dp.move_span(0, 1, 1)["layers"] == 1
    while dp.active:
        dp.step()
    for r in reqs:
        assert r.generated == greedy_reference(MIXED, params, r.prompt,
                                               r.max_new_tokens), r.rid


# ---------------------------------------------------------------------------
# Orchestrator: LAYER actions carry a span amount on a split decode tier
# ---------------------------------------------------------------------------

def test_orchestrator_span_move_before_and_after_exact(tiny_params,
                                                       greedy_reference,
                                                       make_workload):
    """decode_split=2 fleet: greedy tokens are exact before AND after a
    live MigrationKind.LAYER span move applied mid-run, the move re-cuts
    the pipeline instead of re-rolling, and the payload is logged."""
    orch = Orchestrator(TINY, tiny_params, OrchestratorConfig(
        hw=TPU_V5E,
        n_prefill=1, n_decode=1, engine=TINY_ECFG, migration=False,
        decode_split=2))
    assert orch.fleet == {"prefill0": "prefill", "decode0.0": "decode",
                          "decode0.1": "decode"}
    reqs = make_workload(6, seed=9, max_new=8)
    for r in reqs:
        orch.submit(r)
    for _ in range(3):
        orch.step()
    assert orch.decode_pipes[0].active > 0       # mid-flight slots exist
    act = MigrationAction(MigrationKind.LAYER, src="decode0.0",
                          dst="decode0.1", amount=1,
                          predicted_benefit=1.0, predicted_cost=1e-3)
    assert orch.apply_action(act)
    assert orch.decode_pipes[0].bounds == [(0, 1), (1, 4)]
    assert orch.fleet["decode0.0"] == "decode"   # no role changed
    while orch.metrics.n_requests < len(reqs):
        orch.step()
    s = orch.summary()
    assert s["span_moves"] == 1 and s["span_bytes_moved"] > 0
    assert s["span_bounds"]["decode0"] == [(0, 1), (1, 4)]
    for r in reqs:
        assert r.generated == greedy_reference(TINY, tiny_params, r.prompt,
                                               r.max_new_tokens), r.rid


def test_orchestrator_span_stages_never_reroll(tiny_params):
    """LAYER actions between a pipeline stage and anything outside its
    pipeline are refused — stages re-slice spans, not roles."""
    orch = Orchestrator(TINY, tiny_params, OrchestratorConfig(
        hw=TPU_V5E,
        n_prefill=1, n_decode=2, engine=TINY_ECFG, migration=False,
        decode_split=2))
    act = MigrationAction(MigrationKind.LAYER, src="decode0.1",
                          dst="prefill0", amount=TINY.n_layers,
                          predicted_benefit=1.0, predicted_cost=1e-3)
    assert not orch.apply_action(act)
    act = MigrationAction(MigrationKind.LAYER, src="decode0.0",
                          dst="decode1.0", amount=1,
                          predicted_benefit=1.0, predicted_cost=1e-3)
    assert not orch.apply_action(act)            # different pipelines
    assert orch.fleet["prefill0"] == "prefill"
    assert len(orch.migration_log) == 0


def test_orchestrator_rebalance_across_pipelines(tiny_params,
                                                 greedy_reference,
                                                 make_workload):
    """KV_HEADS between two pipelines WITH DIFFERENT BOUNDS: slots merge
    to the wire format on exit and re-split at the target's cuts."""
    orch = Orchestrator(TINY, tiny_params, OrchestratorConfig(
        hw=TPU_V5E,
        n_prefill=1, n_decode=2, engine=TINY_ECFG, migration=False,
        decode_split=2))
    # skew the second pipeline's cuts so the wire format must re-slice
    assert orch.decode_pipes[1].move_span(0, 1, 1) is not None
    reqs = make_workload(5, seed=11, max_new=6)
    for r in reqs:
        orch.submit(r)
    for _ in range(3):
        orch.step()
    src, dst = orch.decode_pipes
    if src.active < dst.active:
        src, dst = dst, src
    moved_before = dst.active
    if src.active - dst.active >= 2 and dst.free_slots > 0:
        act = MigrationAction(MigrationKind.KV_HEADS,
                              src=src.lead.name, dst=dst.lead.name,
                              amount=1, predicted_benefit=1.0,
                              predicted_cost=1e-3)
        assert orch.apply_action(act)
        assert dst.active > moved_before
    while orch.metrics.n_requests < len(reqs):
        orch.step()
    for r in reqs:
        assert r.generated == greedy_reference(TINY, tiny_params, r.prompt,
                                               r.max_new_tokens), r.rid
