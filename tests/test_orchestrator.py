"""Live orchestrator: routing over real engines, KV hand-off, and
migration re-rolls must all preserve token-for-token greedy decode."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.analytical import TPU_V5E
from repro.core.migration import (ControllerConfig, MigrationAction,
                                  MigrationKind)
from repro.models.config import Family, ModelConfig
from repro.serving.engine import DecodeEngine, EngineConfig, PrefillEngine
from repro.serving.orchestrator import (ROLE_DECODE, ROLE_PREFILL,
                                        Orchestrator, OrchestratorConfig)
from repro.serving.request import Phase, Request
from repro.serving.workload import WorkloadConfig, generate

CFG = ModelConfig(name="e", family=Family.DENSE, n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128)
ECFG = EngineConfig(max_len=96, max_batch=3, block_size=8)


@pytest.fixture(scope="module")
def params(model_zoo):
    return model_zoo(CFG)


@pytest.fixture
def _reference_rollout(params, greedy_reference):
    """Module-local shim over the session-memoized greedy reference."""
    def ref(_params, prompt, n):
        return greedy_reference(CFG, params, prompt, n)
    return ref


def _single_engine_rollout(params, req: Request):
    """Reference: the same request through one standalone engine pair."""
    pe = PrefillEngine(CFG, params, ECFG, None, name="ref_p")
    de = DecodeEngine(CFG, params, ECFG, name="ref_d")
    ref = Request(rid=10_000 + req.rid, arrival=0.0, prompt=req.prompt,
                  max_new_tokens=req.max_new_tokens)
    st, logits = pe.run(ref)
    de.insert(ref, st, int(jnp.argmax(logits)))
    while de.active:
        de.step()
    return ref.generated


def _workload(n, seed=3, max_new=8):
    # rps is VIRTUAL-clock arrivals/s: at 1e7 the tiny model saturates
    # (inter-arrival ~0.1us vs ~us-scale event costs), matching the old
    # lockstep tests' everything-at-once pressure
    return generate(WorkloadConfig(
        kind="synthetic", rps=1e7, n_requests=n, vocab_size=128,
        max_new_tokens=max_new, prefix_share=0.6, n_prefix_groups=2,
        seed=seed, prompt_len_lo=16, prompt_len_hi=48))


# ---------------------------------------------------------------------------
# Batched prefill (engine-level)
# ---------------------------------------------------------------------------

def test_default_config_constructs_with_frozen_controller():
    """The orchestrator's defaults are shared instances, so every config
    they hold is frozen: the default controller config cannot be mutated
    through one fleet under another."""
    ocfg = OrchestratorConfig()
    assert ocfg.hw is None        # resolved from the fleet's device
    with pytest.raises(dataclasses.FrozenInstanceError):
        ocfg.controller.delta_up = 0.9
    assert ControllerConfig().delta_up == 0.35


def test_batched_prefill_matches_single(params):
    """One dense batch — mixed prefix hit/miss rows — equals per-request
    prefill exactly (states and logits)."""
    from repro.core.kvstore import GlobalKVStore
    rng = np.random.default_rng(0)
    shared = rng.integers(0, 128, 24, dtype=np.int32)
    prompts = [np.concatenate([shared,
                               rng.integers(0, 128, 10, dtype=np.int32)])
               for _ in range(3)]
    prompts.append(rng.integers(0, 128, 34, dtype=np.int32))  # no hit

    def run(batched):
        pe = PrefillEngine(CFG, params, ECFG, GlobalKVStore(block_size=8))
        reqs = [Request(rid=i, arrival=0.0, prompt=p, max_new_tokens=4)
                for i, p in enumerate(prompts)]
        # req 0 populates the store; 1..3 arrive later
        first = pe.run(reqs[0])
        if batched:
            rest = pe.run_batch(reqs[1:])
        else:
            rest = [pe.run(r) for r in reqs[1:]]
        return [first] + rest, reqs

    single, sreqs = run(batched=False)
    batched, breqs = run(batched=True)
    for (st_s, lg_s), (st_b, lg_b) in zip(single, batched):
        np.testing.assert_allclose(np.asarray(lg_b), np.asarray(lg_s),
                                   rtol=1e-5, atol=1e-5)
        for a, b in zip(jax.tree.leaves(st_s), jax.tree.leaves(st_b)):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       rtol=1e-5, atol=1e-5)
    # rows 1/2 share the 24-token prefix; the store served it in both modes
    assert [r.cached_tokens for r in breqs] == \
        [r.cached_tokens for r in sreqs]
    assert breqs[1].cached_tokens == 24


def test_single_token_budget_emits_exactly_one(params, _reference_rollout):
    """max_new_tokens=1: the first (prefill-argmax) token is the output."""
    pe = PrefillEngine(CFG, params, ECFG, None)
    de = DecodeEngine(CFG, params, ECFG)
    r = Request(rid=0, arrival=0.0, prompt=np.arange(16, dtype=np.int32),
                max_new_tokens=1)
    st, lg = pe.run(r)
    de.insert(r, st, int(jnp.argmax(lg)))
    while de.active:
        de.step()
    assert r.generated == _reference_rollout(params, r.prompt, 1)


def test_batched_prefill_shares_uncached_prefix_within_chunk(params):
    """Two same-chunk requests with the same *not-yet-cached* prefix: the
    first wave computes and publishes it, the second request hits it."""
    from repro.core.kvstore import GlobalKVStore
    pe = PrefillEngine(CFG, params, ECFG, GlobalKVStore(block_size=8))
    rng = np.random.default_rng(4)
    shared = rng.integers(0, 128, 16, dtype=np.int32)
    reqs = [Request(rid=i, arrival=0.0,
                    prompt=np.concatenate(
                        [shared, rng.integers(0, 128, 8, dtype=np.int32)]),
                    max_new_tokens=4) for i in range(2)]
    results = pe.run_batch(reqs)
    assert reqs[0].cached_tokens == 0
    assert reqs[1].cached_tokens == 16          # served by the first wave
    # both states equal the per-request reference
    for req, (st, lg) in zip(reqs, results):
        ref_pe = PrefillEngine(CFG, params, ECFG, None)
        ref = Request(rid=100 + req.rid, arrival=0.0, prompt=req.prompt,
                      max_new_tokens=4)
        st_r, lg_r = ref_pe.run(ref)
        np.testing.assert_allclose(np.asarray(lg), np.asarray(lg_r),
                                   rtol=1e-5, atol=1e-5)
        for a, b in zip(jax.tree.leaves(st_r), jax.tree.leaves(st)):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Orchestrator round trip
# ---------------------------------------------------------------------------

def test_round_trip_matches_reference(params, _reference_rollout):
    """Full fleet (2 prefill + 2 decode, shared store, migration on,
    chunked prefill): every request's greedy decode equals the monolithic
    rollout under the event-driven virtual-clock loop."""
    orch = Orchestrator(CFG, params, OrchestratorConfig(
        hw=TPU_V5E,
        n_prefill=2, n_decode=2, engine=ECFG, chunk_tokens=8))
    reqs = _workload(8, max_new=5)
    s = orch.run(reqs)
    assert s["n_requests"] == 8
    for r in reqs:
        assert r.phase == Phase.DONE
        assert r.generated == _reference_rollout(params, r.prompt,
                                                 r.max_new_tokens), r.rid
    # KV hand-off happened across real instances
    assert all(r.decode_instance is not None for r in reqs)
    assert all(r.prefill_instance is not None for r in reqs)


def test_router_balances_prefill(params):
    """Load-aware routing spreads work over >=2 prefill instances."""
    orch = Orchestrator(CFG, params, OrchestratorConfig(
        hw=TPU_V5E,
        n_prefill=2, n_decode=2, engine=ECFG, migration=False))
    rng = np.random.default_rng(7)
    reqs = [Request(rid=i, arrival=0.0,
                    prompt=rng.integers(0, 128, 32, dtype=np.int32),
                    max_new_tokens=4) for i in range(8)]
    orch.run(reqs)
    counts = {m.name: m.n_prefilled for m in orch.members
              if m.role == ROLE_PREFILL}
    assert len(counts) == 2
    assert all(c >= 2 for c in counts.values()), counts
    assert orch.summary()["prefill_token_skew"] <= 0.6


def test_forced_migration_changes_fleet_and_stays_exact(params):
    """A forced LAYER action re-rolls an instance between roles — including
    evacuating live decode KV — without perturbing any output."""
    orch = Orchestrator(CFG, params, OrchestratorConfig(
        hw=TPU_V5E,
        n_prefill=2, n_decode=2, engine=ECFG, migration=False))
    reqs = _workload(6, seed=9, max_new=8)
    for r in reqs:
        orch.submit(r)
    # advance until decode slots are occupied mid-flight AND the prefill
    # tier is idle (a re-roll refuses members with a batch in flight)
    for _ in range(60):
        orch.step()
        if sum(m.decode.active for m in orch.decode_members()) > 0 and \
                all(not m.busy and m._wavegen is None
                    for m in orch.prefill_members()):
            break
    assert sum(m.decode.active for m in orch.decode_members()) > 0
    before = dict(orch.fleet)

    # force: decode1's role moves onto prefill1 (prefill1 -> decode)
    act = MigrationAction(MigrationKind.LAYER, src="decode1", dst="prefill1",
                          amount=CFG.n_layers, predicted_benefit=1.0,
                          predicted_cost=1e-3)
    assert orch.apply_action(act)
    assert orch.fleet != before
    assert orch.fleet["prefill1"] == ROLE_DECODE
    assert len(orch.decode_members()) == 3

    # force the reverse on a decode member holding live KV: evacuation path
    act2 = MigrationAction(MigrationKind.LAYER, src="prefill0", dst="decode0",
                           amount=CFG.n_layers, predicted_benefit=1.0,
                           predicted_cost=1e-3)
    assert orch.apply_action(act2)
    assert orch.fleet["decode0"] == ROLE_PREFILL
    assert len(orch.migration_log) == 2

    # run to completion: all outputs still token-exact
    while orch.metrics.n_requests < len(reqs):
        orch.step()
    for r in reqs:
        assert r.generated == _single_engine_rollout(params, r), r.rid


def test_floors_prevent_draining_a_role(params):
    orch = Orchestrator(CFG, params, OrchestratorConfig(
        hw=TPU_V5E,
        n_prefill=1, n_decode=1, engine=ECFG, migration=False))
    act = MigrationAction(MigrationKind.LAYER, src="decode0", dst="prefill0",
                          amount=CFG.n_layers, predicted_benefit=1.0,
                          predicted_cost=1e-3)
    assert not orch.apply_action(act)       # would leave zero prefill
    assert orch.fleet == {"prefill0": ROLE_PREFILL, "decode0": ROLE_DECODE}


def test_controller_migrates_under_decode_pressure(params):
    """Decode-heavy load on a 3p/1d fleet makes Algorithm 1 re-roll idle
    prefill capacity into the decode tier — live, not simulated."""
    orch = Orchestrator(CFG, params, OrchestratorConfig(
        hw=TPU_V5E,
        n_prefill=3, n_decode=1, engine=ECFG))
    reqs = _workload(10, seed=5, max_new=10)
    orch.run(reqs)
    assert len(orch.migration_log) >= 1
    assert any(a.kind == MigrationKind.LAYER for a in orch.migration_log)
    assert len(orch.decode_members()) > 1    # fleet composition changed
    for r in reqs:
        assert r.generated == _single_engine_rollout(params, r), r.rid


def test_prefix_aware_baseline_runs_with_private_stores(params):
    """Baseline A/B config: per-instance stores + prefix-aware router."""
    orch = Orchestrator(CFG, params, OrchestratorConfig(
        hw=TPU_V5E,
        n_prefill=2, n_decode=2, router="prefix_aware", global_store=False,
        engine=ECFG, migration=False))
    reqs = _workload(8, seed=11, max_new=4)
    s = orch.run(reqs)
    assert s["n_requests"] == 8
    assert s["router"] == "prefix_aware"
    stores = {id(m.prefill.store) for m in orch.prefill_members()}
    assert len(stores) == 2                  # locality-constrained caches
    for r in reqs:
        assert r.generated == _single_engine_rollout(params, r), r.rid
