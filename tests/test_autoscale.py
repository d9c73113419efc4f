"""SLO-driven autoscaling over heterogeneous fleets (serving/autoscale.py).

Covers the policy layer (pure-signal unit tests), warm-up billing on the
virtual clock, the diurnal elastic-vs-static A/B on the cluster
simulator, token-bit-identical drain-down on the live orchestrator,
heterogeneous hardware billing consistency across both backends,
preemption-aware decode placement, and the NaN-free guarantees of the
fleet/utilization timelines."""
import dataclasses
import json
import math

import pytest

from repro.core import analytical as A
from repro.serving.autoscale import (AutoscaleConfig, FleetSignals,
                                     SLOAutoscaler, TierSignals,
                                     pick_profile)
from repro.serving.cluster import ClusterSim, SimConfig
from repro.serving.request import SLO, Metrics
from repro.serving import workload as W
from repro.serving.api import Server
from repro.serving.fairshare import SchedulerConfig, TenantPolicy
from repro.models.config import Family, ModelConfig

SIM_MODEL = ModelConfig(name="as-13b", family=Family.DENSE, n_layers=40,
                        d_model=5120, n_heads=40, n_kv_heads=40,
                        d_ff=13824, vocab_size=32000)
SLO_ = SLO(ttft_s=1.0, tpot_s=0.1)


def _tier(n_active=2, n_warming=0, n_draining=0, util=0.5,
          queue_delay_s=0.0, backlog=0):
    return TierSignals(n_active, n_warming, n_draining, util,
                       queue_delay_s, backlog)


def _sig(t=100.0, prefill=None, decode=None, attainment=0.95):
    return FleetSignals(t, prefill or _tier(), decode or _tier(),
                        slo_attainment=attainment)


def _mk(**kw):
    asc = SLOAutoscaler(AutoscaleConfig(**kw))
    asc._last_tick = -math.inf
    return asc


# ---------------------------------------------------------------------------
# Policy unit tests
# ---------------------------------------------------------------------------

def test_policy_scales_up_proportionally_to_delay():
    asc = _mk(target_delay_s=1.0, step_max=8, max_decode=16)
    out = asc.plan(_sig(decode=_tier(n_active=2, queue_delay_s=4.0,
                                     backlog=10, util=1.0)))
    (d,) = out
    assert d.role == "decode"
    # 4s of backlog at 1s target over 2 active -> ~6 more instances
    assert d.delta == 6


def test_policy_warming_capacity_discounts_the_delay():
    """A burst must not double-order: capacity already warming absorbs
    its share of the modelled delay."""
    asc = _mk(target_delay_s=1.0, step_max=8, cooldown_s=0.0)
    out = asc.plan(_sig(decode=_tier(n_active=2, n_warming=6,
                                     queue_delay_s=4.0, backlog=10,
                                     util=1.0)))
    assert out == []   # 4s * 2/(2+6) = 1s -> already at target


def test_policy_high_util_orders_one_ahead_of_backlog():
    asc = _mk(high_util=0.9)
    out = asc.plan(_sig(prefill=_tier(util=0.95, backlog=0)))
    (d,) = out
    assert (d.role, d.delta) == ("prefill", +1)
    assert "hot" in d.reason


def test_policy_scale_down_gated_on_idle_and_attainment():
    # idle + attaining -> drain one
    asc = _mk(low_util=0.3, min_attainment=0.9)
    (d,) = asc.plan(_sig(decode=_tier(n_active=3, util=0.1)))
    assert (d.role, d.delta) == ("decode", -1)
    # same tier but attainment below the gate -> hold
    asc = _mk(low_util=0.3, min_attainment=0.9)
    assert asc.plan(_sig(decode=_tier(n_active=3, util=0.1),
                         attainment=0.5)) == []
    # never below the floor
    asc = _mk(min_decode=1)
    assert asc.plan(_sig(decode=_tier(n_active=1, util=0.0))) == []


def test_policy_cooldown_and_interval_rate_limit():
    asc = _mk(interval_s=2.0, cooldown_s=10.0)
    sig = lambda t: _sig(t=t, decode=_tier(n_active=2, queue_delay_s=9.0,
                                           backlog=5, util=1.0))
    assert asc.plan(sig(0.0))            # first decision lands
    assert asc.plan(sig(1.0)) == []      # within interval
    assert asc.plan(sig(4.0)) == []      # past interval, within cooldown
    assert asc.plan(sig(11.0))           # cooldown expired


def test_pick_profile_matches_tier_to_roofline():
    flop = A.HardwareProfile("flopzilla", 500e12, 1000e9, 64 << 30,
                             50e9, 16e9)
    bw = A.HardwareProfile("bwmonster", 200e12, 3000e9, 64 << 30,
                           50e9, 16e9)
    assert pick_profile("prefill", (flop, bw)) is flop
    assert pick_profile("decode", (flop, bw)) is bw
    assert pick_profile("decode", None) is None


# ---------------------------------------------------------------------------
# Warm-up billing
# ---------------------------------------------------------------------------

def test_instance_warmup_time_is_weight_load_plus_jit():
    t = A.instance_warmup_time(SIM_MODEL, A.TPU_V5E, jit_compile_s=2.0)
    expect = SIM_MODEL.param_count() * 2 / A.TPU_V5E.host_bw + 2.0
    assert t == pytest.approx(expect)
    # a part with faster host DMA warms up strictly faster
    assert (A.instance_warmup_time(SIM_MODEL, A.TPU_V5P)
            < A.instance_warmup_time(SIM_MODEL, A.TPU_V4))


def test_sim_scale_up_bills_warmup_before_serving():
    scfg = dataclasses.replace(
        SimConfig.preset(SIM_MODEL, "banaserve", n_instances=2), slo=SLO_)
    sim = ClusterSim(scfg)
    srv = Server(sim, autoscaler=AutoscaleConfig())
    name = sim._scale_up("decode", A.TPU_V5P)
    sim._record_fleet()          # what _autoscale_tick does after planning
    inst = sim.by_name[name]
    warmup = A.instance_warmup_time(SIM_MODEL, A.TPU_V5P,
                                    jit_compile_s=2.0)
    assert inst.warming_until == pytest.approx(sim.now + warmup)
    assert inst.hw is A.TPU_V5P
    assert inst not in sim._decode_candidates()   # no traffic while warming
    # the ordered instance is billed from t=0: the fleet timeline already
    # counts it under "warming"
    assert sim.metrics.fleet_timeline[-1][1]["warming"] == 1
    srv.backend.step_until(inst.warming_until + 1e-6)
    assert inst in sim._decode_candidates()
    last = sim.metrics.fleet_timeline[-1][1]
    assert last.get("warming", 0) == 0 and last["decode"] == 2


# ---------------------------------------------------------------------------
# The diurnal elastic-vs-static A/B (acceptance scenario, shrunk)
# ---------------------------------------------------------------------------

def _diurnal(n, seed=0):
    return W.generate(W.WorkloadConfig(
        kind="synthetic", rps=40.0, n_requests=n, seed=seed,
        rate_schedule=W.diurnal_schedule(120.0, 3.0, 40.0),
        max_new_tokens=96, prompt_len_lo=256, prompt_len_hi=1024,
        prefix_share=0.0))


def _arm(n_requests, n_instances, autoscale):
    scfg = dataclasses.replace(
        SimConfig.preset(SIM_MODEL, "banaserve", n_instances=n_instances),
        decode_batch_max=8, slo=SLO_)
    asc = None
    if autoscale:
        asc = AutoscaleConfig(target_delay_s=0.3, low_util=0.3,
                              high_util=0.85, interval_s=2.0,
                              cooldown_s=4.0, max_prefill=12,
                              max_decode=12, step_max=4)
    srv = Server(ClusterSim(scfg), autoscaler=asc)
    for r in _diurnal(n_requests):
        srv.submit(r, at=r.arrival)
    srv.backend.drain()
    return srv.summary()


def test_diurnal_autoscale_matches_peak_at_lower_cost():
    n = 1200
    peak = _arm(n, 12, False)
    trough = _arm(n, 4, False)
    auto = _arm(n, 4, True)
    assert auto["n_requests"] == n            # drain-down loses nothing
    # within 5% of the peak-provisioned bar ...
    assert auto["slo_attainment"] >= peak["slo_attainment"] - 0.05
    # ... at >= 30% fewer instance-seconds (static arms: exact n x span)
    peak_secs = 12 * peak["total_time_s"]
    assert auto["instance_seconds"] <= 0.70 * peak_secs
    # ... and strictly better than trough-provisioned
    assert auto["slo_attainment"] > trough["slo_attainment"]
    # the fleet actually breathed: grew past trough, shrank back
    assert auto["fleet_peak"] > 4
    assert auto["n_retired"] > 0


# ---------------------------------------------------------------------------
# Heterogeneous billing consistency
# ---------------------------------------------------------------------------

def test_faster_profile_strictly_lowers_modelled_times():
    for L in (128, 1024):
        assert (A.prefill_time(SIM_MODEL, L, A.TPU_V5P)
                < A.prefill_time(SIM_MODEL, L, A.TPU_V5E))
    assert (A.decode_iter_time(SIM_MODEL, 512, A.TPU_V5P, batch=8)
            < A.decode_iter_time(SIM_MODEL, 512, A.TPU_V5E, batch=8))


def test_sim_bills_per_instance_profiles():
    """Two single-instance fleets, identical workload: the v5p fleet
    finishes strictly sooner because every cost is billed on its part."""
    def run(hw):
        scfg = dataclasses.replace(
            SimConfig.preset(SIM_MODEL, "vllm", n_instances=1),
            hw=hw, slo=SLO_)
        srv = Server(ClusterSim(scfg))
        for r in W.generate(W.WorkloadConfig(
                kind="synthetic", rps=4.0, n_requests=40, seed=1,
                max_new_tokens=32, prompt_len_lo=128, prompt_len_hi=512)):
            srv.submit(r, at=r.arrival)
        srv.backend.drain()
        return srv.summary()

    fast, slow = run(A.TPU_V5P), run(A.TPU_V5E)
    assert fast["n_requests"] == slow["n_requests"] == 40
    assert fast["mean_ttft_s"] < slow["mean_ttft_s"]
    assert fast["mean_tpot_s"] < slow["mean_tpot_s"]


def test_sim_cycles_heterogeneous_profiles_over_fleet():
    scfg = dataclasses.replace(
        SimConfig.preset(SIM_MODEL, "distserve", n_instances=4),
        profiles=(A.TPU_V5P, A.TPU_V5E))
    sim = ClusterSim(scfg)
    assert [i.hw.name for i in sim.instances] == [
        "tpu_v5p", "tpu_v5e", "tpu_v5p", "tpu_v5e"]


def test_router_sees_and_exploits_per_part_queue_delay():
    """The load-aware router routes by modelled queue delay, which is
    priced on each instance's own roofline — so under sustained load the
    faster prefill part absorbs far more than an equal share of work."""
    scfg = dataclasses.replace(
        SimConfig.preset(SIM_MODEL, "distserve", n_instances=4,
                         hw=A.TPU_V5E),
        profiles=(A.TPU_V5P, A.TPU_V5E, A.TPU_V5E, A.TPU_V5E),
        router="load_aware", decode_batch_max=16)
    sim = ClusterSim(scfg)
    srv = Server(sim)
    for r in W.generate(W.WorkloadConfig(
            kind="synthetic", rps=30.0, n_requests=150, seed=2,
            max_new_tokens=16, prompt_len_lo=512, prompt_len_hi=1024)):
        srv.submit(r, at=r.arrival)
    srv.backend.drain()
    fast = next(i for i in sim.instances if i.hw is A.TPU_V5P)
    slow = next(i for i in sim.instances
                if i.hw is A.TPU_V5E and i.prefill_cap > 0)
    # equal-share routing would leave work_p(v5p) ~ work_p(v5e) / 2.3;
    # queue-delay routing keeps the fast part at least as busy
    assert fast.work_p > 0.8 * slow.work_p


# ---------------------------------------------------------------------------
# Preemption-aware decode placement
# ---------------------------------------------------------------------------

def _preempt_arm(penalty: float):
    scfg = dataclasses.replace(
        SimConfig.preset(SIM_MODEL, "distserve", n_instances=3,
                         hw=A.TPU_V5E),
        prefill_fraction=0.34, decode_batch_max=2,
        profiles=(A.A100_80G, A.TPU_V5P, A.TPU_V5E),
        preempt_penalty=penalty, slo=SLO_)
    sched = SchedulerConfig(
        policy="fifo", preemption="swap",
        tenants={"hi": TenantPolicy(priority=1),
                 "lo": TenantPolicy(priority=0)})
    srv = Server(ClusterSim(scfg), scheduler=sched)
    # three long-lived low-priority residents: the fast part fills both
    # its slots, the slow part keeps one open — the placement choice the
    # penalty is about (risk-blind ranks the fast-but-full part first)
    lo = W.generate(W.WorkloadConfig(
        kind="synthetic", rps=50.0, n_requests=3, seed=3, tenant="lo",
        max_new_tokens=512, prompt_len_lo=64, prompt_len_hi=128))
    for r in lo:                       # pin long residencies (the
        r.max_new_tokens = 800         # generator draws [16, max] uniform)
    hi = W.generate(W.WorkloadConfig(
        kind="synthetic", rps=1.0, n_requests=6, seed=4, tenant="hi",
        max_new_tokens=16, prompt_len_lo=64, prompt_len_hi=128))
    for r in hi:
        r.max_new_tokens = 16
    for r in W.merge_workloads(lo, hi):
        srv.submit(r, at=r.arrival)
    srv.backend.drain()
    return srv.summary()


def test_preempt_penalty_avoids_evictions_at_equal_attainment():
    blind = _preempt_arm(0.0)
    aware = _preempt_arm(1.0)
    n_blind = blind["n_preempted_swap"] + blind["n_preempted_sacrifice"]
    n_aware = aware["n_preempted_swap"] + aware["n_preempted_sacrifice"]
    # risk-blind ranking lands high-priority work on the fast-but-full
    # part and evicts residents; the penalty prefers any open slot
    assert n_blind > n_aware
    hi_aware = aware["tenants"]["hi"]["slo_attainment"]
    hi_blind = blind["tenants"]["hi"]["slo_attainment"]
    assert hi_aware >= hi_blind - 1e-9


# ---------------------------------------------------------------------------
# Metrics timelines: NaN-free under empty fleets / zero traffic / retirement
# ---------------------------------------------------------------------------

def test_metrics_timelines_empty_and_zero_traffic():
    m = Metrics()
    s = m.summary()
    assert m.instance_seconds() == 0.0
    assert "instance_seconds" not in s          # static fleets unchanged
    assert s["mean_instance_util"] is None      # None, never NaN
    # zero-traffic windows: empty util samples are legal and stay NaN-free
    m.record_util(1.0, {})
    m.record_util(2.0, {"a": 0.0})
    s = m.summary()
    assert s["mean_instance_util"] == 0.0
    assert not math.isnan(s["mean_instance_util"])


def test_metrics_fleet_timeline_integral_with_mid_run_retirement():
    m = Metrics()
    m.record_fleet(0.0, {"prefill": 1, "decode": 1})
    m.record_fleet(10.0, {"prefill": 1, "decode": 1, "warming": 1})
    m.record_fleet(12.0, {"prefill": 1, "decode": 2})   # warmed
    m.record_fleet(20.0, {"prefill": 1, "decode": 1})   # retired mid-run
    m.t_end = 30.0
    # 2*10 + 3*2 + 3*8 + 2*10 = 70
    assert m.instance_seconds() == pytest.approx(70.0)
    s = m.summary()
    assert s["fleet_peak"] == 3 and s["fleet_min"] == 2
    assert s["n_scale_events"] == 3
    # duplicate consecutive snapshots are dropped
    m.record_fleet(25.0, {"prefill": 1, "decode": 1})
    assert len(m.fleet_timeline) == 4


def test_sim_autoscaled_summary_is_nan_free_json():
    s = _arm(150, 2, True)
    # every elasticity metric must survive strict JSON (no NaN/inf)
    elastic = {k: s[k] for k in
               ("instance_seconds", "fleet_peak", "fleet_min",
                "fleet_mean", "n_scale_events", "mean_instance_util",
                "autoscale_decisions", "n_retired")}
    json.dumps(elastic, allow_nan=False)


# ---------------------------------------------------------------------------
# Scale: 10^5 requests over hundreds of instances (slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_cluster_sim_scale_smoke_events_per_second():
    import time
    scfg = dataclasses.replace(
        SimConfig.preset(SIM_MODEL, "banaserve", n_instances=200),
        decode_batch_max=8, slo=SLO_, control_interval=1.0)
    srv = Server(ClusterSim(scfg))
    for r in W.generate(W.WorkloadConfig(
            kind="synthetic", rps=600.0, n_requests=100_000, seed=5,
            max_new_tokens=16, prompt_len_lo=64, prompt_len_hi=256,
            prefix_share=0.0)):
        srv.submit(r, at=r.arrival)
    t0 = time.process_time()      # CPU time: immune to co-tenant noise
    srv.backend.drain()
    cpu = time.process_time() - t0
    s = srv.summary()
    assert s["n_requests"] == 100_000
    rate = srv.backend.clock.n_processed / max(cpu, 1e-9)
    # regression floor for the event loop's hot path.  An unloaded dev
    # core clears ~24k events/s after the O(fleet)-rescan fixes (cached
    # tier caps / candidate lists, incremental queued-work); the code
    # those fixes replaced managed ~8.7k, so 12k catches that class of
    # regression while leaving ~2x headroom for slower CI hardware.
    assert rate > 12_000, f"{rate:.0f} events/s"


# ---------------------------------------------------------------------------
# live orchestrator: scale-down drains with zero token divergence
# ---------------------------------------------------------------------------

def test_live_scale_down_drain_is_token_bit_identical(tiny_params,
                                                      make_workload):
    """Acceptance: drain-down moves decode residents via extract/adopt,
    so every request finishes with exactly the token stream an untouched
    fleet produces — scaling events are invisible in token space."""
    from conftest import TINY, TINY_ECFG
    from repro.serving.orchestrator import Orchestrator, OrchestratorConfig
    from repro.serving.request import Outcome

    wl_kw = dict(n=6, seed=13, max_new=10)

    def fleet():
        return Orchestrator(TINY, tiny_params, OrchestratorConfig(
            hw=A.TPU_V5E,
            n_prefill=1, n_decode=2, engine=TINY_ECFG, chunk_tokens=8))

    ref_srv = Server(fleet())
    ref_handles = [ref_srv.submit(r, at=r.arrival)
                   for r in make_workload(**wl_kw)]
    ref_srv.drain()
    assert all(h.outcome == Outcome.COMPLETED for h in ref_handles)
    ref = {h.rid: h.tokens for h in ref_handles}

    orch = fleet()
    srv = Server(orch)
    handles = [srv.submit(r, at=r.arrival) for r in make_workload(**wl_kw)]
    # spawn an extra decode member on a faster profile: warm-up is billed
    # on the virtual clock, so it must NOT be serving immediately
    name = orch._scale_up("decode", A.TPU_V5P)
    assert name is not None
    spawned = orch._by_name[name]
    assert spawned.warming_until > orch.clock.now
    drained = False
    for _ in range(800):
        alive = srv.step()
        if not drained and any(u.active for u in orch.decode_units()):
            drained = orch._scale_down("decode")   # mid-decode drain
        if not alive and srv.in_flight() == 0:
            break
    srv.drain()
    assert drained, "scale-down never engaged"
    assert orch.retired, "drained member failed to retire"
    assert all(h.outcome == Outcome.COMPLETED for h in handles)
    assert {h.rid: h.tokens for h in handles} == ref
