"""Scenario matrix: bursty, prefill-heavy, decode-heavy and prefix-skewed
traffic (P/D-Serve-style shape coverage), driven through the
backend-agnostic front door (serving/api.py).

Every live scenario asserts (a) token-exactness against the monolithic
greedy reference for every request — streamed through ``StreamHandle``s,
since stream consumption must never perturb state — and (b) that when
the Algorithm 1 controller acted, it reduced the hot-tier utilization gap
it acted on.  The same driver then runs the matrix against the
``ClusterSim`` backend (analytical costs), pinning that the scenario
shapes are expressible on either side of the protocol.  The heavier runs
— bigger matrices and the span-partitioned (decode_split) variants —
carry the ``slow`` marker and run in CI's second job."""
import jax
import pytest

from conftest import TINY, TINY_ECFG, assert_pools_restored
from repro.core.analytical import TPU_V5E
from repro.core.migration import MigrationKind
from repro.serving.api import Server
from repro.serving.cluster import ClusterSim, SimConfig
from repro.serving.orchestrator import Orchestrator, OrchestratorConfig
from repro.serving.request import Outcome, Phase

# name -> (workload overrides, fleet overrides).  rps values are VIRTUAL
# arrivals/s: event costs for the tiny model are ~us-scale, so saturating
# shapes need 1e6–1e8 rps on the virtual clock.  Chunked prefill is on
# everywhere (chunk_tokens) — the whole matrix asserts exactness with
# micro-chunked prompts interleaving decode events.
SCENARIOS = {
    # everything lands at once; routing has to spread a thundering herd
    "bursty": (dict(rps=1e8, prompt_len_lo=12, prompt_len_hi=48,
                    max_new_tokens=4, prefix_share=0.3),
               dict(n_prefill=2, n_decode=2, chunk_tokens=16)),
    # long prompts, short generations: the prefill tier saturates
    "prefill_heavy": (dict(rps=2e6, prompt_len_lo=56, prompt_len_hi=80,
                           max_new_tokens=3, prefix_share=0.2),
                      dict(n_prefill=1, n_decode=2, chunk_tokens=16)),
    # short prompts, long generations: decode slots are the bottleneck
    "decode_heavy": (dict(rps=1e7, prompt_len_lo=8, prompt_len_hi=16,
                          max_new_tokens=10, prefix_share=0.2),
                     dict(n_prefill=3, n_decode=1, chunk_tokens=8)),
    # two hot prefixes dominate: the store + router must not skew load
    "prefix_skewed": (dict(rps=5e6, prompt_len_lo=24, prompt_len_hi=48,
                           max_new_tokens=4, prefix_share=0.95,
                           n_prefix_groups=2, prefix_zipf=2.0),
                      dict(n_prefill=2, n_decode=2, chunk_tokens=16)),
}


@pytest.fixture(autouse=True)
def _per_test_compile_cache():
    """This module is the suite's biggest compile generator: every request
    of every scenario gets an eager greedy-reference rollout, which
    compiles a fresh layer scan per sequence length.  One module's worth
    is enough to hit jaxlib's CPU ``backend_compile`` accumulation
    segfault (see conftest), so clear per *test* here, not per module —
    shared jits recompile lazily on next touch."""
    yield
    jax.clear_caches()


def _drive(backend, reqs):
    """Backend-agnostic scenario driver: open-loop submission through the
    Server front door, streams consumed while the run is in flight."""
    server = Server(backend)
    handles = [server.submit(r, at=r.arrival)
               for r in sorted(reqs, key=lambda r: r.arrival)]
    while server.in_flight():
        server.step()
        for h in handles:
            h.events()        # consuming streams must not perturb state
    server.drain()
    return server, handles


def _scenario_workload(name, make_workload, n_requests, seed):
    wl_kw, fleet_kw = SCENARIOS[name]
    wl_kw = dict(wl_kw)
    max_new = wl_kw.pop("max_new_tokens")
    return make_workload(n_requests, seed=seed, max_new=max_new, **wl_kw), \
        fleet_kw


def _run(name, tiny_params, make_workload, greedy_reference, n_requests,
         seed=13, **fleet_extra):
    reqs, fleet_kw = _scenario_workload(name, make_workload, n_requests,
                                        seed)
    fleet_kw = {**fleet_kw, **fleet_extra}
    orch = Orchestrator(TINY, tiny_params, OrchestratorConfig(
        hw=TPU_V5E,
        engine=TINY_ECFG, **fleet_kw))
    server, handles = _drive(orch, reqs)
    s = server.summary()
    assert s["n_requests"] == n_requests
    for r, h in zip(sorted(reqs, key=lambda r: r.arrival), handles):
        assert r.phase == Phase.DONE
        assert h.outcome == Outcome.COMPLETED
        assert h.tokens == r.generated
        assert r.generated == greedy_reference(TINY, tiny_params, r.prompt,
                                               r.max_new_tokens), \
            (name, r.rid)
    # when the controller acted, the acted-on utilization gap went down
    if orch.control_trace:
        assert s["util_gap_after"] <= s["util_gap_before"] + 1e-9, \
            (name, orch.control_trace)
    # no page leaks: every pool's free list is restored up to the store's
    # refcount-matched holds, across hand-offs, migrations and re-rolls
    assert_pools_restored(orch)
    return orch, s


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_scenario_token_exact_and_balanced(name, tiny_params,
                                           make_workload,
                                           greedy_reference):
    orch, s = _run(name, tiny_params, make_workload, greedy_reference,
                   n_requests=6)
    if name == "decode_heavy":
        # decode pressure on a 3p/1d fleet must trigger Algorithm 1
        assert s["migrations"] >= 1
        assert any(a.kind == MigrationKind.LAYER
                   for a in orch.migration_log)
        assert len(orch.decode_members()) > 1


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_scenario_sim_backend(name, make_workload):
    """The same scenario shapes through the analytical ClusterSim via the
    identical front-door driver: every request completes and the shared
    metrics schema comes out."""
    reqs, _fleet_kw = _scenario_workload(name, make_workload, 8, seed=13)
    sim = ClusterSim(SimConfig(model=TINY, mode="banaserve"))
    server, handles = _drive(sim, reqs)
    s = server.summary()
    assert s["n_requests"] == 8
    for h in handles:
        assert h.outcome == Outcome.COMPLETED
        assert len(h.tokens) == h.request.max_new_tokens
    assert s["throughput_tok_s"] > 0
    assert "p99_ttft_s" in s and "n_submitted" in s


def test_scenario_abort_leaves_no_page_leaks(tiny_params, make_workload,
                                             greedy_reference):
    """Aborts mid-run through the prefix-skewed scenario (shared pages in
    flight): the release_slot path must unref — not blindly free — the
    victim's pages, so survivors stay exact and every pool restores up to
    the store's refcount-matched holds."""
    reqs, fleet_kw = _scenario_workload("prefix_skewed", make_workload,
                                        8, seed=17)
    orch = Orchestrator(TINY, tiny_params, OrchestratorConfig(
        hw=TPU_V5E,
        engine=TINY_ECFG, **fleet_kw))
    server = Server(orch)
    ordered = sorted(reqs, key=lambda r: r.arrival)
    for r in ordered:
        server.submit(r, at=r.arrival)
    victims = {ordered[2].rid, ordered[5].rid}
    aborted = set()
    while server.in_flight():
        server.step()
        for rid in victims - aborted:
            r = next(q for q in reqs if q.rid == rid)
            if r.phase == Phase.DECODE:       # mid-decode: pages resident
                server.abort(rid)
                aborted.add(rid)
    server.drain()
    assert aborted == victims                 # both were caught in flight
    for r in reqs:
        if r.rid in victims:
            assert r.outcome == Outcome.ABORTED
        else:
            assert r.generated == greedy_reference(
                TINY, tiny_params, r.prompt, r.max_new_tokens), r.rid
    assert_pools_restored(orch)


@pytest.mark.parametrize("name", sorted(SCENARIOS))
@pytest.mark.slow
def test_scenario_matrix_large(name, tiny_params, make_workload,
                               greedy_reference):
    """The heavy sweep: more requests, longer generations."""
    _run(name, tiny_params, make_workload, greedy_reference,
         n_requests=14, seed=29)


@pytest.mark.parametrize("name", sorted(SCENARIOS))
@pytest.mark.slow
def test_scenario_matrix_span_fleet(name, tiny_params, make_workload,
                                    greedy_reference):
    """Every traffic shape again on a span-partitioned decode tier
    (decode_split=2): pipelined partial-stack execution must be invisible
    under all of them."""
    wl_kw, fleet_kw = SCENARIOS[name]
    extra = {"decode_split": 2}
    if fleet_kw.get("n_decode", 2) * 2 + fleet_kw.get("n_prefill", 2) > 6:
        extra["n_prefill"] = 2       # keep the fleet small on CPU
    orch, s = _run(name, tiny_params, make_workload, greedy_reference,
                   n_requests=8, seed=31, **extra)
    assert orch.decode_pipes


# -- adversarial multi-tenant mix (the fairshare front door) ----------------

def test_scenario_adversarial_tenant_mix_sim():
    """A long-prompt flood tenant arrives alongside an interactive
    tenant.  Through a FIFO front door, head-of-line blocking collapses
    interactive SLO attainment; behind WFQ + per-tenant budgets + swap
    preemption the interactive tenant stays within 10% of its solo
    attainment and the flood's overflow is REJECTED explicitly."""
    from repro.core import analytical as A
    from repro.models.config import Family, ModelConfig
    from repro.serving.fairshare import SchedulerConfig, TenantPolicy
    from repro.serving.request import SLO
    from repro.serving.workload import (WorkloadConfig, generate,
                                        merge_workloads)

    model = ModelConfig(name="mix7b", family=Family.DENSE, n_layers=32,
                        d_model=4096, n_heads=32, n_kv_heads=32,
                        d_ff=11008, vocab_size=32000)

    def interactive(seed=0):
        return generate(WorkloadConfig(
            kind="synthetic", rps=8.0, n_requests=24, seed=seed,
            max_new_tokens=64, prompt_len_lo=32, prompt_len_hi=128,
            prefix_share=0.0, tenant="interactive"))

    def flood(seed=1):
        return generate(WorkloadConfig(
            kind="synthetic", rps=12.0, n_requests=24, seed=seed,
            max_new_tokens=256, prompt_len_lo=2048, prompt_len_hi=4096,
            prefix_share=0.0, tenant="flood"))

    def run(reqs, sched):
        sim = ClusterSim(SimConfig(model, "banaserve", hw=A.A100_80G,
                                   n_instances=4, decode_batch_max=8,
                                   slo=SLO(ttft_s=1.0, tpot_s=0.1)))
        srv = Server(sim, scheduler=sched)
        for r in reqs:
            srv.submit(r, at=r.arrival)
        srv.backend.drain()
        return srv.summary()

    wfq = SchedulerConfig(
        policy="wfq", srpt_bias=0.25, aging_rate=0.05, preemption="swap",
        tenants={"interactive": TenantPolicy(weight=8.0, priority=1),
                 "flood": TenantPolicy(weight=1.0, priority=0,
                                       max_inflight_requests=8,
                                       max_inflight_tokens=24576)})
    solo = run(interactive(), None)["tenants"]["interactive"]
    s_fifo = run(merge_workloads(interactive(), flood()),
                 SchedulerConfig(policy="fifo"))
    s_wfq = run(merge_workloads(interactive(), flood()), wfq)
    att = lambda s, t: s["tenants"][t]["slo_attainment"] or 0.0
    # WFQ protects the interactive tenant to within 10% of solo...
    assert att(s_wfq, "interactive") >= solo["slo_attainment"] - 0.10
    # ...while plain FIFO demonstrably fails it
    assert att(s_fifo, "interactive") < att(s_wfq, "interactive") - 0.10
    # the flood pays: budget overflow is rejected, residents preempted
    assert s_wfq["tenants"]["flood"]["n_rejected"] > 0
    assert sum(s_wfq["sched_rejections"].values()) \
        == s_wfq["tenants"]["flood"]["n_rejected"]
    assert s_wfq["n_preempted_swap"] >= 1
    # both scenarios expose the per-tenant schema
    for s in (s_fifo, s_wfq):
        assert set(s["tenants"]) == {"interactive", "flood"}
        assert s["scheduler"] in ("fifo", "wfq")


def test_scenario_tenant_metrics_live(tiny_params, make_workload):
    """The live orchestrator exposes the same per-tenant metrics schema:
    a two-tenant mix behind WFQ completes exactly and each tenant's
    slice accounts for its own requests."""
    from repro.serving.fairshare import SchedulerConfig, TenantPolicy

    reqs = make_workload(n=6, max_new=4)
    for i, r in enumerate(reqs):
        r.tenant = "a" if i % 2 else "b"
    orch = Orchestrator(TINY, tiny_params, OrchestratorConfig(
        hw=TPU_V5E,
        engine=TINY_ECFG, n_prefill=2, n_decode=2, chunk_tokens=16))
    server = Server(orch, scheduler=SchedulerConfig(
        policy="wfq", tenants={"a": TenantPolicy(weight=2.0),
                               "b": TenantPolicy(weight=1.0)}))
    handles = [server.submit(r, at=r.arrival) for r in reqs]
    server.drain()
    s = server.summary()
    assert all(h.outcome == Outcome.COMPLETED for h in handles)
    assert set(s["tenants"]) == {"a", "b"}
    assert s["tenants"]["a"]["n_requests"] == 3
    assert s["tenants"]["b"]["n_requests"] == 3
    assert s["scheduler"] == "wfq"
    assert_pools_restored(orch)
