"""Backend-contract suite for the session-oriented front door.

Both serving backends — the live ``Orchestrator`` (real engines, exact
tokens) and the analytical ``ClusterSim`` — sit behind
``serving/api.py``'s ``ServingBackend`` protocol, and this suite pins the
*shared* semantics against both: submit returns a live stream handle,
token/phase events replay committed state in virtual-time order, abort
frees capacity immediately and never perturbs survivors, drain finishes
everything, admission backpressure rejects explicitly at arrival time,
and mid-run (open-loop) submissions are routed on the next dispatch.
Live-only tests additionally pin bit-exactness: a streaming run through
``Server`` equals the batch ``run()`` path token-for-token and
timestamp-for-timestamp, and an abort leaves every surviving stream
unchanged while returning the victim's paged blocks to the free list.
"""
import math

import numpy as np
import pytest

from conftest import TINY, TINY_ECFG, assert_pools_restored
from repro.core.analytical import TPU_V5E
from repro.serving.api import Server
from repro.serving.cluster import ClusterSim, SimConfig
from repro.serving.orchestrator import Orchestrator, OrchestratorConfig
from repro.serving.request import (Metrics, Outcome, Phase, Request, SLO)
from repro.serving.workload import (ClosedLoopClients, WorkloadConfig,
                                    generate)

_PHASE_ORDER = {p: i for i, p in enumerate(Phase)}


def _wl(n, seed=3, max_new=6, rps=1e7, **kw):
    base = dict(kind="synthetic", rps=rps, n_requests=n,
                vocab_size=TINY.vocab_size, max_new_tokens=max_new,
                prefix_share=0.5, n_prefix_groups=2, seed=seed,
                prompt_len_lo=16, prompt_len_hi=40)
    base.update(kw)
    return generate(WorkloadConfig(**base))


@pytest.fixture(params=["live", "sim"])
def make_backend(request, tiny_params):
    """Fresh-backend factory, parametrized over both implementations.
    The sim serves the same tiny config so virtual rps calibrations
    carry over; ``make.kind`` tags backend-specific assertions."""
    kind = request.param

    def make(**kw):
        if kind == "live":
            return Orchestrator(TINY, tiny_params, OrchestratorConfig(
                hw=TPU_V5E,
                n_prefill=2, n_decode=2, engine=TINY_ECFG, chunk_tokens=8,
                **kw))
        return ClusterSim(SimConfig(model=TINY, mode="banaserve",
                                    slo=kw.get("slo")))

    make.kind = kind
    return make


def _assert_stream_wellformed(h):
    """Every handle's drained stream: token events replay the committed
    token ids, phase events move forward only, times are monotone."""
    evs = h.events()
    assert evs, h.rid
    assert evs[-1].kind == h.outcome.value
    # the terminal event closes the stream in time too (clamped past any
    # future-stamped hand-off token)
    if len(evs) > 1 and not math.isnan(evs[-1].t):
        assert evs[-1].t >= evs[-2].t
    toks = [e for e in evs if e.kind == "token"]
    assert [e.token for e in toks] == h.request.generated
    assert [e.index for e in toks] == list(range(len(toks)))
    t_tok = [e.t for e in toks]
    assert t_tok == sorted(t_tok)
    phases = [e.phase for e in evs if e.kind == "phase"]
    assert [_PHASE_ORDER[p] for p in phases] == \
        sorted(_PHASE_ORDER[p] for p in phases)
    t_ph = [e.t for e in evs if e.kind == "phase"]
    assert t_ph == sorted(t_ph)
    # draining again yields nothing new
    assert h.events() == []


# ---------------------------------------------------------------------------
# Shared contract
# ---------------------------------------------------------------------------

def test_contract_submit_stream_drain(make_backend):
    server = Server(make_backend())
    handles = [server.submit(r, at=r.arrival) for r in _wl(5)]
    server.drain()
    assert server.in_flight() == 0
    for h in handles:
        assert h.outcome == Outcome.COMPLETED
        assert h.request.phase == Phase.DONE
        _assert_stream_wellformed(h)
    s = server.summary()
    assert s["n_requests"] == 5 and s["n_submitted"] == 5
    assert s["n_rejected"] == 0 and s["n_aborted"] == 0
    assert server.fleet and all(isinstance(v, str)
                                for v in server.fleet.values())


def test_contract_step_until_horizon(make_backend):
    reqs = _wl(6, rps=1e5)       # spread arrivals out
    server = Server(make_backend())
    for r in reqs:
        server.submit(r, at=r.arrival)
    t_mid = reqs[2].arrival
    server.step_until(t_mid)
    assert server.now <= t_mid           # never ran past the horizon
    assert server.backend.clock          # later work still scheduled
    done_early = {h.rid for h in server.handles.values() if h.finished}
    server.drain()
    assert server.metrics.n_requests == 6
    # the early horizon had completed at most the early arrivals
    assert done_early <= {r.rid for r in reqs}


def test_contract_abort_before_arrival_and_double_cancel(make_backend):
    reqs = _wl(4)
    server = Server(make_backend())
    handles = {r.rid: server.submit(r, at=r.arrival) for r in reqs}
    victim = handles[reqs[1].rid]
    assert victim.cancel()               # still only an arrival event
    assert victim.outcome == Outcome.ABORTED
    assert not victim.cancel()           # terminal: second cancel refused
    server.drain()
    s = server.summary()
    assert s["n_aborted"] == 1 and s["n_requests"] == 3
    assert victim.events()[-1].kind == "aborted"
    for h in handles.values():
        if h is not victim:
            assert h.outcome == Outcome.COMPLETED


def test_contract_abort_mid_decode_frees_slot(make_backend):
    """Cancel a request that holds a decode slot: the slot frees at once
    (the backend serves strictly fewer residents afterwards) and every
    survivor still completes."""
    reqs = _wl(5, max_new=8)
    server = Server(make_backend())
    handles = {r.rid: server.submit(r, at=r.arrival) for r in reqs}
    victim = None
    for _ in range(200):
        server.step()
        victim = next((h for h in handles.values()
                       if not h.finished and len(h.tokens) >= 2), None)
        if victim is not None:
            break
    assert victim is not None, "no request reached mid-decode"
    n_before = len(victim.tokens)
    assert victim.cancel()
    assert victim.outcome == Outcome.ABORTED
    # freed immediately: no backend structure still holds the victim
    backend = server.backend
    if make_backend.kind == "live":
        assert all(victim.request not in u.slots
                   for u in backend.decode_units())
    else:
        assert all(all(s.req is not victim.request
                       for s in i.decode_slots)
                   for i in backend.instances)
    server.drain()
    assert victim.tokens == victim.request.generated[:len(victim.tokens)]
    assert len(victim.request.generated) >= n_before   # stream froze
    _assert_stream_wellformed(victim)   # incl. terminal-time clamp
    s = server.summary()
    assert s["n_aborted"] == 1 and s["n_requests"] == 4
    for h in handles.values():
        if h is not victim:
            assert h.outcome == Outcome.COMPLETED


def test_contract_admission_backpressure(make_backend):
    """A bounded central queue rejects overflow arrivals explicitly:
    outcomes, metrics and the attainment denominator all see them."""
    reqs = _wl(8, rps=1e9, max_new=6)    # a thundering herd
    server = Server(make_backend(), admission_limit=3)
    assert server.admission_limit == 3
    handles = [server.submit(r, at=r.arrival) for r in reqs]
    server.drain()
    s = server.summary()
    assert s["n_rejected"] >= 1
    assert s["n_requests"] + s["n_rejected"] == 8
    assert s["n_submitted"] == 8
    for h in handles:
        assert h.outcome in (Outcome.COMPLETED, Outcome.REJECTED)
        if h.outcome == Outcome.REJECTED:
            assert h.tokens == []
            assert h.events()[-1].kind == "rejected"


def test_contract_late_cancel_is_noop_on_terminal_handles(make_backend):
    """cancel() on a handle that already reached a terminal state —
    REJECTED at admission or COMPLETED after decode — must refuse (return
    False) and record nothing: metrics counters are unchanged and no
    aborted event ever appears on the stream."""
    reqs = _wl(8, rps=1e9, max_new=4)
    server = Server(make_backend(), admission_limit=3)
    handles = [server.submit(r, at=r.arrival) for r in reqs]
    server.drain()
    s0 = server.summary()
    assert s0["n_rejected"] >= 1 and s0["n_aborted"] == 0
    rejected = [h for h in handles if h.outcome == Outcome.REJECTED]
    completed = [h for h in handles if h.outcome == Outcome.COMPLETED]
    assert rejected and completed
    for h in rejected + completed:
        h.events()                           # drain the terminal event
        assert not h.cancel()                # refused, not double-counted
        assert not server.abort(h.rid)       # backend path agrees
        assert h.events() == []              # nothing new on the stream
    s1 = server.summary()
    for k in ("n_requests", "n_rejected", "n_aborted", "n_submitted"):
        assert s1[k] == s0[k], k
    assert all(h.outcome == Outcome.REJECTED for h in rejected)
    assert all(h.outcome == Outcome.COMPLETED for h in completed)
    # tokens survive a refused cancel bit-unchanged
    for h in completed:
        assert h.tokens == list(h.request.generated)


def test_contract_open_loop_submit_mid_run(make_backend):
    """``submit`` after the run has started: the request is routed on the
    next dispatch and completes like any other."""
    reqs = _wl(3)
    server = Server(make_backend())
    for r in reqs:
        server.submit(r, at=r.arrival)
    server.step()                        # the run is now mid-flight
    late = _wl(2, seed=17)
    late_handles = [server.submit(
        Request(rid=100 + r.rid, arrival=0.0, prompt=r.prompt,
                max_new_tokens=r.max_new_tokens)) for r in late]
    for h in late_handles:
        assert h.request.arrival == server.now   # stamped to now
    server.drain()
    assert server.metrics.n_requests == 5
    for h in late_handles:
        assert h.outcome == Outcome.COMPLETED
        assert h.request.prefill_instance is not None
        _assert_stream_wellformed(h)


def test_contract_closed_loop_bounds_concurrency(make_backend):
    """Closed-loop clients keep at most n_clients requests in flight;
    every budgeted request is eventually issued and completed."""
    cfg = WorkloadConfig(kind="synthetic", n_requests=6,
                         vocab_size=TINY.vocab_size, max_new_tokens=4,
                         prefix_share=0.3, n_prefix_groups=2, seed=5,
                         prompt_len_lo=12, prompt_len_hi=24)
    clients = ClosedLoopClients(cfg, n_clients=2)
    server = Server(make_backend())
    for r in clients.initial(server.now):
        server.submit(r)
    while server.in_flight():
        assert server.in_flight() <= 2
        for h in server.step():
            nxt = clients.on_complete(h.request, server.now)
            if nxt is not None:
                server.submit(nxt, at=nxt.arrival)
    assert clients.issued == 6
    assert server.metrics.n_requests == 6


def test_contract_closed_loop_honors_think_time(make_backend):
    """Each follow-up request arrives think_time_s after its trigger, so
    the run's virtual makespan grows with the think time."""
    think = 1.0    # enormous vs the us-scale service times
    cfg = WorkloadConfig(kind="synthetic", n_requests=3,
                         vocab_size=TINY.vocab_size, max_new_tokens=3,
                         seed=5, prefix_share=0.0, prompt_len_lo=12,
                         prompt_len_hi=16)
    clients = ClosedLoopClients(cfg, n_clients=1, think_time_s=think)
    server = Server(make_backend())
    s = server.run_closed_loop(clients)
    assert s["n_requests"] == 3
    # two follow-ups, each preceded by a full think pause
    assert s["total_time_s"] >= 2 * think
    arrivals = sorted(h.request.arrival for h in server.handles.values())
    assert arrivals[1] >= think and arrivals[2] >= 2 * think


def test_contract_closed_loop_survives_rejections(make_backend):
    """A bounded queue rejecting a closed-loop client's request must not
    kill the client: every terminal outcome triggers the next submission
    until the budget is spent."""
    cfg = WorkloadConfig(kind="synthetic", n_requests=8,
                         vocab_size=TINY.vocab_size, max_new_tokens=3,
                         seed=7, prefix_share=0.0, prompt_len_lo=12,
                         prompt_len_hi=16)
    clients = ClosedLoopClients(cfg, n_clients=4)
    server = Server(make_backend(), admission_limit=2)
    s = server.run_closed_loop(clients)
    assert clients.issued == 8                     # budget fully spent
    assert s["n_rejected"] >= 1                    # the bound really bit
    assert s["n_requests"] + s["n_rejected"] == 8


def test_attainment_denominator_is_explicit():
    """Rejected requests are SLO misses; aborted ones are excluded."""
    m = Metrics(slo=SLO(ttft_s=1.0, tpot_s=1.0))
    for rid in (1, 2):
        r = Request(rid=rid, arrival=0.0,
                    prompt=np.arange(4, dtype=np.int32), max_new_tokens=2)
        r.generated = [0, 0]
        r.t_tokens = [0.5, 1.0]
        r.t_first_token, r.t_done = 0.5, 1.0
        m.record(r)
    rej = Request(rid=3, arrival=0.0, prompt=np.arange(4, dtype=np.int32),
                  max_new_tokens=2)
    m.record_rejected(rej)
    ab = Request(rid=4, arrival=0.0, prompt=np.arange(4, dtype=np.int32),
                 max_new_tokens=2)
    m.record_aborted(ab)
    s = m.summary()
    assert rej.outcome == Outcome.REJECTED
    assert ab.outcome == Outcome.ABORTED
    assert s["n_submitted"] == 4
    # 2 attained of (2 completed + 1 rejected); the abort doesn't count
    assert s["slo_attainment"] == pytest.approx(2 / 3)


# ---------------------------------------------------------------------------
# Live-only: bit-exactness of the streaming surface
# ---------------------------------------------------------------------------

def _fresh_orch(tiny_params, **kw):
    return Orchestrator(TINY, tiny_params, OrchestratorConfig(
        hw=TPU_V5E,
        n_prefill=2, n_decode=2, engine=TINY_ECFG, chunk_tokens=8, **kw))


def test_streaming_server_equals_batch_run(tiny_params):
    """The acceptance pin: a streaming run through ``Server`` yields
    token streams AND virtual timestamps bit-identical to the batch
    ``run()`` path, and the summaries agree."""
    slo = SLO(ttft_s=5e-6, tpot_s=2e-6)
    reqs_a = _wl(6, max_new=6)
    s_a = _fresh_orch(tiny_params, slo=slo).run(reqs_a)

    reqs_b = _wl(6, max_new=6)
    server = Server(_fresh_orch(tiny_params, slo=slo))
    handles = [server.submit(r, at=r.arrival) for r in reqs_b]
    # consume streams WHILE running — consumption must not perturb state
    while server.in_flight():
        server.step()
        for h in handles:
            h.events()
    server.drain()            # mop up trailing control events, like run()
    s_b = server.summary()
    assert [r.generated for r in reqs_a] == [r.generated for r in reqs_b]
    assert [r.t_tokens for r in reqs_a] == [r.t_tokens for r in reqs_b]
    assert s_a == s_b


def test_live_abort_mid_decode_survivors_bit_exact(tiny_params):
    """Abort one stream mid-decode: every surviving stream is
    token-identical to the uncancelled reference run, and the victim's
    paged blocks are all back on the free lists afterwards."""
    ref = _wl(5, seed=9, max_new=8)
    _fresh_orch(tiny_params, migration=False).run(ref)

    reqs = _wl(5, seed=9, max_new=8)
    orch = _fresh_orch(tiny_params, migration=False)
    server = Server(orch)
    handles = {r.rid: server.submit(r, at=r.arrival) for r in reqs}
    victim = None
    for _ in range(200):
        server.step()
        victim = next((h for h in handles.values()
                       if not h.finished and len(h.tokens) >= 3), None)
        if victim is not None:
            break
    assert victim is not None
    assert victim.cancel()
    server.drain()
    by_rid = {r.rid: r for r in ref}
    for r in reqs:
        if r.rid != victim.rid:
            assert r.generated == by_rid[r.rid].generated, r.rid
        else:   # the victim's committed prefix is a prefix of the ref
            n = len(r.generated)
            assert r.generated == by_rid[r.rid].generated[:n]
            assert n < len(by_rid[r.rid].generated)
    # every paged page is back on a free list or held by the store with a
    # matching refcount, every slot empty
    assert_pools_restored(orch)


def test_live_abort_mid_prefill_dropped_at_handoff(tiny_params):
    """Abort while the request is inside a chunked prefill batch: its KV
    is dropped at hand-off (no decode slot is ever taken) and its
    batch-mates stay bit-exact."""
    ref = _wl(3, seed=21, max_new=5, prompt_len_lo=56, prompt_len_hi=64)
    _fresh_orch(tiny_params, migration=False).run(ref)

    reqs = _wl(3, seed=21, max_new=5, prompt_len_lo=56, prompt_len_hi=64)
    orch = _fresh_orch(tiny_params, migration=False)
    server = Server(orch)
    handles = {r.rid: server.submit(r, at=r.arrival) for r in reqs}
    victim = None
    for _ in range(100):
        server.step()
        for m in orch.prefill_members():
            for r in m._batch:
                if r.outcome is None and not r.generated:
                    victim = handles[r.rid]
                    break
            if victim:
                break
        if victim:
            break
    assert victim is not None, "no request observed mid-prefill"
    assert victim.cancel()
    server.drain()
    assert victim.outcome == Outcome.ABORTED
    assert victim.tokens == []                 # never reached decode
    assert victim.request.decode_instance is None
    by_rid = {r.rid: r for r in ref}
    for r in reqs:
        if r.rid != victim.rid:
            assert r.generated == by_rid[r.rid].generated, r.rid
    s = server.summary()
    assert s["n_aborted"] == 1 and s["n_requests"] == 2


def test_sim_server_run_equals_legacy_run():
    """Legacy ``ClusterSim.run()`` (constructor workload) and a streaming
    ``Server.run`` over the same requests produce one summary."""
    wl = WorkloadConfig(kind="synthetic", rps=1e6, n_requests=12,
                        vocab_size=TINY.vocab_size, max_new_tokens=8,
                        seed=2, prompt_len_lo=16, prompt_len_hi=40)
    cfg = SimConfig(model=TINY, mode="banaserve")
    s_a = ClusterSim(cfg, wl).run()
    s_b = Server(ClusterSim(cfg)).run(generate(wl))
    for k, v in s_a.items():
        if isinstance(v, float) and math.isnan(v):
            assert math.isnan(s_b[k]), k
        else:
            assert s_b[k] == v, k
