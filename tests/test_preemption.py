"""Decode-preemption suite: swap (park KV off-device, resume
bit-identically) and sacrifice (drop KV, re-prefill, adopt) on the live
Orchestrator, plus the analytical simulator's mirror of both.

The load-bearing claim: preemption is INVISIBLE in token space.  A
request that is swapped out or sacrificed mid-decode must finish with
exactly the token stream an uninterrupted run produces — the KV is
either moved bit-for-bit or recomputed from the committed prefix, and
decode resumes from the last committed token.  The seeded property test
hammers that with random interleavings of step / preempt / abort.
"""
import numpy as np
import pytest

from conftest import TINY, TINY_ECFG, assert_pools_restored
from repro.core.analytical import TPU_V5E
from repro.serving.api import Server
from repro.serving.cluster import ClusterSim, SimConfig
from repro.serving.fairshare import SchedulerConfig
from repro.serving.orchestrator import Orchestrator, OrchestratorConfig
from repro.serving.request import Outcome


def _live(tiny_params, **kw):
    return Orchestrator(TINY, tiny_params, OrchestratorConfig(
        hw=TPU_V5E,
        n_prefill=2, n_decode=2, engine=TINY_ECFG, chunk_tokens=8, **kw))


def _reference_tokens(tiny_params, make_workload, **wl_kw):
    """Per-rid token streams of an uninterrupted run over the workload."""
    srv = Server(_live(tiny_params))
    handles = [srv.submit(r, at=r.arrival)
               for r in make_workload(**wl_kw)]
    srv.drain()
    assert all(h.outcome == Outcome.COMPLETED for h in handles)
    return {h.rid: h.tokens for h in handles}


def _decode_resident_rids(orch):
    return [r.rid for u in orch.decode_units() for r in u.slots
            if r is not None]


@pytest.mark.parametrize("mode", ["swap", "sacrifice"])
def test_forced_preemption_is_bit_identical(tiny_params, make_workload,
                                            mode):
    """Preempt every request once mid-decode; the finished streams must
    equal the uninterrupted reference token-for-token."""
    wl_kw = dict(n=5, seed=11, max_new=8)
    ref = _reference_tokens(tiny_params, make_workload, **wl_kw)
    orch = _live(tiny_params)
    srv = Server(orch)
    handles = [srv.submit(r, at=r.arrival)
               for r in make_workload(**wl_kw)]
    hit = set()
    for _ in range(400):
        if not srv.step() and srv.in_flight() == 0:
            break
        for rid in _decode_resident_rids(orch):
            h = srv.handles[rid]
            if rid not in hit and not h.finished and len(h.tokens) >= 2:
                assert orch.preempt(rid, mode)
                hit.add(rid)
                break
    srv.drain()
    assert hit, "no request was ever decode-resident long enough"
    for h in handles:
        assert h.outcome == Outcome.COMPLETED
        assert h.tokens == ref[h.rid], f"rid {h.rid} diverged after {mode}"
    s = srv.summary()
    assert s[f"n_preempted_{mode}"] == len(hit)
    if mode == "swap":
        assert s["pages_swapped"] > 0
        assert orch.swap_io_s > 0
    assert_pools_restored(orch)


def test_preempt_non_resident_rid_refused(tiny_params, make_workload):
    orch = _live(tiny_params)
    srv = Server(orch)
    for r in make_workload(n=2, max_new=4):
        srv.submit(r, at=r.arrival)
    assert not orch.preempt(0, "swap")     # nothing decode-resident yet
    with pytest.raises(ValueError):
        orch.preempt(0, "migrate")         # unknown mode
    with pytest.raises(ValueError):
        orch.preempt(0)                    # no scheduler -> no default
    srv.drain()
    assert srv.summary()["n_preempted_swap"] == 0


@pytest.mark.parametrize("seed", [0, 1])
def test_random_preempt_abort_sequences_restore_pools(
        tiny_params, make_workload, seed):
    """Seeded chaos: random step / preempt(swap|sacrifice) / abort
    interleavings.  Afterwards every pool refcount is restored, aborted
    streams froze on a prefix of the reference, and every survivor is
    bit-identical to the uninterrupted run."""
    wl_kw = dict(n=6, seed=23 + seed, max_new=6)
    ref = _reference_tokens(tiny_params, make_workload, **wl_kw)
    rng = np.random.default_rng(seed)
    orch = _live(tiny_params)
    srv = Server(orch)
    handles = [srv.submit(r, at=r.arrival)
               for r in make_workload(**wl_kw)]
    n_preempts = n_aborts = 0
    for _ in range(500):
        if srv.in_flight() == 0:
            break
        op = rng.random()
        if op < 0.25:
            resident = _decode_resident_rids(orch)
            if resident:
                rid = int(rng.choice(resident))
                mode = ("swap", "sacrifice")[int(rng.integers(2))]
                if srv.handles[rid].tokens and orch.preempt(rid, mode):
                    n_preempts += 1
                continue
        if op < 0.30 and n_aborts < 2:
            live = [h for h in handles if not h.finished]
            if live:
                victim = live[int(rng.integers(len(live)))]
                if victim.cancel():
                    n_aborts += 1
                continue
        srv.step()
    srv.drain()
    s = srv.summary()
    assert s["n_preempted_swap"] + s["n_preempted_sacrifice"] == n_preempts
    assert s["n_aborted"] == n_aborts
    for h in handles:
        if h.outcome == Outcome.ABORTED:
            assert h.tokens == ref[h.rid][:len(h.tokens)]
        else:
            assert h.outcome == Outcome.COMPLETED
            assert h.tokens == ref[h.rid], f"rid {h.rid} diverged"
    assert_pools_restored(orch)


@pytest.mark.parametrize("mode", ["swap", "sacrifice"])
def test_sim_preemption_parks_and_resumes(mode):
    """The analytical simulator mirrors both policies: a preempted slot
    leaves the decode tier (and bills swap bandwidth), the request stays
    in flight while parked, and everything still completes."""
    from repro.serving.workload import WorkloadConfig, generate
    sim = ClusterSim(SimConfig(model=TINY, mode="banaserve"))
    srv = Server(sim, scheduler=SchedulerConfig(preemption=mode))
    reqs = generate(WorkloadConfig(
        kind="synthetic", rps=1e7, n_requests=6, seed=4,
        vocab_size=TINY.vocab_size, max_new_tokens=64,
        prompt_len_lo=16, prompt_len_hi=32, prefix_share=0.0))
    handles = [srv.submit(r, at=r.arrival) for r in reqs]
    hit = False
    for _ in range(300):
        srv.step()
        resident = [s.req.rid for i in sim.instances
                    for s in i.decode_slots]
        if resident and not hit:
            assert sim.preempt(resident[0])   # mode defaults from sched
            hit = True
        if srv.in_flight() == 0:
            break
    assert hit, "no request ever held a sim decode slot"
    srv.drain()
    s = srv.summary()
    assert all(h.outcome == Outcome.COMPLETED for h in handles)
    assert s[f"n_preempted_{mode}"] >= 1
    if mode == "swap":
        assert s["swap_io_s"] > 0
