"""Live disaggregated orchestrator: an event-driven virtual-clock loop
over real engines.

This is the executable counterpart of the discrete-event simulator
(``serving/cluster.py``) — and since this refactor the two share the same
substrate: a ``serving/clock.py`` ``VirtualClock`` (heap event queue +
virtual ``now``) drives a fleet of ``PrefillEngine`` / ``DecodeEngine``
instances over the *real* JAX model.  Tokens are exact (every forward
really runs); *time* is virtual — each event's duration is charged from
the §4.3 analytical model (``core/analytical.py``) for the real batch
shapes the engines executed, so TTFT/TPOT/goodput and SLO attainment are
well-defined, deterministic under a fixed workload seed, and directly
comparable with the simulator's (one summary schema, see docs/serving.md).

Event loop (each instance steps independently when it has work):

* ``arrival`` — a workload request reaches the central queue at its
  Poisson timestamp; Algorithm 2 (§4.4.2) routes the queue over live
  ``InstanceLoad`` snapshots (now queue-delay-aware: the router minimizes
  modelled backlog seconds, not just utilization).
* ``prefill`` / ``prefill_done`` — an idle prefill member picks up to
  ``prefill_chunk`` requests (admission-controlled by *reserved* decode
  slots) and runs ONE dense prefill wave per event.  With
  ``chunk_tokens`` set, long prompts split into successive partial-prefill
  micro-chunks (KV accumulated across waves, exactness preserved — the
  DynaServe insight), so decode events interleave with a long prefill in
  virtual time instead of stalling behind it.
* ``decode_kick`` / ``decode_done`` — a decode unit (engine or span
  pipeline) runs one continuous-batching iteration per event; completed
  hand-offs kick it after their §4.2 overlapped transfer latency.
* ``control`` — every ``control_interval`` virtual seconds (not step
  counts) the Algorithm 1 controller (§4.4.1) plans over per-member
  ``DeviceLoad``s: LAYER actions between adjacent span stages move
  boundary layers live; between full-stack members they re-roll roles
  (Fig. 3); KV_HEADS actions rebalance in-flight KV between decode units.

Every hand-off and migration is exact pytree surgery (``models.kvcache``),
so orchestrated greedy decode is token-identical to a single-engine
rollout — asserted by tests/test_orchestrator.py, the tests/test_scenarios
matrix (with chunked prefill on), and examples/serve_disaggregated.py.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Deque, Dict, List, Optional, Sequence, Set

import jax
import jax.numpy as jnp
import numpy as np

from ..core import analytical as A
from ..core.kvstore import GlobalKVStore, chain_hashes, leading_block_key
from ..core.layer_migration import even_spans
from ..core.migration import (ControllerConfig, DeviceLoad, MigrationAction,
                              MigrationController, MigrationKind)
from ..core.scheduling import (LoadAwareRouter, PrefixAwareRouter,
                               RequestInfo, RoundRobinRouter,
                               live_instance_loads, utilization_gap)
from ..models import kvcache as KC
from ..models.config import ModelConfig
from . import tracing
from .api import BackendBase
from .clock import VirtualClock
from .engine import DecodeEngine, EngineConfig, PrefillEngine
from .request import SLO, Metrics, Phase, Request
from .span import DecodePipeline

ROLE_PREFILL = "prefill"
ROLE_DECODE = "decode"


def _make_router(name: str):
    if name == "load_aware":
        return LoadAwareRouter()
    if name == "prefix_aware":
        return PrefixAwareRouter()
    if name == "round_robin":
        return RoundRobinRouter()
    raise ValueError(f"unknown router {name!r}")


@dataclasses.dataclass(frozen=True)
class OrchestratorConfig:
    n_prefill: int = 2
    n_decode: int = 2
    router: str = "load_aware"     # load_aware | prefix_aware | round_robin
    global_store: bool = True      # shared store vs per-instance caches
    # zero-copy prefix sharing: store entries point at live decode-pool
    # pages (refcounted, COW) and hand-offs bind cached prefixes by
    # reference.  False falls back to the payload-copy store everywhere
    # (the A/B arm of benchmarks/bench_prefix_reuse.py).
    prefix_sharing: bool = True
    engine: EngineConfig = EngineConfig()
    migration: bool = True
    # Algorithm 1 cadence in VIRTUAL SECONDS (the clock interval, not a
    # step count); None derives ~2 decode iterations for the fleet's model
    # and hardware, so the controller keeps pace at any model scale
    control_interval: Optional[float] = None
    controller: ControllerConfig = ControllerConfig(
        delta_up=0.5, delta_down=0.25, rho=0.5, max_actions_per_cycle=2)
    # the part the virtual clock bills; None = the part the fleet runs on
    # (``analytical.device_profile`` of device 0, which every member
    # shares).  CPU runs model a part and must name it.
    hw: Optional[A.HardwareProfile] = None
    # heterogeneous fleets: per-member profiles cycled over the initial
    # fleet (prefill members first, then decode).  None = homogeneous
    # ``hw``.  Each member's event costs, store-fetch overlap and
    # queue-delay reports are billed on its OWN part, so the router and
    # the autoscaler see (and exploit) the speed difference.  Span
    # pipelines stay on the fleet default (one pipeline = one part).
    hw_profiles: Optional[tuple] = None
    prefill_chunk: int = 4         # max requests per prefill batch
    # chunked prefill: max prompt tokens one row computes per wave (None =
    # one-shot).  Smaller chunks -> decode interleaves sooner behind long
    # prompts; exactness is preserved at any value.
    chunk_tokens: Optional[int] = None
    min_prefill: int = 1           # role floors: the serving path must exist
    min_decode: int = 1
    # layer-span partitioning of the decode tier: each of the n_decode
    # logical decode instances becomes a pipeline of this many span stages
    # (one fleet member per stage).  LAYER actions between adjacent stages
    # move boundary layers instead of re-rolling whole instances.
    decode_split: int = 1
    slo: Optional[SLO] = None      # TTFT/TPOT targets for goodput accounting
    efficiency: float = 0.5        # prefill MFU for event costs (Eq. 20)


class _Member:
    """One fleet slot: a named device currently playing one role.

    Exactly one of ``prefill``/``decode`` is live; a re-roll swaps them.
    A member may also be one *stage* of a span-partitioned decode pipeline
    (``pipe``/``stage`` set): it then hosts a partial-stack engine and
    LAYER migrations re-slice its span rather than its role.
    Token counters live here (not on the engine) so they survive re-rolls.
    """

    def __init__(self, name: str, role: str,
                 hw: Optional[A.HardwareProfile] = None):
        self.name = name
        self.role = role
        self.hw = hw                   # this part's roofline (None = fleet)
        self.warming_until = 0.0       # autoscaled: no traffic before
        self.draining = False          # autoscaled: no NEW work; retires
        self.prefill: Optional[PrefillEngine] = None
        self.decode: Optional[DecodeEngine] = None
        self.pipe: Optional[DecodePipeline] = None
        self.stage: int = 0
        self.rerolled = False          # role changed at least once
        self.tokens_prefilled = 0
        self.n_prefilled = 0
        self.tokens_decoded = 0
        self.fetch_latency_s = 0.0
        self.busy = False              # a prefill wave's event is in flight
        self._wavegen = None           # resumable prefill_waves generator
        self._batch: List[Request] = []  # requests the generator is serving
        self._wave_left = 0            # batch requests not yet handed off

    @property
    def engine(self):
        return self.prefill if self.role == ROLE_PREFILL else self.decode

    @property
    def unit(self):
        """The schedulable decode unit this member contributes to: its
        pipeline when span-partitioned, else its own engine."""
        return self.pipe if self.pipe is not None else self.decode

    def load_report(self):
        return self.engine.load_report()


class Orchestrator(BackendBase):
    """Owns the fleet; the virtual clock drives route → chunked prefill →
    hand-off → decode → control as independently-timed events.  The
    submit/step/abort/drain front door comes from ``api.BackendBase`` —
    the same surface (and code) the simulator serves."""

    def __init__(self, cfg: ModelConfig, params,
                 ocfg: OrchestratorConfig = OrchestratorConfig(),
                 draft=None):
        if ocfg.n_prefill < 1 or ocfg.n_decode < 1:
            raise ValueError("fleet needs >=1 prefill and >=1 decode "
                             f"instance, got {ocfg.n_prefill}p/"
                             f"{ocfg.n_decode}d")
        if ocfg.hw is None:
            ocfg = dataclasses.replace(
                ocfg, hw=A.device_profile(jax.devices()[0]))
        self.cfg = cfg
        self.params = params
        self.ocfg = ocfg
        # two-model speculation: (draft ModelConfig, draft params), handed
        # to every decode engine when engine.speculation == "draft"
        self.draft = draft
        # engines bill Global-KV-Store fetches and queue-delay reports on
        # the fleet's hardware profile + prefill MFU (one scale with the
        # router's est_time_s bumps); an explicitly hw-configured engine
        # config is taken as-is
        self.ecfg = (dataclasses.replace(ocfg.engine, hw=ocfg.hw,
                                         efficiency=ocfg.efficiency)
                     if ocfg.engine.hw is None else ocfg.engine)
        self.store = (GlobalKVStore(block_size=self.ecfg.block_size)
                      if ocfg.global_store else None)
        self.router = _make_router(ocfg.router)
        if ocfg.decode_split < 1 or ocfg.decode_split > cfg.n_layers:
            raise ValueError(f"decode_split {ocfg.decode_split} must be in "
                             f"[1, {cfg.n_layers}]")
        self.members: List[_Member] = []
        self._hw_seq = 0
        for i in range(ocfg.n_prefill):
            m = _Member(f"prefill{i}", ROLE_PREFILL, hw=self._next_hw())
            m.prefill = self._new_prefill(m.name, m.hw)
            self.members.append(m)
        self.decode_pipes: List[DecodePipeline] = []
        for i in range(ocfg.n_decode):
            if ocfg.decode_split == 1:
                m = _Member(f"decode{i}", ROLE_DECODE, hw=self._next_hw())
                m.decode = DecodeEngine(cfg, params, self._ecfg_for(m.hw),
                                        name=m.name, draft=draft)
                self.members.append(m)
                continue
            # one pipeline of decode_split span stages, one member each
            bounds = even_spans(cfg.n_layers, ocfg.decode_split)
            stages = []
            for j, span in enumerate(bounds):
                m = _Member(f"decode{i}.{j}", ROLE_DECODE)
                m.decode = DecodeEngine(cfg, params, self.ecfg,
                                        name=m.name, layer_span=span,
                                        draft=draft)
                m.stage = j
                stages.append(m)
                self.members.append(m)
            pipe = DecodePipeline(cfg, params, self.ecfg, bounds,
                                  name=f"decode{i}",
                                  engines=[m.decode for m in stages])
            for m in stages:
                m.pipe = pipe
            self.decode_pipes.append(pipe)
        self._by_name = {m.name: m for m in self.members}
        # zero-copy prefix sharing: hand-offs bind store-registered pages
        # by reference when source and destination agree on the pool —
        # only full-stack paged decode engines over the shared store (span
        # pipelines keep today's copy path across their per-stage pools)
        self.prefix_sharing = (ocfg.prefix_sharing
                               and self.store is not None
                               and KC.prefix_cacheable(cfg))
        self.pages_bound = 0           # prefix pages bound by reference
        # prefill waves that resumed at least one parked chunk partial
        self.chunk_resume_waves = 0
        self.bound_bytes_saved = 0.0   # hand-off bytes the binds skipped
        self.pages_moved = 0           # pages the hand-offs copied
        self.handoff_bytes_moved = 0   # bytes the hand-offs copied
        self.migration_bytes = 0       # KV and weight bytes migrations moved
        if self.prefix_sharing:
            for m in self.decode_members():
                if m.pipe is None and m.decode.paged:
                    m.decode.attach_store(self.store)
        self.controller = (MigrationController(ocfg.controller,
                                               self._migration_cost)
                           if ocfg.migration else None)
        self.clock = VirtualClock()
        self.control_interval = (
            float(ocfg.control_interval) if ocfg.control_interval is not None
            else 2.0 * A.decode_iter_time(cfg, self.ecfg.max_len, ocfg.hw,
                                          batch=max(self.ecfg.max_batch, 1)))
        self._control_armed = False
        self.pending: Deque[Request] = deque()  # submitted, not yet routed
        self.metrics = Metrics(slo=ocfg.slo)
        self.migration_log: List[MigrationAction] = []
        self.util_trace: List[Dict[str, float]] = []
        # (gap_before, gap_after) per control cycle that applied actions —
        # the hot-tier Δ the controller is supposed to drive down (Eq. 35)
        self.control_trace: List[tuple] = []
        self.span_move_log: List[Dict[str, int]] = []
        # per-layer overlapped transfer schedule accounting: modelled
        # hand-off seconds with and without §4.2 layer-wise overlap
        self.n_handoffs = 0
        self.handoff_serial_s = 0.0
        self.handoff_overlap_s = 0.0
        # decode slots reserved by prefill batches in flight: prefill never
        # produces KV that has nowhere to land, even across chunk waves
        self._reserved = 0
        self._unit_busy: Set[str] = set()   # decode iteration in flight
        # stale-event fencing: a re-roll bumps its member's epoch so
        # decode completions scheduled for the old engine are discarded
        self._epoch: Dict[str, int] = {}
        # swap-preempted decode residents parked off-device:
        # rid -> (request, gathered paged state, pending token).  Resumed
        # (bit-identically, via adopt) once capacity frees AND no admitted
        # work is still waiting for a slot.
        self._swapped: Dict[int, tuple] = {}
        # sacrifice re-prefill clones: clone rid -> (clone, original)
        self._resume_of: Dict[int, tuple] = {}
        self._clone_rid = -1           # clones use negative rids
        self.swap_io_s = 0.0           # modelled host-tier swap traffic
        # load-aware speculation routing: decode iterations billed at the
        # speculative verification cost vs forced back to plain decode
        self.spec_iters = 0
        self.plain_iters = 0
        self.retired: List[_Member] = []    # drained-down members
        self._scale_seq = 0                 # autoscaled-member naming
        self._init_backend()     # _by_rid registry + admission_limit

    # -- fleet views -----------------------------------------------------
    def _next_hw(self) -> A.HardwareProfile:
        hw = (self.ocfg.hw_profiles[self._hw_seq % len(self.ocfg.hw_profiles)]
              if self.ocfg.hw_profiles else self.ocfg.hw)
        self._hw_seq += 1
        return hw

    def _member_hw(self, m: Optional[_Member]) -> A.HardwareProfile:
        return m.hw if m is not None and m.hw is not None else self.ocfg.hw

    def _ecfg_for(self, hw: Optional[A.HardwareProfile]) -> EngineConfig:
        """The fleet engine config rebased onto one member's part, so the
        engine's store-fetch overlap and queue-delay reports price its
        own roofline."""
        if hw is None or hw is self.ecfg.hw:
            return self.ecfg
        return dataclasses.replace(self.ecfg, hw=hw)

    def _new_prefill(self, name: str,
                     hw: Optional[A.HardwareProfile] = None) -> PrefillEngine:
        store = self.store if self.store is not None else \
            GlobalKVStore(block_size=self.ecfg.block_size)
        return PrefillEngine(self.cfg, self.params, self._ecfg_for(hw),
                             store, name=name)

    def _serving_member(self, m: _Member) -> bool:
        """Eligible for NEW work: warmed up and not draining."""
        return m.warming_until <= self.clock.now and not m.draining

    def prefill_members(self) -> List[_Member]:
        return [m for m in self.members if m.role == ROLE_PREFILL]

    def decode_members(self) -> List[_Member]:
        return [m for m in self.members if m.role == ROLE_DECODE]

    def decode_units(self) -> List:
        """Schedulable decode targets: span pipelines count once (their
        stages share one slot layout), full-stack engines count as
        themselves."""
        units, seen = [], set()
        for m in self.decode_members():
            u = m.unit
            if id(u) not in seen:
                seen.add(id(u))
                units.append(u)
        return units

    def _unit_member(self, unit) -> _Member:
        """The member that owns a unit's counters (a pipeline's lead
        stage, or the engine's own member)."""
        name = unit.lead.name if isinstance(unit, DecodePipeline) \
            else unit.name
        return self._by_name[name]

    def _placeable_units(self) -> List:
        """Decode units that may take NEW residents: their member is
        warmed up and not draining.  Warming/draining units still run
        the iterations for whatever they already hold."""
        return [u for u in self.decode_units()
                if self._serving_member(self._unit_member(u))]

    def _unit_by_name(self, name: str):
        for u in self.decode_units():
            if u.name == name:
                return u
        return None

    @property
    def fleet(self) -> Dict[str, str]:
        out = {}
        for m in self.members:
            role = m.role
            if m.warming_until > self.clock.now:
                role += ":warming"
            elif m.draining:
                role += ":draining"
            out[m.name] = role
        return out

    def in_flight(self) -> int:
        return (len(self.pending)
                + sum(len(m.prefill.queue) for m in self.prefill_members())
                + self._reserved
                + sum(u.active for u in self.decode_units())
                + len(self._swapped))

    def _free_capacity(self) -> int:
        """Decode slots available for NEW prefill admissions."""
        return sum(u.free_slots for u in self._placeable_units()) \
            - self._reserved

    # -- submission / routing (the ServingBackend surface) ----------------
    # submit / step / step_until / drain come from api.BackendBase; only
    # the fleet-structure search half of ``abort`` is backend-specific.
    def abort(self, rid: int) -> bool:
        """Cancel a request wherever it lives.  A decode-resident request
        frees its slot and paged blocks immediately; a mid-prefill one is
        dropped at its hand-off (its batch's dense waves are unaffected,
        so batch-mates stay bit-exact).  Surviving token streams are
        unperturbed — greedy decode rows are independent."""
        req = self._by_rid.get(rid)
        if req is None or req.outcome is not None or req.phase == Phase.DONE:
            return False
        if req in self.pending:                       # central queue
            self.pending.remove(req)
            return self._finish_abort(req)
        for m in self.prefill_members():
            if req in m.prefill.queue:                # routed, not started
                m.prefill.queue.remove(req)
                return self._finish_abort(req)
        for u in self.decode_units():                 # decoding
            for slot, s in enumerate(u.slots):
                if s is req:
                    u.release_slot(slot)
                    ok = self._finish_abort(req)
                    self._dispatch()          # freed capacity admits more
                    return ok
        if rid in self._swapped:                      # swap-parked
            self._swapped.pop(rid)
            return self._finish_abort(req)
        # a sacrificed original waiting on its re-prefill clone: pull the
        # clone from any queue it still sits in (a mid-prefill clone stays
        # mapped — the hand-off handler drops its recomputed KV instead)
        for crid, (clone, orig) in list(self._resume_of.items()):
            if orig.rid != rid:
                continue
            if clone in self.pending:
                self.pending.remove(clone)
                del self._resume_of[crid]
            else:
                for m in self.prefill_members():
                    if clone in m.prefill.queue:
                        m.prefill.queue.remove(clone)
                        del self._resume_of[crid]
                        break
            break
        # still mid-prefill (its reservation is released at hand-off time,
        # where the aborted request's KV is dropped) or its arrival event
        # has not popped yet (the arrival handler skips terminal requests)
        return self._finish_abort(req)

    def _prefix_key(self, req: Request) -> Optional[bytes]:
        return leading_block_key(req.prompt, self.ecfg.block_size)

    def _account_handoff(self, req: Request, st: Dict) -> float:
        """Cost the KV hand-off's ordered per-layer transfer schedule with
        and without §4.2 layer-wise overlap (Eq. 4/11 on ``ocfg.hw``): the
        overlap partner is the destination's per-layer decode compute.
        Returns the overlapped seconds — the latency the request's first
        token actually pays."""
        sched = KC.layer_transfer_schedule(st)
        if not sched:
            return 0.0
        t_layer = A.decode_time_per_token(
            self.cfg, req.prompt_len, self.ocfg.hw) / max(len(sched), 1)
        nbytes = [b for _, b in sched]
        self.n_handoffs += 1
        # t_sync=0: a per-request page stream has no global sync barrier
        # (that term belongs to migration ops, Eq. 28) — with it, every
        # hand-off would carry a constant floor that swamps small models
        self.handoff_serial_s += A.serial_schedule_time(
            nbytes, self.ocfg.hw.net_bw, t_layer, t_sync=0.0)
        t_ov = A.overlapped_schedule_time(nbytes, self.ocfg.hw.net_bw,
                                          t_layer, t_sync=0.0)
        self.handoff_overlap_s += t_ov
        return t_ov

    def _sharing_target(self, tgt) -> bool:
        """Does ``tgt`` bind store pages by reference?  Only full-stack
        paged engines whose pool the shared store holds — everything else
        (span pipelines, dense fallbacks, per-instance stores) takes the
        copy path."""
        return (self.prefix_sharing and isinstance(tgt, DecodeEngine)
                and tgt.paged and tgt._store is self.store)

    def _bind_shared(self, req: Request, st: Dict, tgt,
                     keys: List[bytes]) -> tuple:
        """Zero-copy bind: when ``tgt``'s pool already holds the request's
        prefix blocks (registered by an earlier hand-off), drop those
        pages from the wire state and return them for by-reference
        binding — no gather/scatter, no bytes on the wire for the shared
        head.  Returns (possibly head-split state, pages)."""
        if "n_blocks" not in st or not keys:
            return st, []
        pages = self.store.resident_prefix(keys, tgt.name)
        n = min(len(pages), int(st["n_blocks"]))
        if n <= 0:
            return st, []
        full = KC.state_num_bytes(st)
        st = KC.split_paged_state(st, n, self.ecfg.block_size)
        self.pages_bound += n
        self.bound_bytes_saved += full - KC.state_num_bytes(st)
        return st, pages[:n]

    def _register_prefix(self, req: Request, tgt, slot: int,
                         keys: List[bytes]) -> None:
        """Re-point the store's entries for this prompt's full blocks at
        the pages now resident in ``tgt``'s pool (refcount++; the payload
        copies drop).  Later hand-offs of the same prefix to this engine
        bind them by reference."""
        n_full = req.prompt_len // self.ecfg.block_size
        if n_full <= 0:
            return
        row = tgt.slot_pages(slot)
        self.store.register_pages(keys[:n_full], tgt.name, row[:n_full])

    def _dispatch(self) -> None:
        """Algorithm 2 over the central queue: dispatch every pending
        request (or, with a fair-share scheduler, the WFQ-ordered slice
        capacity can serve) onto a prefill member's queue using live load
        snapshots (queue-delay-aware), then kick idle members."""
        members = [m for m in self.prefill_members()
                   if self._serving_member(m)]
        if not members:
            return                   # whole tier warming/draining: wait
        release = (self._sched_release() if self.scheduler is not None
                   else list(self.pending))
        if release:
            loads = live_instance_loads([m.prefill for m in members])
            budget = max(self.ecfg.max_batch * self.ecfg.max_len, 1)
            infos = [RequestInfo(
                r.rid, r.prompt_len,
                est_load=min(r.prompt_len / budget, 1.0),
                prefix_key=self._prefix_key(r),
                est_time_s=A.prefill_time(self.cfg, r.prompt_len,
                                          self.ocfg.hw,
                                          efficiency=self.ocfg.efficiency))
                for r in release]
            plan = self.router.dispatch(infos, loads)
            for req in release:
                self._by_name[plan[req.rid]].prefill.enqueue(req)
        if self.scheduler is None:
            self.pending.clear()
        self._kick_prefills()

    def _sched_release(self) -> List[Request]:
        """The fair-share gate between the central queue and the routers:
        release at most the fleet's uncommitted decode capacity, in WFQ
        order (the FIFO policy releases everything — it must behave like
        no scheduler at all).  When capacity is exhausted and preemption
        is configured, evict a victim for the best-ranked waiter."""
        if not self.pending:
            return []
        queued = sum(len(m.prefill.queue) for m in self.prefill_members())
        budget = self._free_capacity() - queued
        if self.scheduler.preemption is not None:
            while budget < 1 and self.pending:
                head = self.scheduler.peek(list(self.pending),
                                           self.clock.now)
                if not self._preempt_for(head):
                    break
                budget = self._free_capacity() - queued
        chosen = self.scheduler.select(list(self.pending), self.clock.now,
                                       budget=max(budget, 0))
        for r in chosen:
            self.pending.remove(r)
        return chosen

    def _kick_prefills(self) -> None:
        self._resume_swapped()
        for m in self.prefill_members():
            if m.warming_until > self.clock.now:
                continue       # wakes via its "warmed" event
            if not m.busy and (m._wavegen is not None or m.prefill.queue):
                self.clock.push(self.clock.now, "prefill", m.name)

    # -- decode preemption (swap / sacrifice) ------------------------------
    def _preempt_for(self, waiting: Request) -> bool:
        """Ask the scheduler for a decode-resident victim whose tenant
        ranks strictly below ``waiting``'s, then apply the configured
        eviction policy.  Returns True when a slot was freed."""
        running, where = [], {}
        for u in self.decode_units():
            for slot, r in enumerate(u.slots):
                if r is None:
                    continue
                running.append((r, r.max_new_tokens - len(r.generated)))
                where[r.rid] = (u, slot)
        victim = self.scheduler.pick_victim(waiting, running)
        if victim is None:
            return False
        u, slot = where[victim.rid]
        if self.scheduler.preemption == "swap":
            self._swap_out(u, slot)
        else:
            self._sacrifice(u, slot)
        return True

    def _swap_out(self, unit, slot: int) -> None:
        """Demote a decode resident's KV to the host tier: its pages free
        immediately, the gathered state parks off-device, and the store
        bills tier-1 bandwidth (both directions, here and at resume)."""
        req, st, tok = unit.extract_slot(slot)
        nbytes = KC.state_num_bytes(st)
        self.swap_io_s += (self.store.swap_out(nbytes)
                           if self.store is not None
                           else nbytes / self.ocfg.hw.host_bw)
        self._swapped[req.rid] = (req, st, tok)
        pages = int(st["n_blocks"]) if "n_blocks" in st else 0
        self.metrics.record_preempted(req, "swap", pages=pages)

    def _sacrifice(self, unit, slot: int) -> None:
        """Drop a decode resident's KV and recompute it later: a fresh
        clone request (prompt = original prompt + all committed tokens but
        the last) rides the normal chunked-prefill path, and the original
        adopts the recomputed state at the clone's hand-off."""
        victim = unit.release_slot(slot)
        clone = Request(
            rid=self._clone_rid, arrival=self.clock.now,
            prompt=np.concatenate([
                victim.prompt,
                np.asarray(victim.generated[:-1],
                           dtype=victim.prompt.dtype)]),
            max_new_tokens=max(
                victim.max_new_tokens - len(victim.generated), 1),
            tenant=victim.tenant)
        self._clone_rid -= 1
        self._resume_of[clone.rid] = (clone, victim)
        self.metrics.record_preempted(victim, "sacrifice")
        self.pending.append(clone)

    def _finish_resume(self, clone: Request, st: Dict) -> None:
        """A sacrifice clone's recompute finished: the original adopts the
        rebuilt KV and continues from its last committed token (so the
        resumed stream is bit-identical to an uninterrupted run)."""
        _, orig = self._resume_of.pop(clone.rid)
        if orig.outcome is not None:
            return                     # aborted while recomputing
        tgt = min((u for u in self._placeable_units() if u.free_slots > 0),
                  key=lambda u: (u.active, u.kv_tokens, u.name))
        t_ov = self._account_handoff(orig, st)
        tgt.adopt(orig, st, int(orig.generated[-1]))
        self.clock.push_in(t_ov, "decode_kick", tgt.name)

    def _resume_swapped(self) -> None:
        """Bring swap-parked victims back on-device — but only when spare
        capacity exceeds the claims of admitted work still waiting for a
        slot, so a fresh preemption isn't immediately undone."""
        if not self._swapped:
            return
        claimed = len(self.pending) + sum(
            len(m.prefill.queue) for m in self.prefill_members())
        while self._swapped and self._free_capacity() - claimed > 0:
            rid = next(iter(self._swapped))
            req, st, tok = self._swapped.pop(rid)
            if req.outcome is not None:
                continue
            nbytes = KC.state_num_bytes(st)
            t_in = (self.store.swap_in(nbytes) if self.store is not None
                    else nbytes / self.ocfg.hw.host_bw)
            self.swap_io_s += t_in
            tgt = min((u for u in self._placeable_units()
                       if u.free_slots > 0),
                      key=lambda u: (u.active, u.kv_tokens, u.name))
            tgt.adopt(req, st, tok)
            self.clock.push_in(t_in, "decode_kick", tgt.name)

    def preempt(self, rid: int, mode: Optional[str] = None) -> bool:
        """Force-preempt a decode-resident request (ops/test hook):
        ``swap`` parks its KV off-device, ``sacrifice`` drops it for
        re-prefill.  ``mode`` defaults to the scheduler's configured
        policy.  False when ``rid`` is not decode-resident."""
        if mode is None and self.scheduler is not None:
            mode = self.scheduler.preemption
        if mode not in ("swap", "sacrifice"):
            raise ValueError(f"unknown preemption mode {mode!r}")
        for u in self.decode_units():
            for slot, r in enumerate(u.slots):
                if r is not None and r.rid == rid:
                    if mode == "swap":
                        self._swap_out(u, slot)
                    else:
                        self._sacrifice(u, slot)
                    self._dispatch()
                    return True
        return False

    def _spec_capable(self, unit) -> bool:
        """Can this unit run the speculative verify step at all?  Only
        full-stack paged engines with speculation configured — span
        pipelines and gated architectures decode plain regardless."""
        return (self.ecfg.speculation != "off"
                and isinstance(unit, DecodeEngine)
                and getattr(unit, "_spec_ok", False))

    def _accept_estimate(self, unit) -> float:
        """Measured acceptance rate for the unit's proposer, optimistic
        (0.8) until it has evidence — speculation gets tried at low load
        and the observed rate then governs the routing decision."""
        if unit.spec_proposed > 0:
            return unit.spec_accepted / unit.spec_proposed
        return 0.8

    def _kick_decode(self, unit) -> None:
        """Schedule one continuous-batching iteration for ``unit`` if it
        has work and none is in flight; cost = the analytical iteration
        time for the real batch shape (Eq. 22).

        Load-aware speculation routing: when the unit can speculate, the
        per-committed-token cost of a speculative iteration (verification
        compute scales ~(k+1)x, bytes barely move) is compared against a
        plain step at the unit's live batch and context.  Memory-bound
        shapes (low batch) favour speculation; once the batch grows deep
        enough that verification turns compute-bound, the unit is flipped
        back to plain decode.  The flip is per-iteration and the engine's
        ``spec_on`` gate makes the next ``step()`` obey it."""
        if unit is None or unit.name in self._unit_busy or unit.active == 0:
            return
        hw = self._member_hw(self._unit_member(unit))
        ctx = unit.kv_tokens // max(unit.active, 1)
        cost = A.decode_iter_time(self.cfg, max(ctx, 1), hw,
                                  batch=unit.active)
        if self._spec_capable(unit):
            k = max(self.ecfg.spec_len, 1)
            spec_cost = A.speculative_decode_iter_time(
                self.cfg, max(ctx, 1), hw, batch=unit.active,
                k=k, draft_cfg=self.draft[0] if self.draft else None)
            e_tok = A.speculative_tokens_per_iter(
                k, self._accept_estimate(unit))
            speculate = spec_cost / e_tok < cost
            unit.spec_on = speculate
            if speculate:
                cost = spec_cost
                self.spec_iters += 1
            else:
                self.plain_iters += 1
        self._unit_busy.add(unit.name)
        self.clock.push_in(cost, "decode_done",
                           (unit.name, self._epoch.get(unit.name, 0)))

    def _arm_control(self) -> None:
        if (self.controller is not None or self.autoscaler is not None) \
                and not self._control_armed:
            self.clock.push_in(self.control_interval, "control")
            self._control_armed = True

    # -- event handlers ---------------------------------------------------
    def _handle(self, ev) -> List[Request]:
        with tracing.span("event." + ev.kind):
            if ev.kind == "arrival":
                if self._admit(ev.payload):   # bounced: aborted or queue full
                    self.pending.append(ev.payload)
                    self._dispatch()
            elif ev.kind == "prefill":
                self._on_prefill(ev.payload)
            elif ev.kind == "prefill_done":
                self._on_prefill_done(*ev.payload)
            elif ev.kind == "decode_kick":
                self._kick_decode(self._unit_by_name(ev.payload))
            elif ev.kind == "decode_done":
                return self._on_decode_done(*ev.payload)
            elif ev.kind == "control":
                self._on_control()
            elif ev.kind == "warmed":
                self._on_warmed(ev.payload)
            else:
                raise ValueError(f"unknown event kind {ev.kind!r}")
            return []

    def _on_prefill(self, name: str) -> None:
        """One prefill wave: pick up a batch if idle, run the next dense
        forward (one chunk per row at most), charge its analytical cost."""
        m = self._by_name.get(name)
        if m is None or m.role != ROLE_PREFILL or m.busy:
            return
        if m._wavegen is None:
            if m.draining:
                # a draining member finishes its in-flight wave but never
                # starts another; retires once idle
                self._try_retire_member(m)
                return
            n = min(self.ocfg.prefill_chunk, len(m.prefill.queue),
                    self._free_capacity())
            if n <= 0:
                return
            batch = [m.prefill.queue.popleft() for _ in range(n)]
            for r in batch:
                r.t_prefill_start = r.t_prefill_start or self.clock.now
            self._reserved += n
            m._wave_left = n
            m._batch = batch
            m._wavegen = m.prefill.prefill_waves(
                batch, chunk_tokens=self.ocfg.chunk_tokens)
        # counters accumulate on the member (engines don't survive
        # re-rolls), fed by engine deltas — one source of truth
        before = (m.prefill.tokens_prefilled, m.prefill.n_prefilled,
                  m.prefill.fetch_latency_s)
        wave = next(m._wavegen, None)
        m.tokens_prefilled += m.prefill.tokens_prefilled - before[0]
        m.n_prefilled += m.prefill.n_prefilled - before[1]
        m.fetch_latency_s += m.prefill.fetch_latency_s - before[2]
        if wave is None:                      # defensive: empty generator
            m._wavegen = None
            m._batch = []
            return
        self.chunk_resume_waves += wave["resumed"] > 0
        done = [(m._batch[i], st, lg) for i, st, lg in wave["done"]]
        m._wave_left -= len(done)
        if m._wave_left <= 0:
            m._wavegen = None
            m._batch = []
        cost = A.prefill_time(self.cfg, wave["padded_len"],
                              self._member_hw(m), batch=wave["rows"],
                              efficiency=self.ocfg.efficiency)
        m.busy = True
        self.clock.push_in(cost, "prefill_done", (name, done))

    def _on_prefill_done(self, name: str, done) -> None:
        m = self._by_name.get(name)
        if m is not None:
            m.busy = False
        for req, st, logits in done:
            self._reserved -= 1
            if req.rid in self._resume_of:
                self._finish_resume(req, st)   # a sacrifice clone landed
                continue
            if req.outcome is not None:
                continue       # aborted mid-prefill: its KV is dropped here
            with tracing.span("handoff", rid=req.rid) as sp:
                self._handoff(req, st, logits, sp)
        if m is not None and m.role == ROLE_PREFILL and \
                (m._wavegen is not None or m.prefill.queue):
            self.clock.push(self.clock.now, "prefill", m.name)
        if m is not None and m.draining:
            self._try_retire_member(m)

    def _handoff(self, req: Request, st: Dict, logits, sp) -> None:
        """Move one prefilled request into a decode slot: bind the pages
        the target already holds, copy the rest, and schedule the first
        decode iteration.  ``sp`` is the request's ``handoff`` span."""
        req.advance(Phase.TRANSFER)
        # ties broken by unit name so target selection is
        # deterministic across re-rolls and fleet orderings
        tgt = min((u for u in self._placeable_units()
                   if u.free_slots > 0),
                  key=lambda u: (u.active, u.kv_tokens, u.name))
        shared: List[int] = []
        keys: List[bytes] = []
        with tracing.span("handoff.bind"):
            if self._sharing_target(tgt):
                keys = chain_hashes(req.prompt, self.ecfg.block_size)
                st, shared = self._bind_shared(req, st, tgt, keys)
        moved, nbytes = st.get("n_blocks", 0), KC.state_num_bytes(st)
        self.pages_moved += moved
        self.handoff_bytes_moved += nbytes
        sp.set_metadata(pages_bound=len(shared), pages_moved=moved,
                        bytes_moved=nbytes)
        # the hand-off bills only the pages that actually move — a
        # bound prefix crosses as references, not bytes
        t_ov = self._account_handoff(req, st)
        with tracing.span("handoff.wait"):
            # the first token's readback waits for the wave's device work
            first = int(jnp.argmax(logits))
        with tracing.span("handoff.insert"):
            slot = tgt.insert(req, st, first, shared_pages=shared or None)
        if keys:
            with tracing.span("handoff.register"):
                self._register_prefix(req, tgt, slot, keys)
        # the first token becomes visible once its KV hand-off's
        # overlapped per-layer schedule completes
        req.t_first_token = self.clock.now + t_ov
        req.t_tokens.append(req.t_first_token)
        self.clock.push_in(t_ov, "decode_kick", tgt.name)

    def _on_decode_done(self, name: str, epoch: int) -> List[Request]:
        self._unit_busy.discard(name)
        if epoch != self._epoch.get(name, 0):
            return []                      # unit re-rolled mid-iteration
        unit = self._unit_by_name(name)
        if unit is None:
            return []
        m = self._unit_member(unit)
        before_tok = unit.tokens_decoded
        snapshot = [(r, len(r.generated))
                    for r in unit.slots if r is not None]
        finished = [req for req, _slot in unit.step()]
        now = self.clock.now
        self.metrics.decode_iters += 1
        for req, n0 in snapshot:
            # one stamp PER committed token (a speculative iteration can
            # land several at once — they all become visible when the
            # verify step's event completes), kept monotonic per request
            # (a hand-off's transfer latency may overlap this iteration)
            for _ in range(len(req.generated) - n0):
                last = req.t_tokens[-1] if req.t_tokens else now
                req.t_tokens.append(max(now, last))
        for req in finished:
            req.t_done = req.t_tokens[-1] if req.t_tokens else now
            self._sched_done(req)
            self.metrics.record(req)
        m.tokens_decoded += unit.tokens_decoded - before_tok
        if unit.active:
            self._kick_decode(unit)
        if finished:
            self._dispatch()               # freed slots -> admit more
        return finished

    def _on_control(self) -> None:
        self._control_armed = False
        if self.controller is not None:
            self._control()
        self._autoscale_tick()
        for m in [m for m in self.members if m.draining]:
            self._try_retire_member(m)
        if self.autoscaler is not None:
            self.metrics.record_util(self.clock.now, {
                d.device: d.utilization for d in self._device_loads()})
        if self.in_flight() > 0 or self.clock:
            self._arm_control()

    # -- autoscaling hooks (api.BackendBase._autoscale_tick drives these) --
    def set_autoscaler(self, policy) -> None:
        if policy is not None and self.ocfg.decode_split != 1:
            raise ValueError("autoscaling requires decode_split == 1 "
                             "(span pipelines scale by re-slicing, not "
                             "by spawn/retire)")
        super().set_autoscaler(policy)

    def _on_warmed(self, name: str) -> None:
        """A spawned member finished its billed warm-up (weights streamed
        host→device + jit) and starts taking traffic."""
        if name not in self._by_name:
            return
        self._record_fleet()
        self._dispatch()

    def _fleet_counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for m in self.members:
            if m.warming_until > self.clock.now:
                k = "warming"
            elif m.draining:
                k = "draining"
            else:
                k = m.role
            out[k] = out.get(k, 0) + 1
        return out

    def _autoscale_signals(self):
        from .autoscale import FleetSignals, TierSignals
        now = self.clock.now
        warm = {"prefill": 0, "decode": 0}
        drain = {"prefill": 0, "decode": 0}
        act_p: List[_Member] = []
        act_d: List[_Member] = []
        for m in self.members:
            if m.warming_until > now:
                warm[m.role] += 1
            elif m.draining:
                drain[m.role] += 1
            elif m.role == ROLE_PREFILL:
                act_p.append(m)
            elif m.pipe is None or m.stage == 0:
                act_d.append(m)        # pipelines count once (lead stage)
        backlog_p = len(self.pending) + sum(
            len(m.prefill.queue) for m in act_p)
        qd_p = util_p = 0.0
        if act_p:
            reps = [m.load_report() for m in act_p]
            qd_p = sum(r.queue_delay_s for r in reps) / len(act_p)
            util_p = sum(min(r.compute_frac, 1.0)
                         for r in reps) / len(act_p)
        qd_p += sum(A.prefill_time(self.cfg, r.prompt_len, self.ocfg.hw,
                                   efficiency=self.ocfg.efficiency)
                    for r in self.pending) / max(len(act_p), 1)
        prefill = TierSignals(
            n_active=len(act_p), n_warming=warm["prefill"],
            n_draining=drain["prefill"], util=util_p,
            queue_delay_s=qd_p, backlog=backlog_p)
        units = [m.unit for m in act_d]
        active = sum(u.active for u in units)
        total = sum(u.active + u.free_slots for u in units)
        backlog_d = len(self._swapped)
        qd_d = 0.0
        if backlog_d and active:
            ctx = sum(u.kv_tokens for u in units) / active
            t_iter = A.decode_iter_time(
                self.cfg, max(int(ctx), 1), self.ocfg.hw,
                batch=max(active // max(len(units), 1), 1))
            rem = sum(r.max_new_tokens - len(r.generated)
                      for u in units for r in u.slots if r is not None)
            qd_d = (rem / max(active, 1)) * t_iter * backlog_d \
                / max(len(units), 1)
        decode = TierSignals(
            n_active=len(act_d), n_warming=warm["decode"],
            n_draining=drain["decode"],
            util=active / max(total, 1),
            queue_delay_s=qd_d, backlog=backlog_d)
        return FleetSignals(t=now, prefill=prefill, decode=decode)

    def _scale_up(self, role: str, profile=None) -> Optional[str]:
        """Spawn a live engine for ``role``.  The member exists (and
        costs instance-seconds) immediately, but takes no traffic until
        its warm-up — full weight set streamed at the part's DMA
        bandwidth plus jit — elapses on the virtual clock."""
        if role == ROLE_DECODE and self.ocfg.decode_split != 1:
            return None
        hw = profile or self.ocfg.hw
        self._scale_seq += 1
        name = f"{role}-s{self._scale_seq}"
        m = _Member(name, role, hw=hw)
        if role == ROLE_PREFILL:
            m.prefill = self._new_prefill(name, hw)
        else:
            m.decode = DecodeEngine(self.cfg, self.params,
                                    self._ecfg_for(hw), name=name,
                                    draft=self.draft)
            if self.prefix_sharing and m.decode.paged:
                m.decode.attach_store(self.store)
        jit_s = (self.autoscaler.cfg.jit_compile_s
                 if self.autoscaler is not None else 2.0)
        m.warming_until = self.clock.now + A.instance_warmup_time(
            self.cfg, hw, jit_compile_s=jit_s)
        self.members.append(m)
        self._by_name[name] = m
        self.clock.push(m.warming_until, "warmed", name)
        return name

    def _scale_down(self, role: str) -> bool:
        """Start draining the least-loaded serving member of ``role``.
        Prefill: queued requests re-route centrally, the in-flight wave
        finishes, then the member retires.  Decode: residents move to
        peers via extract/adopt (exact pytree surgery — token streams
        bit-identical), then the member retires."""
        if role == ROLE_PREFILL:
            cands = [m for m in self.prefill_members()
                     if self._serving_member(m)]
            if len(cands) <= max(self.ocfg.min_prefill, 1):
                return False
            victim = min(cands, key=lambda m: (
                len(m.prefill.queue), m.tokens_prefilled))
            victim.draining = True
            if victim.prefill.queue:
                self.pending.extendleft(reversed(victim.prefill.queue))
                victim.prefill.queue.clear()
                self._dispatch()
            self._try_retire_member(victim)
            return True
        cands = [m for m in self.decode_members()
                 if self._serving_member(m) and m.pipe is None]
        if len(cands) <= max(self.ocfg.min_decode, 1):
            return False
        victim = min(cands, key=lambda m: (m.decode.active,
                                           m.decode.kv_tokens))
        victim.draining = True
        spare = sum(u.free_slots for u in self._placeable_units()) \
            - self._reserved
        if victim.decode.active > spare:
            victim.draining = False
            return False        # residents would not fit on the peers
        self._epoch[victim.name] = self._epoch.get(victim.name, 0) + 1
        self._unit_busy.discard(victim.name)
        for req, st, tok in victim.decode.drain():
            tgt = min((u for u in self._placeable_units()
                       if u.free_slots > 0),
                      key=lambda u: (u.active, u.kv_tokens, u.name))
            t_ov = self._account_handoff(req, st)
            tgt.adopt(req, st, tok)
            self.clock.push_in(t_ov, "decode_kick", tgt.name)
        if self.store is not None:
            self.store.detach_pool(victim.name)
        self._try_retire_member(victim)
        return True

    def _try_retire_member(self, m: _Member) -> bool:
        """Remove a drained member once nothing references it."""
        if not m.draining or m.name not in self._by_name:
            return False
        if m.role == ROLE_PREFILL:
            if m.busy or m._wavegen is not None or m.prefill.queue:
                return False
        elif m.decode is not None and (m.decode.active > 0
                                       or m.name in self._unit_busy):
            return False
        self.members.remove(m)
        del self._by_name[m.name]
        self.retired.append(m)
        self._record_fleet()
        return True

    # -- public drive ------------------------------------------------------
    def run(self, reqs: Sequence[Request],
            max_events: int = 1_000_000) -> dict:
        """Batch drive, now a thin wrapper over the streaming surface:
        each request is submitted at its workload Poisson timestamp (the
        virtual arrival time) and the loop drains — event-for-event what
        ``api.Server.run`` does, so the two paths are bit-identical."""
        for r in sorted(reqs, key=lambda r: r.arrival):
            self.submit(r, at=r.arrival)
        self.drain(max_events=max_events)
        lost = [r.rid for r in reqs if r.outcome is None]
        if lost:
            raise RuntimeError(f"orchestrator lost requests {lost}")
        return self.summary()

    # -- Algorithm 1: control cycle --------------------------------------
    def _device_loads(self) -> List[DeviceLoad]:
        out = []
        for m in self.members:
            if not self._serving_member(m):
                continue   # the migration controller leaves them alone
            r = m.load_report()
            out.append(DeviceLoad(
                device=m.name, compute_frac=r.compute_frac,
                memory_frac=r.memory_frac, supports_layer=True,
                supports_attention=(m.role == ROLE_DECODE)))
        return out

    def _control(self) -> List[MigrationAction]:
        loads = self._device_loads()
        utils = {d.device: d.utilization for d in loads}
        self.util_trace.append(utils)
        acts = self.controller.plan(loads)
        applied = [a for a in acts if self.apply_action(a)]
        if applied:
            after = {d.device: d.utilization
                     for d in self._device_loads()}
            self.control_trace.append((utilization_gap(utils),
                                       utilization_gap(after)))
        return applied

    def _span_pair(self, src: _Member, dst: _Member
                   ) -> Optional[DecodePipeline]:
        """The pipeline owning src/dst iff they are adjacent span stages
        of the same one (the only topology a live span move can serve)."""
        if (src.pipe is not None and src.pipe is dst.pipe
                and abs(src.stage - dst.stage) == 1):
            return src.pipe
        return None

    def _can_reroll(self, member: _Member, new_role: str) -> bool:
        if member.pipe is not None:
            return False       # pipeline stages re-slice spans, not roles
        if member.role == new_role:
            return False
        if not self._serving_member(member):
            return False       # autoscaler owns warming/draining members
        if member.role == ROLE_PREFILL:
            if len(self.prefill_members()) <= self.ocfg.min_prefill:
                return False
            if member.busy or member._wavegen is not None:
                return False   # a prefill batch is mid-flight on it
        if member.role == ROLE_DECODE:
            if len(self.decode_units()) <= self.ocfg.min_decode:
                return False
            # resident KV must fit on the remaining decode peers, net of
            # slots already reserved by in-flight prefill batches
            spare = sum(u.free_slots for u in self._placeable_units()
                        if u is not member.unit) - self._reserved
            if member.decode.active > spare:
                return False
        return True

    def _migration_cost(self, kind: MigrationKind, d_o: DeviceLoad,
                        d_u: DeviceLoad, amount: int):
        """Benefit/cost hook for the controller, over live fleet state.

        Benefit is the utilization-gap reduction a feasible action buys;
        cost is the Eq. 4/11 analytical transfer time on ``ocfg.hw``."""
        src = self._by_name[d_o.device]
        dst = self._by_name[d_u.device]
        gap = d_o.utilization - d_u.utilization
        if kind == MigrationKind.LAYER:
            pipe = self._span_pair(src, dst)
            if pipe is not None:
                # true span move: bill only the boundary layers' weights +
                # resident KV, layer-wise overlapped (Eq. 4/11)
                a, b = src.decode.layer_span
                n = min(amount, (b - a) - 1)
                t_layer = A.decode_time_per_token(
                    self.cfg, self.ecfg.max_len, self.ocfg.hw) \
                    / max(self.cfg.n_layers, 1)
                cost = max(A.span_migration_time(
                    self.cfg, max(n, 1), kv_tokens=src.decode.kv_tokens,
                    hw=self.ocfg.hw, t_layer_compute=t_layer), 1e-6)
                if n <= 0:
                    return 0.0, cost
                # moving n layers closes ~n/span of the stage gap
                return gap * n / max(b - a, 1), cost
            kv = dst.decode.kv_tokens if dst.role == ROLE_DECODE else 0
            cost = max(A.layer_migration_time(self.cfg, self.cfg.n_layers,
                                              kv_tokens=kv, hw=self.ocfg.hw),
                       1e-6)
            # span stages never trade roles with anything outside their
            # pipeline — pricing such a pair as a re-roll would make the
            # controller plan actions apply_action must refuse
            if src.pipe is not None or not self._can_reroll(dst, src.role):
                return 0.0, cost
            return gap / 2.0, cost
        # KV_HEADS: rebalance in-flight decode KV between two decode units
        su = src.unit if src.role == ROLE_DECODE else None
        du = dst.unit if dst.role == ROLE_DECODE else None
        cost = max(A.attention_migration_time(
            self.cfg, amount,
            kv_tokens=su.kv_tokens if su is not None else 0,
            hw=self.ocfg.hw), 1e-6)
        if (su is None or du is None or su is du
                or su.active <= du.active + 1 or du.free_slots <= 0):
            return 0.0, cost
        return gap / 4.0, cost

    # -- action execution -------------------------------------------------
    def apply_action(self, act: MigrationAction) -> bool:
        """Execute one controller action against the live fleet.  Public so
        hosts/tests can force a migration.  Returns True if applied.

        LAYER between adjacent stages of one decode pipeline = live span
        move of ``act.amount`` boundary layers; LAYER between full-stack
        members = whole-instance role re-roll."""
        with tracing.span("migrate", kind=act.kind.value) as sp:
            before = self.migration_bytes
            ok = self._apply(act)
            sp.set_metadata(bytes=self.migration_bytes - before)
        return ok

    def _apply(self, act: MigrationAction) -> bool:
        src = self._by_name.get(act.src)
        dst = self._by_name.get(act.dst)
        if src is None or dst is None:
            return False
        if act.kind == MigrationKind.LAYER:
            pipe = self._span_pair(src, dst)
            if pipe is not None:
                res = pipe.move_span(src.stage, dst.stage, act.amount)
                ok = res is not None
                if ok:
                    self.span_move_log.append(res)
                    self.migration_bytes += res["weight_bytes"] \
                        + res["kv_bytes"]
            elif src.pipe is None and dst.pipe is None:
                ok = self._reroll(dst, src.role)
            else:
                ok = False     # span stages never trade roles with others
        else:
            ok = self._rebalance_decode(src, dst)
        if ok:
            self.migration_log.append(act)
            # re-plumb the event flow around the new topology: requeued
            # requests re-route, adopters and the new capacity get kicked
            self._dispatch()
            for u in self.decode_units():
                self._kick_decode(u)
        return ok

    def _reroll(self, member: _Member, new_role: str) -> bool:
        """Fig. 3 executable: repurpose ``member`` into ``new_role``."""
        if not self._can_reroll(member, new_role):
            return False
        self._epoch[member.name] = self._epoch.get(member.name, 0) + 1
        self._unit_busy.discard(member.name)
        if new_role == ROLE_DECODE:
            # prefill -> decode: queued (unstarted) requests go back to the
            # front of the central queue; Algorithm 2 re-routes them next
            # dispatch (extendleft reverses, so feed it the reversed queue)
            self.pending.extendleft(reversed(member.prefill.queue))
            member.prefill.queue.clear()
            member.prefill = None
            member.decode = DecodeEngine(self.cfg, self.params, self.ecfg,
                                         name=member.name, draft=self.draft)
            if self.prefix_sharing and member.decode.paged:
                member.decode.attach_store(self.store)
        else:
            # decode -> prefill: evacuate resident KV to decode peers first
            # (the migrated layers' serving state moves with them)
            for req, st, tok in member.decode.drain():
                tgt = min((u for u in self._placeable_units()
                           if u is not member.unit and u.free_slots > 0),
                          key=lambda u: (u.active, u.name))
                self.migration_bytes += KC.state_num_bytes(st)
                tgt.adopt(req, st, tok)
            if self.store is not None:
                # the pool's pages die with the engine: demote the store's
                # page-resident entries to the backing tiers first
                self.store.detach_pool(member.name)
            member.decode = None
            member.prefill = self._new_prefill(member.name)
        member.role = new_role
        member.rerolled = True
        return True

    def _rebalance_decode(self, src: _Member, dst: _Member) -> bool:
        """Attention-level migration: move half the slot excess src→dst.
        Units speak the full-stack wire format, so slots move freely
        between pipelines (even with different span boundaries) and
        full-stack engines."""
        if src.role != ROLE_DECODE or dst.role != ROLE_DECODE:
            return False
        su, du = src.unit, dst.unit
        if su is du:
            return False
        n = min((su.active - du.active) // 2, du.free_slots)
        if n <= 0:
            return False
        moved = 0
        for slot, s in enumerate(su.slots):
            if moved >= n:
                break
            if s is None:
                continue
            req, st, tok = su.extract_slot(slot)
            self.migration_bytes += KC.state_num_bytes(st)
            du.adopt(req, st, tok)
            moved += 1
        return moved > 0

    # -- reporting ---------------------------------------------------------
    def summary(self) -> dict:
        s = self.metrics.summary()
        s["router"] = self.ocfg.router
        s["global_store"] = self.ocfg.global_store
        s["migrations"] = len(self.migration_log)
        s["fleet"] = self.fleet
        s["virtual_time_s"] = self.clock.now
        s["events"] = self.clock.n_processed
        s["chunk_tokens"] = self.ocfg.chunk_tokens
        s["chunk_resume_waves"] = self.chunk_resume_waves
        s["span_moves"] = len(self.span_move_log)
        s["span_bytes_moved"] = sum(r["weight_bytes"] + r["kv_bytes"]
                                    for r in self.span_move_log)
        if self.decode_pipes:
            s["span_bounds"] = {p.name: [tuple(b) for b in p.bounds]
                                for p in self.decode_pipes}
        if self.control_trace:
            s["util_gap_before"] = float(
                sum(g for g, _ in self.control_trace)
                / len(self.control_trace))
            s["util_gap_after"] = float(
                sum(g for _, g in self.control_trace)
                / len(self.control_trace))
        s["speculation"] = self.ecfg.speculation
        if self.ecfg.speculation != "off":
            s["spec_iters"] = self.spec_iters
            s["spec_plain_iters"] = self.plain_iters
        s["handoffs"] = self.n_handoffs
        s["handoff_serial_s"] = self.handoff_serial_s
        s["handoff_overlap_s"] = self.handoff_overlap_s
        if self.autoscaler is not None:
            s["autoscale_decisions"] = len(self.autoscaler.decisions)
            s["n_retired"] = len(self.retired)
        if self.scheduler is not None:
            s["scheduler"] = self.scheduler.cfg.policy
            s["sched_rejections"] = dict(self.scheduler.rejections)
            s["swap_io_s"] = self.swap_io_s
        s["store_fetch_s"] = sum(m.fetch_latency_s for m in self.members)
        # routing-imbalance metric (Fig. 2a): only members that held the
        # prefill role for the whole run — re-rolled members' counters
        # reflect migration, not router quality
        pw = [m.tokens_prefilled for m in self.members
              if m.role == ROLE_PREFILL and not m.rerolled]
        s["prefill_token_skew"] = ((max(pw) - min(pw)) / max(max(pw), 1)
                                   if pw else 0.0)
        if self.store is not None:
            s["store_hit_rate"] = self.store.stats.hit_rate
            s["store_entries"] = len(self.store)
            # zero-copy sharing accounting (paper motivation iii: the hot
            # prefix is HBM-resident once, not once per slot)
            s["prefix_sharing"] = self.prefix_sharing
            s["pages_bound"] = self.pages_bound
            s["bound_bytes_saved"] = self.bound_bytes_saved
            s["pages_moved"] = self.pages_moved
            s["handoff_bytes_moved"] = self.handoff_bytes_moved
            s["cow_forks"] = sum(
                m.decode.cow_forks for m in self.decode_members()
                if m.decode is not None)
            s["store_registered_blocks"] = self.store.stats.registered_blocks
            s["store_demotions"] = self.store.stats.demotions
            s["hbm_pages_peak"] = sum(
                m.decode.pool.peak_used for m in self.decode_members()
                if m.decode is not None and m.decode.paged)
        else:
            stores = [m.prefill.store for m in self.prefill_members()
                      if m.prefill.store is not None]
            hits = sum(st.stats.hit_blocks for st in stores)
            tot = hits + sum(st.stats.miss_blocks for st in stores)
            s["store_hit_rate"] = hits / tot if tot else 0.0
            s["store_entries"] = sum(len(st) for st in stores)
        return s
