"""Live serving engines over the real JAX model, on the paged KV runtime.

``PrefillEngine`` — batched prefill with Global-KV-Store integration:
longest-prefix match, KV fetch + incremental (prefix-aware) prefill of the
suffix only, and insertion of freshly produced full blocks back into the
store.  This is the executable form of Fig. 5.  Requests are bucketed by
(padded suffix length, prefix-hit) so every forward is a dense ``(G, S)``
batch; rows inside a bucket may carry *different* cached-prefix lengths —
per-row cache lengths drive positions and masks, so the batch is exact.
Suffixes (and row counts) are padded to power-of-two buckets capped at
``max_len`` so the set of compiled XLA shapes is bounded and reported
(``compile_report``); padded junk lands at masked future positions the
decoder overwrites before ever attending to them.

``DecodeEngine`` — slot-based continuous batching over a **paged block
pool** (models.kvcache): per-slot block tables index pages of
``block_size`` tokens, decode gathers pages through the tables inside the
jitted step, and prefill output states are *inserted* by copying only
their pages into freshly allocated blocks (the prefill→decode KV transfer
of PD disaggregation).  Slots can also be *extracted* mid-flight as page
payloads — the attention-level migration / role re-roll unit whose cost
scales with the request's blocks, not the cache size.  Architectures with
no pageable attention KV (pure recurrent stacks, windows that don't divide
into blocks) fall back to the dense row layout transparently.

Both report ``core.scheduling.LoadReport`` snapshots so the Algorithm 1/2
policies run over live engines exactly as they run over the simulator, and
both run the exact same ``models.transformer`` stack used by training and
the dry-run — no separate serving model definition.
"""
from __future__ import annotations

import dataclasses
import functools
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import analytical as A
from ..core import layer_migration as LM
from ..core.kvstore import GlobalKVStore, PageRef, chain_hashes
from ..core.scheduling import LoadReport
from ..models import kvcache as KC
from ..models import transformer as T
from ..models.config import ModelConfig
from . import tracing
from .request import Phase, Request


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    max_len: int = 512
    max_batch: int = 8
    block_size: int = 16          # must match the store's block size
    greedy: bool = True
    # paged decode via the page-fused split-KV Pallas kernel.  None = auto:
    # the kernel is the default whenever the cache is paged (compiled on
    # TPU, interpret=True on the CPU — kernels/ops picks per backend).
    # False forces the gather-then-attend dense reference path (kept as
    # the bit-level A/B baseline); True forces the kernel.
    decode_kernel: Optional[bool] = None
    # when set, store fetches are billed as the §4.2 layer-wise overlapped
    # transmission against this hardware's per-layer prefill compute
    hw: Optional[A.HardwareProfile] = None
    efficiency: float = 0.5       # prefill MFU for the analytical billings
    # speculative decoding on the decode step: "off" = one token per jitted
    # iteration; "ngram" = draft-free lookahead (per-slot suffix match over
    # prompt+output proposes up to spec_len tokens); "draft" = a second,
    # smaller model drafts the proposals (DecodeEngine's ``draft`` arg
    # carries its config+params).  Proposals are verified EXACTLY in one
    # multi-query pass — the committed stream is bit-identical to plain
    # greedy decode; rejected tokens' pages roll back through the pool.
    speculation: str = "off"
    spec_len: int = 4             # max proposed tokens per iteration
    spec_adaptive: bool = True    # adapt per-slot depth to acceptance rate


def _pow2_ceil(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def _attn_cache_lens(cfg: ModelConfig, max_len: int) -> List[int]:
    """Attention cache lengths probed from batch-1 layer-state protos, so
    ``transformer._block_state`` stays the single source of truth for
    per-kind window rules."""
    lens = []
    for kind in set(cfg.blocks()):
        st = T._block_state(cfg, kind, 1, max_len, jnp.float32)
        if "pos" in st:
            lens.append(int(st["pos"].shape[-1]))
    return lens


def serving_page_len(cfg: ModelConfig, max_len: int) -> Optional[int]:
    """The paged runtime's page space for this arch at this cache size, or
    None when the stack holds no attention KV."""
    lens = _attn_cache_lens(cfg, max_len)
    return max(lens) if lens else None


def _paged_page_len(cfg: ModelConfig, ecfg: EngineConfig) -> Optional[int]:
    """Page length if the serving cache can be paged, else None (dense
    fallback).  Shared by both engines so hand-off wire formats agree."""
    plen = serving_page_len(cfg, ecfg.max_len)
    if plen is None or plen % ecfg.block_size:
        return None
    return plen


@functools.lru_cache(maxsize=None)
def _jit_apply(cfg: ModelConfig, mode: str, prefix_aware: bool,
               paged_kernel: bool = False, hidden_in: bool = False,
               hidden_out: bool = False, logits_slice: str = "last"):
    """Jitted forward shared across engine instances.

    Keyed on the (hashable, frozen) ModelConfig so re-rolling an instance
    between the prefill and decode roles reuses compiled executables instead
    of paying a fresh trace+compile per engine object.  Span engines key on
    their span config plus the partial-stack direction flags (``hidden_in``
    consumes the previous span's residual stream, ``hidden_out`` emits one
    for the next).  The cache is donated: decode updates its pools in place
    instead of copying them every step (callers never reuse the cache they
    pass in).

    Each kind of forward compiles under its own program name
    (``jit_prefill``, ``jit_prefill_prefix`` over a held prefix,
    ``jit_decode``, ``jit_verify``), so a profile tells them apart."""
    fwd = functools.partial(T.apply, cfg, mode=mode,
                            logits_slice=logits_slice,
                            prefix_aware=prefix_aware,
                            paged_kernel=paged_kernel, hidden_in=hidden_in,
                            hidden_out=hidden_out)
    if mode == "prefill":
        fwd.__name__ = "prefill_prefix" if prefix_aware else "prefill"
    else:
        fwd.__name__ = "verify" if logits_slice == "all" else "decode"
    return jax.jit(fwd, donate_argnames=("cache",))


def _span_view(cfg: ModelConfig, params,
               layer_span: Optional[Tuple[int, int]]):
    """(span, span_cfg, span_params): identity for a full-stack engine, a
    span-sliced config + restacked per-layer weights otherwise."""
    span = (0, cfg.n_layers) if layer_span is None else tuple(layer_span)
    if span == (0, cfg.n_layers):
        return span, cfg, params
    return span, LM.span_config(cfg, *span), LM.span_params(cfg, params,
                                                            *span)


# Jitted page movers shared by every engine: XLA specializes per
# (pool shape, n_blocks) and the donated scatter writes pages in place —
# hand-off/migration cost is the moved request's pages, not the pool.
_page_gather = jax.jit(KC.gather_pages, static_argnames=("block_size",))
_page_scatter = jax.jit(KC.scatter_pages, static_argnames=("block_size",),
                        donate_argnums=(0,))
_page_reset = jax.jit(KC.reset_page_positions,
                      static_argnames=("block_size",), donate_argnums=(0,))
_page_copy = jax.jit(KC.copy_pages, static_argnames=("block_size",),
                     donate_argnums=(0,))
# A prefill wave's store pages: a hit row's pool-resident prefix copied
# pool to pool, and a row's new blocks cut into store payloads, one
# program each.  Index vectors are padded to the row's table width, so
# these specialize per pool shape only, never per page count.
_pool_copy = jax.jit(KC.copy_pool_pages, static_argnames=("block_size",),
                     donate_argnums=(0,))
_page_payloads = jax.jit(KC.page_payloads, static_argnames=("block_size",))


def ngram_propose(ctx: List[int], k: int, max_n: int = 3) -> List[int]:
    """Draft-free lookahead proposal: suffix-match the last ``n``-gram of
    ``ctx`` (prompt + generated, pending token last) against its own
    earlier occurrences, longest ``n`` first, most recent match wins, and
    propose the up-to-``k`` tokens that followed it.  Purely host-side and
    rebuilt from the Request every call, so it survives extract/adopt,
    preemption and ``move_span`` with no extra wire state."""
    L = len(ctx)
    for n in range(min(max_n, L - 1), 0, -1):
        pat = ctx[L - n:]
        for s in range(L - n - 1, -1, -1):
            if ctx[s:s + n] == pat:
                return ctx[s + n:s + n + k]
    return []


class _Draft:
    """The two-model speculation path's draft side: a small model with its
    own dense per-slot KV cache, advanced one token at a time to propose
    continuations the target then verifies in one batched pass.  The dense
    layout makes draft rollback free — stale rows past a slot's valid
    length are position-masked and overwritten in place on the next pass —
    so rejected proposals just truncate the host length mirror."""

    def __init__(self, cfg: ModelConfig, params, ecfg: EngineConfig):
        assert cfg.uses_kv_cache and not cfg.uses_recurrent_state \
            and cfg.sliding_window is None, \
            "draft model must have rollback-safe (full-attention) KV"
        self.cfg = cfg
        self.params = params
        self.ecfg = ecfg
        self.cache = T.init_cache(cfg, ecfg.max_batch, ecfg.max_len,
                                  dtype=params["embed"].dtype)
        # valid resident tokens per slot (committed-stream prefix length)
        self.len = np.zeros((ecfg.max_batch,), np.int64)
        self._step = _jit_apply(cfg, "decode", False)
        self._prefill = _jit_apply(cfg, "prefill", False)

    def reset_slot(self, slot: int) -> None:
        self.len[slot] = 0

    def prefill_slot(self, slot: int, resident: List[int]) -> None:
        """(Re)build one slot's draft KV from the committed stream —
        adopt/migration receive path, and the resync fallback when the
        draft fell too far behind (e.g. plain-decode interludes)."""
        n = len(resident)
        if n == 0:
            self.len[slot] = 0
            return
        padded = min(_pow2_ceil(n), self.ecfg.max_len)
        buf = np.zeros((1, padded), np.int32)
        buf[0, :n] = np.asarray(resident, np.int32)
        cache = T.init_cache(self.cfg, 1, self.ecfg.max_len,
                             dtype=self.params["embed"].dtype)
        _, cache, _ = self._prefill(self.params, jnp.asarray(buf),
                                    cache=cache,
                                    logits_at=jnp.asarray([n - 1]))
        st = KC.extract_request_state(cache, 0)
        st["length"] = jnp.asarray(n, jnp.int32)
        self.cache = KC.insert_request_state(self.cache, slot, st)
        self.len[slot] = n

    def run(self, schedules: Dict[int, List[int]], n_out: int,
            greedy_from: Dict[int, int]
            ) -> Tuple[Dict[int, List[int]], int]:
        """Batched draft micro-steps.  ``schedules[i]`` is slot i's forced
        input sequence (catch-up tokens then the pending token); once a
        slot's schedule is exhausted its own greedy output feeds back in.
        Returns (per-slot proposals, total micro-steps run): the first
        ``n_out`` greedy outputs per slot starting at the step that
        consumed its pending token (``greedy_from[i]``)."""
        if not schedules:
            return {}, 0
        bsz = self.ecfg.max_batch
        n_steps = max(greedy_from[i] + n_out for i in schedules)
        self.cache["lengths"] = jnp.asarray(self.len.astype(np.int32))
        col = np.zeros((bsz,), np.int32)
        prev = np.zeros((bsz,), np.int32)
        outs: Dict[int, List[int]] = {i: [] for i in schedules}
        for t in range(n_steps):
            for i, sched in schedules.items():
                col[i] = sched[t] if t < len(sched) else prev[i]
            logits, self.cache, _ = self._step(
                self.params, jnp.asarray(col[:, None]), cache=self.cache)
            nxt = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
            for i in schedules:
                prev[i] = nxt[i]
                if t >= greedy_from[i] and len(outs[i]) < n_out:
                    outs[i].append(int(nxt[i]))
        return outs, n_steps


@dataclasses.dataclass
class _Waves:
    """One ``prefill_waves`` batch, carried from wave to wave."""
    reqs: List[Request]
    toks: List[np.ndarray]
    keys_of: List[List[bytes]]         # each prompt's block hash chain
    chunk: Optional[int]
    frames: Optional[jax.Array]
    remaining: List[int]               # rows not yet done
    partials: Dict[int, Dict[str, Any]] = dataclasses.field(
        default_factory=dict)          # chunked rows mid-prompt
    progress: Dict[int, int] = dataclasses.field(
        default_factory=dict)          # tokens resident in partial
    store_matched: Dict[int, int] = dataclasses.field(
        default_factory=dict)          # store hit (for publish)
    published: Dict[int, int] = dataclasses.field(
        default_factory=dict)          # block-aligned publish mark


class PrefillEngine:
    """One prefill instance.

    ``layer_span=(a, b)`` makes this a *partial-stack* instance hosting
    layers [a, b): params, caches and the jitted forward are span-sliced,
    and a chain of span engines covering the stack (serving/span.py's
    ``PrefillPipeline``) reproduces the monolithic prefill exactly.  Pad /
    bucket / wire-format decisions always follow the FULL stack so chained
    stages agree and the hand-off state stays in the universal format.
    Span engines hold no store (store payloads are full-stack)."""

    def __init__(self, cfg: ModelConfig, params, ecfg: EngineConfig,
                 store: Optional[GlobalKVStore] = None, name: str = "prefill0",
                 layer_span: Optional[Tuple[int, int]] = None):
        self.cfg = cfg
        self.params = params
        self.ecfg = ecfg
        self.layer_span, self.scfg, self.sparams = \
            _span_view(cfg, params, layer_span)
        full = self.layer_span == (0, cfg.n_layers)
        self.store = store if full and KC.prefix_cacheable(cfg) else None
        self.name = name
        # set by PrefillPipeline: downstream span engines this one chains
        # its residual stream into (wave by wave, inside run_batch)
        self._followers: List["PrefillEngine"] = []
        self.queue: Deque[Request] = deque()   # routed, not yet prefilled
        self.tokens_prefilled = 0         # suffix tokens actually computed
        self.n_prefilled = 0
        # leading-block hash -> cached tokens; the locality signal the
        # prefix-aware baseline router keys on (Fig. 2a)
        self._leading: Dict[bytes, int] = {}
        self._page_len = _paged_page_len(cfg, ecfg)
        # hit waves (store hits + chunk resumes) run over a paged wave
        # cache: the prefix stays in pages the fused prefill kernel reads
        # through the block table, instead of being re-gathered into a
        # dense row every wave.  Needs every attention cache paged at the
        # full page space (prefix_cacheable) and the standard self-attn
        # write path (no cross-frame KV riding along).
        self._paged_inc = (self._page_len is not None
                           and KC.prefix_cacheable(cfg)
                           and not cfg.cross_attention)
        # recurrent states would integrate junk pad tokens; attention-only
        # stacks mask them, so only those get the padded bucket discipline
        self._pad = not cfg.uses_recurrent_state
        # padded writes must never wrap the SHORTEST attention ring: a
        # wrapped pad token would evict a live in-window key
        attn_lens = _attn_cache_lens(cfg, ecfg.max_len)
        self._pad_cap = min(attn_lens) if attn_lens else ecfg.max_len
        self.prefill_shapes: Set[Tuple[int, int, bool]] = set()
        # store-fetch billing: per-layer prefill compute of one block, the
        # overlap partner of the store's per-layer page streams
        self._t_layer_fetch = (
            A.prefill_time(cfg, ecfg.block_size, ecfg.hw)
            / max(cfg.n_layers, 1) if ecfg.hw is not None else None)
        self.fetch_latency_s = 0.0    # modelled (overlapped when hw set)
        self._prefill = _jit_apply(self.scfg, "prefill", False)
        self._prefill_inc = _jit_apply(self.scfg, "prefill", True)

    def rebase_span(self, layer_span: Tuple[int, int]) -> None:
        """Re-slice this prefill stage to a different contiguous span
        (layer-level migration).  Prefill holds no resident serving state,
        so only the span weights and jitted forwards rebuild."""
        self.layer_span, self.scfg, self.sparams = \
            _span_view(self.cfg, self.params, layer_span)
        self._prefill = _jit_apply(self.scfg, "prefill", False)
        self._prefill_inc = _jit_apply(self.scfg, "prefill", True)

    # -- queue / load ----------------------------------------------------
    def enqueue(self, req: Request) -> None:
        req.advance(Phase.ROUTED)
        req.prefill_instance = self.name
        self.queue.append(req)

    def load_report(self) -> LoadReport:
        """Backlog-normalized utilization: queued prompt tokens against one
        full engine's worth of work (max_batch·max_len).  Prefill holds no
        resident KV — it is handed off — so memory_frac is 0.  With a
        hardware profile configured, ``queue_delay_s`` is the analytical
        time to drain the queued prompt tokens — the TTFT signal
        queue-delay-aware routing minimizes."""
        budget = max(self.ecfg.max_batch * self.ecfg.max_len, 1)
        queued = sum(r.prompt_len for r in self.queue)
        # per-request sum (not one concatenated sequence: the quadratic
        # attention term would overstate a deep queue), same efficiency the
        # router's est_time_s bumps use — one scale end to end
        delay = (sum(A.prefill_time(self.cfg, r.prompt_len, self.ecfg.hw,
                                    efficiency=self.ecfg.efficiency)
                     for r in self.queue)
                 if self.ecfg.hw is not None else 0.0)
        return LoadReport(compute_frac=min(queued / budget, 1.0),
                          memory_frac=0.0, queue_len=len(self.queue),
                          queue_delay_s=delay,
                          cached_prefix_tokens=dict(self._leading),
                          layer_span=self.layer_span)

    # -- prefill ---------------------------------------------------------
    def _match(self, tokens: np.ndarray, keys: List[bytes],
               pages: bool = False) -> Tuple[int, List[Any]]:
        """Longest block-aligned cached prefix + its fetched payloads
        (``pages``: page-resident blocks as ``PageRef``s, uncopied)."""
        if self.store is None or len(tokens) < 2:
            return 0, []
        matched, hit_keys = self.store.match(tokens, keys=keys)
        matched = min(matched, len(tokens) - 1)  # always prefill >=1 token
        matched -= matched % self.ecfg.block_size
        if matched <= 0:
            return 0, []
        hit_keys = hit_keys[: matched // self.ecfg.block_size]
        fetch = self.store.fetch_pages if pages else self.store.fetch
        payloads, t_fetch = fetch(hit_keys,
                                  t_layer_compute=self._t_layer_fetch)
        self.fetch_latency_s += t_fetch
        return matched, payloads

    def _stage_prefix(self, pcache: Dict[str, Any], row: int,
                      items: List[Any], start: int
                      ) -> Tuple[Dict[str, Any], int]:
        """Write a store hit's blocks into wave pages ``start``,
        ``start + 1``, ... of ``row``: page-resident blocks in one page
        copy per source pool, its index vectors padded to the table width;
        payload blocks (demoted to a backing tier, or published and not
        yet handed off) stacked and scattered.  Returns the cache and the
        pages copied."""
        bs = self.ecfg.block_size
        nb_slot = self._page_len // bs
        runs: Dict[int, Tuple[Any, List[int], List[int]]] = {}
        payloads, dst = [], []
        for j, it in enumerate(items):
            if isinstance(it, PageRef):
                run = runs.setdefault(id(it.pool), (it.pool, [], []))
                run[1].append(it.page)
                run[2].append(start + j)
            else:
                payloads.append(it)
                dst.append(start + j)
        for pool, src_pages, dst_pages in runs.values():
            n = len(src_pages)
            src_idx = np.zeros(nb_slot, np.int32)
            dst_idx = np.zeros(nb_slot, np.int32)
            src_idx[:n], dst_idx[:n] = src_pages, dst_pages
            pcache = _pool_copy(pcache, pool.cache, src_idx, dst_idx, n,
                                block_size=bs)
        if payloads:
            pcache = KC.insert_paged_state(
                pcache, row, KC.pages_from_payloads(payloads,
                                                    len(items) * bs),
                dst, bs, scatter=_page_scatter)
        return pcache, len(items) - len(payloads)

    def _match_len(self, tokens: np.ndarray, keys: List[bytes]) -> int:
        """Tentative match length for batch planning: no stats, no fetch."""
        if self.store is None or len(tokens) < 2:
            return 0
        matched, _ = self.store.match(tokens, record_stats=False, keys=keys)
        matched = min(matched, len(tokens) - 1)
        return max(matched - matched % self.ecfg.block_size, 0)

    def _publish(self, tokens: np.ndarray, st: Dict[str, Any],
                 matched: int, keys: List[bytes],
                 pages: Optional[Tuple[Dict[str, Any], np.ndarray]] = None
                 ) -> None:
        """Insert freshly computed full blocks into the global store.  A
        paged wave passes ``pages``, its pool and the row's block-table
        row: the pages ARE the blocks, cut out in one program."""
        bs = self.ecfg.block_size
        if not keys:
            return
        n_full = len(keys) * bs
        self._leading[keys[0]] = max(self._leading.get(keys[0], 0), n_full)
        if self.store is None:
            return
        if pages is not None:
            pool, table_row = pages
            ids = table_row[matched // bs: n_full // bs]
            idx = np.zeros(len(table_row), np.int32)
            idx[:len(ids)] = ids
            payloads = list(_page_payloads(pool, idx,
                                           block_size=bs)[:len(ids)])
        else:
            payloads = [KC.slice_prefix_kv(st, i, i + bs)
                        for i in range(matched, n_full, bs)]
        if payloads:
            nbytes = KC.state_num_bytes(payloads[0])
            self.store.insert(tokens[:n_full],
                              [None] * (matched // bs) + payloads, nbytes,
                              keys=keys)

    def _bucket_len(self, slen: int, matched: int) -> int:
        """Pad a suffix length to its power-of-two bucket, capped at the
        row's remaining capacity in the SHORTEST attention cache (padded
        writes must never wrap a ring past live tokens).  ``matched`` is
        block-aligned, so the cap values form the finite set
        {pad_cap - j*block_size} and the shape set stays bounded (see
        ``prefill_shape_bound``).  A suffix longer than a windowed cache
        falls back to its exact shape — those stacks never had bounded
        shapes, and a windowed stack is never store-cacheable anyway."""
        if not self._pad:
            return slen
        padded = min(_pow2_ceil(slen), self._pad_cap - matched)
        return padded if padded >= slen else slen

    def prefill_shape_bound(self) -> int:
        """Upper bound on distinct jitted prefill shapes under the padded
        bucket discipline: power-of-two rows x (power-of-two suffix
        lengths + block-aligned capacity caps) x hit/miss.  Holds whenever
        suffixes fit the shortest attention cache (always true for
        linear-cache stacks)."""
        def pow2s(cap: int) -> set:
            vals, v = {cap}, 1
            while v < cap:
                vals.add(v)
                v <<= 1
            return vals
        lens = pow2s(self.ecfg.max_len)
        lens |= {self._pad_cap - j * self.ecfg.block_size
                 for j in range(0, self._pad_cap
                                // max(self.ecfg.block_size, 1))}
        return 2 * len(pow2s(max(self.ecfg.max_batch, 1))) \
            * len({v for v in lens if v >= 1})

    def compile_report(self) -> Dict[str, Any]:
        """Distinct (rows, padded_suffix, hit) forward shapes this engine
        ran — each is at most one XLA compile in the shared jit cache."""
        return {"shapes": sorted(self.prefill_shapes),
                "n_shapes": len(self.prefill_shapes),
                "bound": self.prefill_shape_bound()}

    def prefill_waves(self, reqs: List[Request],
                      frames: Optional[jax.Array] = None,
                      chunk_tokens: Optional[int] = None):
        """Generator form of the prefill wave loop: one dense forward per
        ``next()``.

        Requests are bucketed by (padded suffix length, prefix-hit) and one
        bucket runs per wave as a dense forward; blocks it publishes can
        turn later requests' misses into hits, so the rest re-match and
        re-bucket each wave.  Within a wave, miss-requests sharing a
        leading block with an already-chosen one are deferred — their
        shared prefix will be in the store by their turn.  Suffixes and
        row counts pad to power-of-two buckets so the compiled-shape set
        stays bounded (see ``compile_report``); each row's true last token
        drives its logits and the padded tail is masked junk the decoder
        overwrites in place.

        **Chunked prefill** (``chunk_tokens``): a row never computes more
        than ``chunk_tokens`` prompt tokens per wave.  A longer prompt
        carries its partial request state across waves — the next wave
        resumes it through the prefix-aware (incremental) forward, exactly
        the store-hit path, so the final state and logits are bit-equal to
        the one-shot prefill.  This is what lets the event-driven
        orchestrator interleave decode iterations between the micro-chunks
        of a long prefill instead of stalling decode behind it
        (DynaServe-style micro-chunking).

        Yields one record per wave::

            {"rows": padded row count, "padded_len": padded suffix length,
             "tokens": prompt tokens actually computed this wave,
             "resumed": rows resuming a parked chunk partial,
             "hit": the rows resume a held prefix (store hit or chunk),
             "done": [(index into reqs, request_state, last_logits_row)]}

        Request states in ``done`` are in the paged wire format when the
        arch supports it (see models.kvcache).  With chained followers
        (span pipeline) every wave's residual stream flows through each
        span in turn and the per-span states merge back into the
        full-stack wire format, so callers never see the partitioning.
        """
        assert self.layer_span[0] == 0, \
            "mid-stack span engines run only as PrefillPipeline followers"
        chunk = max(int(chunk_tokens), 1) if chunk_tokens else None
        for req in reqs:
            req.advance(Phase.PREFILL)
        toks = [np.asarray(r.prompt, np.int32) for r in reqs]
        # hash each prompt exactly once; every store probe reuses the chain.
        # No store (non-cacheable arch) -> no hashing, and empty chains
        # disable the shared-prefix deferral below.
        keys_of = [chain_hashes(t, self.ecfg.block_size)
                   if self.store is not None else [] for t in toks]
        w = _Waves(reqs, toks, keys_of, chunk, frames,
                   remaining=list(range(len(reqs))))
        while w.remaining:
            # the span closes before the yield: what the caller does
            # between two waves is not the wave's
            with tracing.span("prefill.wave") as sp:
                wave = self._wave(w)
                sp.set_metadata(rows=wave["rows"],
                                padded_len=wave["padded_len"],
                                tokens=wave["tokens"],
                                resumed=wave["resumed"],
                                hit=int(wave["hit"]))
            yield wave

    def _wave(self, w: _Waves) -> Dict[str, Any]:
        """One wave of ``prefill_waves``: re-match and bucket the rows
        left, stage the chosen bucket's cache, run its forward, extract
        each row's state (publishing its new full blocks); returns the
        wave's record."""
        reqs, toks, keys_of, chunk = w.reqs, w.toks, w.keys_of, w.chunk
        partials, progress = w.partials, w.progress
        store_matched, published = w.store_matched, w.published
        remaining, frames = w.remaining, w.frames
        with tracing.span("prefill.match"):
            tlen = {i: progress[i] if i in partials
                    else self._match_len(toks[i], keys_of[i])
                    for i in remaining}
            buckets: Dict[Tuple[int, bool], List[int]] = {}
            for i in remaining:
                slen = len(toks[i]) - tlen[i]
                if chunk is not None and slen > chunk:
                    # mid-prompt chunk wave: EXACT length, never padded —
                    # pad junk would land at positions the next resume
                    # wave's prefix attention still reads (only decode
                    # masks/overwrites future-position junk).  chunk is a
                    # constant, so the shape set stays bounded.
                    buckets.setdefault((chunk, tlen[i] > 0), []).append(i)
                    continue
                buckets.setdefault((self._bucket_len(slen, tlen[i]),
                                    tlen[i] > 0), []).append(i)
            (blen, hit), idxs = max(buckets.items(),
                                    key=lambda kv: len(kv[1]))
            # defer duplicate uncached prefixes to a later wave
            seen_leads, chosen = set(), []
            for i in idxs:
                lead = keys_of[i][0] if keys_of[i] else None
                if tlen[i] == 0 and lead is not None and lead in seen_leads:
                    continue
                if lead is not None:
                    seen_leads.add(lead)
                chosen.append(i)
            # the engine's capacity contract: never a denser forward than
            # the configured batch; the wave loop picks up the overflow
            chosen = chosen[: max(self.ecfg.max_batch, 1)]
            n_resumed = sum(i in partials for i in chosen)
            n_rows = len(chosen)
            wave_frames = frames
            if self._pad and (wave_frames is None
                              or wave_frames.shape[0] == n_rows):
                # row padding: dummy rows get zero frames; a frames batch
                # that doesn't match the wave is left alone so the
                # cross-attention shape check stays loud
                padded_rows = min(_pow2_ceil(n_rows),
                                  max(self.ecfg.max_batch, 1))
                if wave_frames is not None and padded_rows > n_rows:
                    wave_frames = jnp.concatenate([
                        wave_frames,
                        jnp.zeros((padded_rows - n_rows,)
                                  + wave_frames.shape[1:],
                                  wave_frames.dtype)])
                n_rows = padded_rows
        chain = [self] + self._followers
        # hit waves on pageable single-span stacks run PAGED: the cached
        # prefix lives in pool pages the fused prefill kernel reads
        # through the block table — no per-wave dense re-gather
        use_paged = hit and len(chain) == 1 and self._paged_inc
        bs = self.ecfg.block_size
        matched_of: Dict[int, int] = {}
        with tracing.span("prefill.stage") as sp:
            if use_paged:
                nb_slot = self._page_len // bs
                pcache = T.init_paged_cache(
                    self.scfg, n_rows, self.ecfg.max_len, bs,
                    dtype=self.params["embed"].dtype)
                # host mirror of the wave's block tables and lengths: each
                # row owns a contiguous run of wave-local pages (prefix
                # pages first, then fresh pages covering this wave's
                # padded suffix)
                tables = np.full((n_rows, nb_slot), -1, np.int32)
                lengths = np.zeros((n_rows,), np.int32)
                pages_in = 0
                for row, i in enumerate(chosen):
                    start = 1 + row * nb_slot
                    if i in partials:
                        # resume a chunked row: its parked state is
                        # already in the paged wire format
                        matched_of[i] = progress[i]
                        part = partials.pop(i)
                        pcache = KC.insert_paged_state(
                            pcache, row, part,
                            list(range(start, start + int(part["n_blocks"]))),
                            bs, scatter=_page_scatter)
                    else:
                        matched, items = self._match(toks[i], keys_of[i],
                                                     pages=True)
                        matched_of[i] = store_matched[i] = matched
                        if matched > 0:
                            reqs[i].cached_tokens = matched
                            pcache, n = self._stage_prefix(
                                pcache, row, items, start)
                            pages_in += n
                    lengths[row] = matched_of[i]
                    # fresh pages out to the wave's padded write horizon
                    # (pad junk lands in the row's own junk pages, same
                    # overwrite-before-read contract as the dense path)
                    n_need = min(-(-(matched_of[i] + blen) // bs),
                                 nb_slot)
                    tables[row, :n_need] = np.arange(start,
                                                     start + n_need)
                pcache["block_tables"] = jnp.asarray(tables)
                pcache["lengths"] = jnp.asarray(lengths)
                caches = [pcache]
                sp.set_metadata(pages_in=pages_in)
            else:
                caches = [T.init_cache(e.scfg, n_rows, self.ecfg.max_len,
                                       dtype=e.params["embed"].dtype)
                          for e in chain]
                for row, i in enumerate(chosen):
                    if i in partials:
                        # resume a chunked row: its partial (full-stack)
                        # state IS the cache — split per span when chained
                        matched_of[i] = progress[i]
                        part = partials.pop(i)
                        if len(chain) == 1:
                            caches[0] = KC.insert_request_state(
                                caches[0], row, part)
                        else:
                            for k, p_k in enumerate(LM.split_state_spans(
                                    self.cfg, part,
                                    [e.layer_span for e in chain])):
                                caches[k] = KC.insert_request_state(
                                    caches[k], row, p_k)
                        continue
                    matched, payloads = self._match(toks[i], keys_of[i])
                    matched_of[i] = store_matched[i] = matched
                    if matched > 0:
                        # store payloads are full-stack; span chains hold
                        # no store (engine.__init__), so this is lead-only
                        reqs[i].cached_tokens = matched
                        st = KC.extract_request_state(caches[0], row)
                        off = 0
                        for p in payloads:
                            st = KC.merge_prefix_kv(st, p, off)
                            off += bs
                        caches[0] = KC.insert_request_state(caches[0],
                                                            row, st)
            suffix = np.zeros((n_rows, blen), np.int32)
            slens = np.ones((n_rows,), np.int32)   # dummy rows read pos 0
            for row, i in enumerate(chosen):
                s_i = toks[i][matched_of[i]:]
                if chunk is not None:
                    s_i = s_i[:chunk]
                suffix[row, : len(s_i)] = s_i
                slens[row] = len(s_i)
            self.prefill_shapes.add((n_rows, blen, hit))
            la = jnp.asarray(slens - 1)
            x: jax.Array = jnp.asarray(suffix)
        with tracing.span("prefill.forward"):
            for k, e in enumerate(chain):
                if len(chain) == 1:
                    fn = self._prefill_inc if hit else self._prefill
                else:
                    # partial-stack wave: stage k consumes the previous
                    # span's residual stream and (except the last) emits one
                    fn = _jit_apply(e.scfg, "prefill", hit, False,
                                    hidden_in=k > 0,
                                    hidden_out=k < len(chain) - 1)
                x, caches[k], _ = fn(e.sparams, x, cache=caches[k],
                                     frames=wave_frames, logits_at=la)
        logits = x
        done_wave: List[Tuple[int, Dict[str, Any], jax.Array]] = []
        wave_tokens = 0
        with tracing.span("prefill.extract"):
            for row, i in enumerate(chosen):
                # the cache advanced by the padded length; the request's
                # true length is what decode must resume from
                new_len = matched_of[i] + int(slens[row])
                if use_paged:
                    # gather only the used pages (junk pages beyond the
                    # true length drop here, like dense_state_to_paged)
                    st = KC.extract_paged_state(
                        caches[0], row, bs,
                        table_row=tables[row][: -(-new_len // bs)],
                        length=new_len, gather=_page_gather)
                elif len(chain) == 1:
                    st = KC.extract_request_state(caches[0], row)
                else:
                    st = LM.merge_state_spans(
                        self.cfg,
                        [KC.extract_request_state(c, row) for c in caches],
                        [e.layer_span for e in chain])
                st["length"] = jnp.asarray(new_len, jnp.int32)
                self.tokens_prefilled += int(slens[row])
                wave_tokens += int(slens[row])
                # publish freshly completed FULL blocks at every chunk
                # boundary (not just prompt completion): a shared prefix
                # computed by chunk 1 serves sibling requests' waves while
                # this prompt is still mid-chunk — same hit pattern as
                # one-shot prefill
                pub_from = published.get(i, store_matched.get(i, 0))
                keys_part = keys_of[i][: new_len // bs]
                if len(keys_part) * bs > pub_from:
                    with tracing.span("prefill.publish",
                                      blocks=len(keys_part) - pub_from // bs):
                        self._publish(toks[i], st, pub_from, keys_part,
                                      pages=((caches[0], tables[row])
                                             if use_paged else None))
                    published[i] = len(keys_part) * bs
                if new_len < len(toks[i]):
                    # chunk boundary: park the partial state, stay
                    # remaining.  On the paged-wave track partials park in
                    # the paged wire format (fresh chunk-1 states convert
                    # here) so every resume runs the fused paged path
                    if (self._paged_inc and len(chain) == 1
                            and "n_blocks" not in st):
                        st = KC.dense_state_to_paged(st, bs)
                    partials[i] = st
                    progress[i] = new_len
                    continue
                self.n_prefilled += 1
                if self._page_len is not None and "n_blocks" not in st:
                    st = KC.dense_state_to_paged(st, bs)
                done_wave.append((i, st, logits[row]))
        done = {i for i, _, _ in done_wave}
        w.remaining = [i for i in remaining if i not in done]
        return {"rows": n_rows, "padded_len": blen,
                "tokens": wave_tokens, "resumed": n_resumed,
                "hit": hit, "done": done_wave}

    def run_batch(self, reqs: List[Request],
                  frames: Optional[jax.Array] = None,
                  chunk_tokens: Optional[int] = None
                  ) -> List[Tuple[Dict[str, Any], jax.Array]]:
        """Prefill several requests in as few dense forwards as possible
        (drains ``prefill_waves``; see there for the wave/chunk semantics).

        Returns ``[(request_state, last_logits_row)]`` aligned with
        ``reqs``.  With ``chunk_tokens`` set, long prompts prefill in
        successive partial waves — same final states and logits, asserted
        by tests/test_slo_metrics.py."""
        out: List[Optional[Tuple[Dict[str, Any], jax.Array]]] = \
            [None] * len(reqs)
        for wave in self.prefill_waves(reqs, frames=frames,
                                       chunk_tokens=chunk_tokens):
            for i, st, lg in wave["done"]:
                out[i] = (st, lg)
        return out  # type: ignore[return-value]

    def run(self, req: Request, frames: Optional[jax.Array] = None
            ) -> Tuple[Dict[str, Any], jax.Array]:
        """Prefill one request.  Returns (request_state, last_logits)."""
        return self.run_batch([req], frames=frames)[0]

    def run_queued(self, max_reqs: int,
                   frames: Optional[jax.Array] = None,
                   chunk_tokens: Optional[int] = None
                   ) -> List[Tuple[Request, Dict[str, Any], jax.Array]]:
        """Prefill up to ``max_reqs`` from the head of the routed queue."""
        n = min(max_reqs, len(self.queue))
        if n <= 0:
            return []
        batch = [self.queue.popleft() for _ in range(n)]
        results = self.run_batch(batch, frames=frames,
                                 chunk_tokens=chunk_tokens)
        return [(r, st, lg) for r, (st, lg) in zip(batch, results)]


class DecodeEngine:
    """One decode instance: slot-based continuous batching over the paged
    block pool (dense row fallback for archs with no pageable KV).

    ``layer_span=(a, b)`` makes this a *partial-stack* stage hosting layers
    [a, b): its cache / block pool / jitted step cover only the span, and a
    ``serving/span.py`` ``DecodePipeline`` chains stages so the batch's
    residual stream flows through the whole stack each step.  A stage can
    be live-re-sliced to a different span (``rebase_span``) — the execution
    half of §4.1 layer-level migration."""

    def __init__(self, cfg: ModelConfig, params, ecfg: EngineConfig,
                 name: str = "decode0",
                 layer_span: Optional[Tuple[int, int]] = None,
                 draft: Optional[Tuple[ModelConfig, Any]] = None):
        self.cfg = cfg
        self.params = params
        self.ecfg = ecfg
        self.name = name
        self.slots: List[Optional[Request]] = [None] * ecfg.max_batch
        self.next_token = np.zeros((ecfg.max_batch,), np.int32)
        # host-side mirror of active rows' cache lengths: keeps the hot
        # hand-off/control paths free of device syncs
        self._slot_len = np.zeros((ecfg.max_batch,), np.int64)
        self.tokens_decoded = 0
        self.decode_iters = 0     # jitted decode/verify iterations run
        self.spec_proposed = 0    # speculative tokens scored for acceptance
        self.spec_accepted = 0    # of those, committed (bonus not counted)
        self._store: Optional[GlobalKVStore] = None
        self.cow_forks = 0        # shared pages forked copy-on-write
        self.pages_shared = 0     # pages bound by reference (no copy)
        # speculation: mode from the config, a runtime switch the
        # orchestrator flips per load (high batch -> verification compute
        # competes with throughput -> plain decode wins), and per-slot
        # adaptive depth driven by the measured acceptance rate
        self.spec_on = ecfg.speculation != "off"
        self._spec_k = np.full((ecfg.max_batch,), max(ecfg.spec_len, 1),
                               np.int64)
        self._spec_ema = np.ones((ecfg.max_batch,), np.float64)
        self._draft: Optional[_Draft] = None
        if ecfg.speculation == "draft":
            assert draft is not None, \
                "speculation='draft' needs draft=(draft_cfg, draft_params)"
            self._draft = _Draft(draft[0], draft[1], ecfg)
        self._set_span(layer_span)

    def _set_span(self, layer_span: Optional[Tuple[int, int]]) -> None:
        """(Re-)derive span machinery + blank serving state for the span."""
        ecfg = self.ecfg
        self.layer_span, self.scfg, self.sparams = \
            _span_view(self.cfg, self.params, layer_span)
        self.page_len = _paged_page_len(self.scfg, ecfg)
        self.paged = self.page_len is not None
        self._page_spec = None        # shapes of one page's payload
        if self.paged:
            self.cache = T.init_paged_cache(self.scfg, ecfg.max_batch,
                                            ecfg.max_len, ecfg.block_size,
                                            dtype=self.params["embed"].dtype)
            self._nb_slot = self.page_len // ecfg.block_size
            n_phys = 1 + ecfg.max_batch * self._nb_slot
            # host-side mirrors: block tables + the refcounted page pool
            # (block 0 is the reserved scratch page); the device table is
            # refreshed from the mirror whenever it goes stale
            self._bt = np.full((ecfg.max_batch, self._nb_slot), -1, np.int32)
            self._bt_dirty = False    # device table out of sync with _bt
            self.pool = KC.BlockPool(n_phys)
            self._slot_blocks: List[List[int]] = \
                [[] for _ in range(ecfg.max_batch)]
        else:
            self.cache = T.init_cache(self.scfg, ecfg.max_batch, ecfg.max_len,
                                      dtype=self.params["embed"].dtype)
        # page-fused kernel decode is the default on paged pools; an
        # explicit decode_kernel=False keeps the dense gather-then-attend
        # reference path for bit-level A/B runs
        self.use_kernel = self.paged and ecfg.decode_kernel is not False
        self._step = _jit_apply(self.scfg, "decode", False, self.use_kernel)
        # speculation needs rollback-safe KV: attention state (recurrent
        # state integrates every token and cannot rewind) with no sliding
        # window (a ring at window capacity would overwrite live in-window
        # keys when several tokens scatter in one pass), on a full-stack
        # engine (span pipelines commit through their lead's plain step)
        self._spec_ok = (ecfg.speculation != "off"
                         and self.layer_span == (0, self.cfg.n_layers)
                         and self.scfg.uses_kv_cache
                         and not self.scfg.uses_recurrent_state
                         and self.scfg.sliding_window is None
                         and not self.scfg.cross_attention)
        self._verify = _jit_apply(self.scfg, "decode", False,
                                  self.use_kernel, logits_slice="all") \
            if self._spec_ok else None

    def rebase_span(self, layer_span: Tuple[int, int]) -> None:
        """Re-slice this stage to a different contiguous span (layer-level
        migration).  The serving state does not survive the re-slice — the
        DecodePipeline drains every slot first and re-adopts the split
        states afterwards, so the call itself only rebuilds weights, blank
        pools and the jitted step for the new span."""
        assert self.active == 0, "drain slots before re-slicing the span"
        self._set_span(layer_span)

    # -- zero-copy prefix sharing (store-held pages) ---------------------
    @property
    def _free(self) -> List[int]:
        """The pool's free list (compat view; allocation goes through
        ``pool``)."""
        return self.pool.free_list

    def attach_store(self, store: GlobalKVStore) -> None:
        """Let the global store hold refcounted references into this
        engine's block pool (zero-copy prefix sharing): store entries for
        published prefixes point at live pages instead of payload copies,
        and binds/reclaims go through the pool-interface methods below."""
        assert self.paged, "page sharing needs the paged layout"
        self._store = store
        store.attach_pool(self.name, self)

    # pool interface the store calls (attach_pool contract)
    def ref_pages(self, pages: List[int]) -> None:
        self.pool.ref(pages)

    def unref_pages(self, pages: List[int]) -> List[int]:
        return self.pool.unref(pages)

    def materialize(self, page: int) -> Dict[str, Any]:
        """One physical page as a dense per-block store payload (the
        store's demotion/fetch copy-out)."""
        return KC.page_payload(self.cache, int(page), self.ecfg.block_size)

    def page_spec(self) -> Dict[str, Any]:
        """The shapes of one ``materialize`` payload, with no device work
        (what the store bills a page it does not copy by)."""
        if self._page_spec is None:
            self._page_spec = jax.eval_shape(
                lambda c: KC.page_payload(c, 0, self.ecfg.block_size),
                self.cache)
        return self._page_spec

    def slot_pages(self, slot: int) -> List[int]:
        """Physical pages backing ``slot`` in block order (bound+owned)."""
        return list(self._slot_blocks[slot])

    def _ensure_free(self, n: int) -> None:
        """Guarantee ``n`` free pages, demoting LRU store-held pages out
        of HBM first (the store's holds are the reclaimable buffer —
        backing tiers keep the bytes, Fig. 5 tiering)."""
        short = n - len(self.pool.free_list)
        if short > 0 and self._store is not None:
            self._store.reclaim_pool(self.name, short)
        assert len(self.pool.free_list) >= n, "decode block pool exhausted"

    # ------------------------------------------------------------------
    def free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    @property
    def active(self) -> int:
        return sum(s is not None for s in self.slots)

    @property
    def free_slots(self) -> int:
        return self.ecfg.max_batch - self.active

    @property
    def kv_tokens(self) -> int:
        """Resident KV across active slots (host-side, no device sync)."""
        return int(self._slot_len.sum())

    @property
    def span_frac(self) -> float:
        """This stage's share of the stack — 1.0 for full-stack engines."""
        a, b = self.layer_span
        return (b - a) / max(self.cfg.n_layers, 1)

    def load_report(self) -> LoadReport:
        """Occupancy as C/C_max (every step touches every active slot) and
        resident KV against the full cache footprint as M/M_max.  Span
        stages scale both by their share of the stack (Eq. 23–26: per-layer
        compute and KV footprints are additive in hosted layers), so a
        stage hosting more layers reads hotter than its siblings and the
        Algorithm 1 controller can rebalance the boundary."""
        cap = max(self.ecfg.max_batch, 1)
        mem = self.kv_tokens / max(self.ecfg.max_batch * self.ecfg.max_len, 1)
        return LoadReport(compute_frac=self.active / cap * self.span_frac,
                          memory_frac=min(mem, 1.0) * self.span_frac,
                          queue_len=self.active,
                          layer_span=self.layer_span)

    # -- slot transfer ---------------------------------------------------
    def _release_blocks(self, slot: int) -> None:
        # refcount-decrement: pages free only at zero — a block the store
        # (or a sharing sibling) still holds stays resident in place
        self.pool.unref(list(reversed(self._slot_blocks[slot])))
        self._slot_blocks[slot] = []
        self._bt[slot, :] = -1
        # the stale device row must be resynced before the next step: a
        # freed block can be reallocated, and a write through the stale
        # row would land in the new owner's page
        self._bt_dirty = True

    def adopt(self, req: Request, state: Dict[str, Any],
              next_token: int, slot: Optional[int] = None,
              shared_pages: Optional[List[int]] = None) -> int:
        """Place an in-flight request's state into a free slot (migration
        receive path: no token is emitted by the move itself).  Paged
        states land as per-layer page copies into freshly allocated
        blocks; dense states are converted first.  ``slot`` pins the
        target row — pipeline stages must keep identical slot layouts.

        ``shared_pages`` is the zero-copy bind: physical pages of THIS
        pool holding the request's prefix (the store's registered blocks).
        They are bound into the front of the slot's block table by
        reference (refcount++, no gather/scatter) and ``state`` must
        already be head-split past them (``KC.split_paged_state``)."""
        if slot is None:
            slot = self.free_slot()
        assert slot is not None and self.slots[slot] is None, \
            "decode engine full"
        if self.paged:
            shared = [int(p) for p in (shared_pages or ())]
            if shared:
                assert "n_blocks" in state, \
                    "shared-page binds need the paged wire format"
                self.pool.ref(shared)
                self.pages_shared += len(shared)
            if "n_blocks" not in state:
                state = KC.dense_state_to_paged(state, self.ecfg.block_size)
            n = int(state["n_blocks"])
            self._ensure_free(n)
            phys = self.pool.alloc(n)
            self.cache = KC.insert_paged_state(
                self.cache, slot, state, phys, self.ecfg.block_size,
                scatter=_page_scatter)
            row = shared + phys
            self._bt[slot, :] = -1
            self._bt[slot, :len(row)] = row
            self._slot_blocks[slot] = list(row)
            if shared:
                # the scatter wrote a suffix-only table row (pages at
                # logical blocks 0..n-1); rewrite it with the bound
                # prefix in front so the very next gather is correct
                self.cache["block_tables"] = \
                    self.cache["block_tables"].at[slot].set(
                        jnp.asarray(self._bt[slot]))
        else:
            assert not shared_pages, "dense layout cannot bind pages"
            self.cache = KC.insert_request_state(self.cache, slot, state)
        self.slots[slot] = req
        self.next_token[slot] = int(next_token)
        self._slot_len[slot] = int(state["length"])
        # speculation state starts optimistic; the draft cache rebuilds
        # lazily from the committed stream on the first verify iteration
        self._spec_ema[slot] = 1.0
        self._spec_k[slot] = max(self.ecfg.spec_len, 1)
        if self._draft is not None:
            self._draft.reset_slot(slot)
        req.decode_instance = self.name
        return slot

    def insert(self, req: Request, state: Dict[str, Any],
               first_token: int,
               shared_pages: Optional[List[int]] = None) -> int:
        """KV transfer: place a prefilled request into a decode slot."""
        slot = self.adopt(req, state, int(first_token),
                          shared_pages=shared_pages)
        req.generated.append(int(first_token))
        req.advance(Phase.DECODE)
        return slot

    def extract_slot(self, slot: int
                     ) -> Tuple[Request, Dict[str, Any], int]:
        """Pull an active slot's state out (migration send path).  On the
        paged layout only the slot's pages are gathered — cost scales with
        the request's blocks, not the cache size."""
        req = self.slots[slot]
        assert req is not None, f"slot {slot} empty"
        if self.paged:
            state = KC.extract_paged_state(
                self.cache, slot, self.ecfg.block_size,
                table_row=self._bt[slot],
                length=int(self._slot_len[slot]), gather=_page_gather)
            self._release_blocks(slot)
        else:
            state = KC.extract_request_state(self.cache, slot)
        tok = int(self.next_token[slot])
        self.slots[slot] = None
        self._slot_len[slot] = 0
        if self._draft is not None:
            self._draft.reset_slot(slot)
        return req, state, tok

    def drain(self) -> List[Tuple[Request, Dict[str, Any], int]]:
        """Extract every active slot (role re-roll / instance teardown)."""
        return [self.extract_slot(i) for i, s in enumerate(self.slots)
                if s is not None]

    def release_slot(self, slot: int) -> Request:
        """Free an active slot WITHOUT gathering its state — the abort
        path.  The slot's paged blocks return to the free list
        immediately; no token is emitted and no state crosses the wire."""
        req = self.slots[slot]
        assert req is not None, f"slot {slot} empty"
        if self.paged:
            self._release_blocks(slot)
        self.slots[slot] = None
        self._slot_len[slot] = 0
        self.next_token[slot] = 0
        if self._draft is not None:
            self._draft.reset_slot(slot)
        return req

    # -- decode ----------------------------------------------------------
    def _prepare_pages(self, n_tokens: int = 1) -> Dict[int, List[Tuple[int,
                                                                        int]]]:
        """Pre-forward page bookkeeping: make sure every active slot
        EXCLUSIVELY owns the block(s) its next ``n_tokens`` tokens land in
        and the device block table is fresh.  Three cases per write block:
        unassigned (fresh allocation — appends past the boundary, ring
        wraps), shared (refcount > 1: fork it copy-on-write via the free
        list before the jitted step touches it — the writer gets a private
        copy, every other holder keeps the original in place), or already
        exclusive (write through).  Returns the freshly allocated blocks
        per slot as ``{slot: [(table_index, block)]}`` — the speculative
        verify step rolls back the ones no committed token reached."""
        if not self.paged:
            return {}
        fresh: List[int] = []
        fresh_by: Dict[int, List[Tuple[int, int]]] = {}
        cow_src: List[int] = []
        cow_dst: List[int] = []
        for i, s in enumerate(self.slots):
            if s is None:
                continue
            for t in range(n_tokens):
                j = ((int(self._slot_len[i]) + t) % self.page_len) \
                    // self.ecfg.block_size
                pb = int(self._bt[i, j])
                if pb < 0:
                    self._ensure_free(1)
                    nb = self.pool.alloc(1)[0]
                    self._bt[i, j] = nb
                    self._slot_blocks[i].append(nb)
                    fresh.append(nb)
                    fresh_by.setdefault(i, []).append((j, nb))
                elif self.pool.refcount[pb] > 1:
                    # copy-on-write fork: this slot's next token lands in a
                    # page other holders can still read — divergence point
                    self._ensure_free(1)
                    nb = self.pool.alloc(1)[0]
                    self._bt[i, j] = nb
                    self._slot_blocks[i][self._slot_blocks[i].index(pb)] = nb
                    self.pool.unref([pb])
                    cow_src.append(pb)
                    cow_dst.append(nb)
                    self.cow_forks += 1
        if cow_src:
            # duplicate the forked pages (in place, donated) — only the
            # destinations are written, so concurrent readers of the
            # source pages are unperturbed
            self.cache = _page_copy(
                self.cache, jnp.asarray(np.asarray(cow_src, np.int32)),
                jnp.asarray(np.asarray(cow_dst, np.int32)),
                block_size=self.ecfg.block_size)
        if fresh:
            # recycled blocks carry the previous owner's positions —
            # invalidate them (in place, donated) before anything
            # gathers through them
            self.cache = _page_reset(
                self.cache, jnp.asarray(np.asarray(fresh, np.int32)),
                block_size=self.ecfg.block_size)
        if fresh or cow_src or self._bt_dirty:
            self.cache["block_tables"] = jnp.asarray(self._bt)
            self._bt_dirty = False
        return fresh_by

    def _forward_step(self, x: jax.Array, *, hidden_in: bool = False,
                      hidden_out: bool = False) -> jax.Array:
        """One jitted forward over this stage's span.  ``x`` is the token
        column (first stage) or the upstream stage's residual stream;
        returns last-token logits, or the residual stream when
        ``hidden_out`` (pipeline hand-off to the next stage)."""
        if hidden_in or hidden_out:
            fn = _jit_apply(self.scfg, "decode", False, self.use_kernel,
                            hidden_in=hidden_in, hidden_out=hidden_out)
        else:
            fn = self._step
        out, self.cache, _ = fn(self.sparams, x, cache=self.cache)
        return out

    def commit(self, nxt: np.ndarray) -> List[Tuple[Request, int]]:
        """Post-forward bookkeeping: append sampled tokens, retire finished
        requests, free their pages.  Returns finished (request, slot)."""
        finished = []
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            if len(req.generated) >= req.max_new_tokens:
                # budget already met at insert time (max_new_tokens == 1):
                # finish without emitting the extra token
                req.advance(Phase.DONE)
                finished.append((req, i))
                self.slots[i] = None
                self._slot_len[i] = 0
                if self.paged:
                    self._release_blocks(i)
                continue
            tok = int(nxt[i])
            req.generated.append(tok)
            self.next_token[i] = tok
            self._slot_len[i] += 1
            self.tokens_decoded += 1
            done = (len(req.generated) >= req.max_new_tokens
                    or int(self._slot_len[i]) >= self.ecfg.max_len - 1)
            if done:
                req.advance(Phase.DONE)
                finished.append((req, i))
                self.slots[i] = None
                self._slot_len[i] = 0
                if self.paged:
                    self._release_blocks(i)
        return finished

    def follow_commit(self, nxt: np.ndarray,
                      finished_slots: Set[int]) -> None:
        """Mirror a pipeline lead's ``commit`` on a follower stage: same
        per-slot advancement and slot retirement, but no Request mutation —
        the lead owns the request lifecycle and token streams."""
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            if i in finished_slots:
                self.slots[i] = None
                self._slot_len[i] = 0
                if self.paged:
                    self._release_blocks(i)
                continue
            self.next_token[i] = int(nxt[i])
            self._slot_len[i] += 1

    def step(self) -> List[Tuple[Request, int]]:
        """One decode iteration for all active slots.  Returns finished.

        With speculation enabled (and the arch rollback-safe), each
        iteration verifies up to ``spec_len`` proposed tokens in ONE jitted
        multi-query pass and commits the longest greedy-identical prefix
        plus the verifier's own bonus token — between 1 and spec_len+1
        tokens per iteration, bit-identical to plain greedy decode."""
        rows = self.active
        if rows == 0:
            return []
        with tracing.span("decode.step", rows=rows):
            if self.spec_on and self._spec_ok:
                out = self._spec_step()
                if out is not None:
                    return out
            self.decode_iters += 1
            with tracing.span("decode.prepare"):
                self._prepare_pages()
            with tracing.span("decode.forward"):
                logits = self._forward_step(
                    jnp.asarray(self.next_token[:, None]))
            with tracing.span("decode.sync"):
                nxt = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
            with tracing.span("decode.commit"):
                return self.commit(nxt)

    # -- speculative decoding -------------------------------------------
    def _commit_slot(self, i: int, toks: List[int]) -> bool:
        """Append committed tokens under the plain-step finish rules (one
        at a time, stopping at the budget/capacity boundary so surplus
        speculation is dropped, never emitted).  True when finished."""
        req = self.slots[i]
        for tok in toks:
            req.generated.append(int(tok))
            self.next_token[i] = int(tok)
            self._slot_len[i] += 1
            self.tokens_decoded += 1
            if (len(req.generated) >= req.max_new_tokens
                    or int(self._slot_len[i]) >= self.ecfg.max_len - 1):
                return True
        return False

    def _rollback_pages(self, slot: int,
                        fresh_blocks: List[Tuple[int, int]]) -> None:
        """Return freshly speculated blocks no committed token reached to
        the free list.  Only blocks allocated by THIS step's
        ``_prepare_pages`` window are candidates — they are exclusively
        owned by construction (refcount 1), so shared/COW prefix pages are
        never touched; and with speculation gated to full-attention stacks
        the page space never wraps, so a block's table index times
        block_size IS its logical start position.  Rejected tokens left in
        kept boundary blocks sit at positions beyond every future query's
        horizon (masked) until the same offsets are overwritten."""
        bs = self.ecfg.block_size
        new_len = int(self._slot_len[slot])
        for j, blk in fresh_blocks:
            if j * bs >= new_len:
                self._bt[slot, j] = -1
                self._slot_blocks[slot].remove(blk)
                self.pool.unref([blk])
                self._bt_dirty = True

    def _retire_slot(self, i: int) -> None:
        self.slots[i] = None
        self._slot_len[i] = 0
        if self.paged:
            self._release_blocks(i)
        if self._draft is not None:
            self._draft.reset_slot(i)

    def _spec_step(self) -> Optional[List[Tuple[Request, int]]]:
        """One speculative iteration: propose per slot (n-gram table or
        draft model), score the pending token plus all proposals in one
        multi-query verify pass, commit the longest prefix bit-identical
        to greedy plus the bonus token, and roll rejected tokens' pages
        back through the pool.  Returns None when no slot can usefully
        speculate this iteration (the caller falls back to a plain step —
        same committed stream either way)."""
        ecfg = self.ecfg
        bsz = ecfg.max_batch
        # the verify width is a static jit shape: one executable per
        # s_len, and s_len only ranges over 2..spec_len+1.  Every row is
        # written s_len tokens deep, so the width is capped by the
        # tightest slot's remaining capacity (no wrap, see rollback).
        room = min(ecfg.max_len - int(self._slot_len[i])
                   for i, r in enumerate(self.slots) if r is not None)
        s_len = min(ecfg.spec_len + 1, room)
        if s_len < 2:
            return None
        kis: Dict[int, int] = {}
        streams: Dict[int, List[int]] = {}
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            emit_budget = req.max_new_tokens - len(req.generated)
            ki = min(s_len - 1, emit_budget - 1)
            if ecfg.spec_adaptive:
                ki = min(ki, int(self._spec_k[i]))
            if ki <= 0:
                continue
            kis[i] = ki
            streams[i] = [int(t) for t in req.prompt] \
                + [int(t) for t in req.generated]
        props: Dict[int, List[int]] = {}
        g_from: Dict[int, int] = {}
        n_steps = 0
        if self._draft is not None:
            scheds: Dict[int, List[int]] = {}
            for i, stream in streams.items():
                need = len(stream) - 1
                deficit = need - int(self._draft.len[i])
                if (deficit < 0 or deficit > 2 * ecfg.spec_len
                        or self._draft.len[i] == 0):
                    # fell too far behind (plain-decode interludes,
                    # adopt/migration) — rebuild from the committed stream
                    self._draft.prefill_slot(i, stream[:-1])
                    deficit = 0
                scheds[i] = stream[need - deficit:]   # catch-up + pending
                g_from[i] = deficit
            outs, n_steps = self._draft.run(scheds, s_len - 1, g_from)
            props = {i: p[:kis[i]] for i, p in outs.items() if p[:kis[i]]}
        else:
            for i, stream in streams.items():
                p = ngram_propose(stream, kis[i])
                if p:
                    props[i] = p
        if not props:
            return None
        toks = np.zeros((bsz, s_len), np.int32)
        toks[:, 0] = self.next_token
        for i, p in props.items():
            toks[i, 1:1 + len(p)] = p
        fresh_by = self._prepare_pages(s_len)
        # verify positions derive from the device lengths; re-pin them to
        # the host mirror (a previous verify advanced them by its full
        # width, committed or not)
        self.cache["lengths"] = jnp.asarray(self._slot_len.astype(np.int32))
        self.decode_iters += 1
        logits, self.cache, _ = self._verify(
            self.sparams, jnp.asarray(toks), cache=self.cache)
        g = np.asarray(jnp.argmax(logits, axis=-1), np.int32)   # (B, s_len)
        finished = []
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            if len(req.generated) >= req.max_new_tokens:
                # budget already met at insert time: finish w/o emitting
                req.advance(Phase.DONE)
                finished.append((req, i))
                self._retire_slot(i)
                continue
            p = props.get(i, [])
            ki = len(p)
            # longest proposal prefix bit-identical to greedy; g[i, a] is
            # the verifier's own next token after the accepted prefix —
            # the "bonus" every iteration commits (so min 1 token/iter)
            a = 0
            while a < ki and int(toks[i, 1 + a]) == int(g[i, a]):
                a += 1
            self.spec_proposed += ki
            self.spec_accepted += a
            req.spec_proposed += ki
            req.spec_accepted += a
            if ki and ecfg.spec_adaptive:
                self._spec_ema[i] = 0.5 * self._spec_ema[i] + 0.5 * (a / ki)
                self._spec_k[i] = 1 + int(round(
                    self._spec_ema[i] * (ecfg.spec_len - 1)))
            done = self._commit_slot(i, [int(t) for t in g[i, :a + 1]])
            if done:
                req.advance(Phase.DONE)
                finished.append((req, i))
                self._retire_slot(i)
                continue
            if self.paged:
                self._rollback_pages(i, fresh_by.get(i, []))
            if self._draft is not None and i in streams:
                # resident draft prefix that matches the committed stream:
                # everything it was force-fed plus the accepted proposals
                # it consumed while drafting
                fed = n_steps - g_from[i] - 1
                self._draft.len[i] = len(streams[i]) + min(a, max(fed, 0))
        # the verify advanced every row's device length by s_len; re-pin
        # to the committed host lengths so the next step's positions and
        # write offsets are exact
        self.cache["lengths"] = jnp.asarray(self._slot_len.astype(np.int32))
        return finished
