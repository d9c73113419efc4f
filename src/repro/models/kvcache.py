"""Per-request cache state manipulation — dense rows and paged blocks.

Dense primitives (the original whole-cache pytree surgery):

* ``extract_request_state`` — pull one batch row's full serving state
  (KV cache slices, ring buffers, recurrent states) out of a batched cache.
* ``insert_request_state`` — write such a state into a (different) batched
  cache at a free slot.
* ``slice_prefix_kv`` / ``merge_prefix_kv`` — token-range slices of the
  attention KV used by the Global KV Cache Store's block granularity.

Paged primitives (the serving runtime's block-table layout):

* ``dense_to_paged`` / ``paged_to_dense`` — exact conversion between the
  dense batched cache ``(B, L, KV, D)`` and a **block pool**
  ``(n_blocks, block_size, KV, D)`` plus per-slot block tables
  ``(B, L // block_size)`` of physical block ids (-1 = unassigned).
  Physical block 0 is a reserved scratch page that absorbs writes from
  inactive decode rows; it is never referenced by a live table entry.
* ``extract_paged_state`` / ``insert_paged_state`` — move ONE request
  between pools by copying only its pages (cost ∝ the request's blocks,
  not the cache size).  This is the prefill→decode hand-off and the
  attention-level migration payload.
* ``dense_state_to_paged`` / ``paged_state_to_dense`` — re-shape a single
  request's state between the two layouts (the hand-off wire format).
* ``layer_transfer_schedule`` — the ordered per-layer byte schedule of a
  hand-off payload; ``core.analytical.overlapped_schedule_time`` costs it
  with the §4.2 layer-wise transmission overlap (Eq. 4/11).

Zero-copy prefix sharing (the vLLM/Mooncake block-sharing scheme):

* ``BlockPool`` — host-side per-page refcount accounting over a pool.
  A page's refcount counts its holders (slot block-table references plus
  Global-KV-Store holds); pages return to the free list only at refcount
  zero, so a cached prefix is HBM-resident once no matter how many slots
  bind it.
* ``copy_pages`` — jitted copy-on-write fork: duplicate pages inside one
  pool (a writer forks a shared page before the step touches it).
* ``copy_pool_pages`` — copy pages from one pool into another (a store
  hit's pool-resident prefix into a prefill wave's pool), in one program.
* ``split_paged_state`` — drop the leading pages of a paged wire state
  (they are bound by reference instead of scattered).
* ``page_payload`` — one physical page as a dense per-block store payload
  (the demotion path out of HBM into the backing tiers); ``page_payloads``
  does many pages in one program (a prefill wave's publishing).

Only attention KV leaves (``k``/``v``/``pos`` + int8 scales) whose cache
length equals the stack's page length (the longest attention cache) are
paged; ring buffers shorter than that, recurrent states and cross-attention
KV stay slot-dense and ride along unchanged, so conversions are exact for
every ``BlockKind``.

All device-side functions are pure pytree surgery and jit-compatible.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import BlockKind, ModelConfig

Cache = Dict[str, Any]
RequestState = Dict[str, Any]

# Attention-state leaves that live in the block pool; everything else
# (recurrent states, cross KV) stays indexed by batch row.
PAGED_KEYS = ("k", "v", "pos", "k_scale", "v_scale")


def extract_request_state(cache: Cache, row: int) -> RequestState:
    """State of batch row ``row``: groups keep their leading repeat dim."""
    return {
        "length": cache["lengths"][row],
        "groups": jax.tree.map(lambda a: a[:, row], cache["groups"]),
        "rem": jax.tree.map(lambda a: a[row], cache["rem"]),
    }


def insert_request_state(cache: Cache, row, st: RequestState) -> Cache:
    return {
        "lengths": cache["lengths"].at[row].set(st["length"]),
        "groups": jax.tree.map(lambda c, s: c.at[:, row].set(s),
                               cache["groups"], st["groups"]),
        "rem": jax.tree.map(lambda c, s: c.at[row].set(s),
                            cache["rem"], st["rem"]),
    }


def blank_request_state(cache: Cache) -> RequestState:
    """An empty request state matching the cache's structure (for eviction)."""
    z = extract_request_state(cache, 0)

    def reset(a):
        if a.dtype == jnp.int32:
            return jnp.full_like(a, -1) if a.ndim >= 1 else jnp.zeros_like(a)
        return jnp.zeros_like(a)
    st = jax.tree.map(reset, z)
    st["length"] = jnp.zeros((), jnp.int32)
    return st


# ---------------------------------------------------------------------------
# Prefix KV slices (Global KV Cache Store payloads)
# ---------------------------------------------------------------------------

def prefix_cacheable(cfg: ModelConfig) -> bool:
    """The global prefix store holds attention KV; it applies only when the
    stack's attention caches are linear (non-ring) — i.e. pure global
    attention.  Recurrent/windowed archs fall back to recompute (noted in
    DESIGN.md §Arch-applicability).  int8 KV caches are excluded too: the
    per-block payload format carries no per-entry scales
    (``slice_prefix_kv``/``merge_prefix_kv`` move only k/v/pos), so a
    quantized prefix could not round-trip through the store exactly."""
    return (cfg.uses_kv_cache
            and cfg.sliding_window is None
            and not cfg.kv_quant
            and all(b == BlockKind.ATTENTION for b in cfg.blocks()))


def slice_prefix_kv(st: RequestState, start: int, end: int) -> RequestState:
    """Token range [start, end) of every attention KV in a request state.

    Only meaningful for prefix-cacheable configs (linear caches where slot i
    holds token i)."""
    def cut(path_leaf):
        return path_leaf

    def cut_group(g):
        out = {}
        for k, a in g.items():
            if k in ("k", "v"):
                out[k] = a[..., start:end, :, :]
            elif k == "pos":
                out[k] = a[..., start:end]
            else:  # cross KV etc: keep whole
                out[k] = a
        return out
    return {
        "length": jnp.asarray(end - start, jnp.int32),
        "groups": tuple(cut_group(g) for g in st["groups"]),
        "rem": tuple(cut_group(g) for g in st["rem"]),
    }


def merge_prefix_kv(dst: RequestState, src: RequestState,
                    offset: int) -> RequestState:
    """Write ``src``'s token range into ``dst`` starting at ``offset``."""
    n = None

    def put_group(d, s):
        out = dict(d)
        for k in ("k", "v"):
            out[k] = d[k].at[..., offset:offset + s[k].shape[-3], :, :].set(s[k])
        out["pos"] = d["pos"].at[..., offset:offset + s["pos"].shape[-1]].set(
            s["pos"])
        return out
    return {
        "length": jnp.asarray(offset, jnp.int32) + src["length"],
        "groups": tuple(put_group(d, s)
                        for d, s in zip(dst["groups"], src["groups"])),
        "rem": tuple(put_group(d, s)
                     for d, s in zip(dst["rem"], src["rem"])),
    }


def state_num_bytes(st: RequestState) -> int:
    """Total bytes of a request state (migration cost accounting)."""
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(st)
               if hasattr(a, "dtype"))


# ---------------------------------------------------------------------------
# Paged block-table layout
# ---------------------------------------------------------------------------

def page_len(cache: Cache) -> Optional[int]:
    """The stack's page space: the longest attention-cache length (works
    on batched caches and single-request states alike — it only reads the
    trailing dim of "pos" leaves).  Groups whose cache is exactly this
    long are paged; shorter ring buffers stay slot-dense (their KV is
    bounded by the window anyway)."""
    best = 0
    for g in tuple(cache["groups"]) + tuple(cache["rem"]):
        if isinstance(g, dict) and "pos" in g:
            best = max(best, int(g["pos"].shape[-1]))
    return best or None


# trailing (non-batch, non-seq) dims of each pageable leaf kind
_LEAF_TAIL = {"k": 2, "v": 2, "pos": 0, "k_scale": 1, "v_scale": 1}


def _is_dense_paged_leaf(key: str, a: Any, batch_axis: int, plen: int) -> bool:
    """A dense-layout cache leaf that belongs in the block pool:
    (lead..., B, plen, tail...)."""
    return (key in PAGED_KEYS and hasattr(a, "shape")
            and a.ndim == batch_axis + 2 + _LEAF_TAIL[key]
            and a.shape[batch_axis + 1] == plen)


def _is_pool_leaf(key: str, a: Any, batch_axis: int, batch: int,
                  block_size: int) -> bool:
    """A pool-layout cache leaf: (lead..., n_blocks, block_size, tail...).
    The pool always holds the scratch block, so n_blocks != batch — that is
    what distinguishes it from a dense ring leaf whose window happens to
    equal block_size."""
    return (key in PAGED_KEYS and hasattr(a, "shape")
            and a.ndim == batch_axis + 2 + _LEAF_TAIL[key]
            and a.shape[batch_axis + 1] == block_size
            and a.shape[batch_axis] != batch)


def _leaf_fill(key: str):
    return -1 if key == "pos" else 0


def dense_to_paged(cache: Cache, block_size: int) -> Cache:
    """Exact conversion: dense batched cache -> block pool + block tables.

    Every logical block of every row gets a physical page (identity
    mapping), so the round trip through ``paged_to_dense`` is bit-exact for
    arbitrary cache contents.  Physical block 0 is the reserved scratch
    page."""
    batch = int(cache["lengths"].shape[0])
    plen = page_len(cache)
    if plen is None:
        raise ValueError("cache has no attention KV to page")
    if plen % block_size:
        raise ValueError(f"page length {plen} not a multiple of "
                         f"block_size {block_size}")
    nb = plen // block_size
    tables = (np.arange(batch * nb, dtype=np.int32).reshape(batch, nb) + 1)

    def conv(g: Dict[str, Any], batch_axis: int) -> Dict[str, Any]:
        out = {}
        for key, a in g.items():
            if _is_dense_paged_leaf(key, a, batch_axis, plen):
                lead = a.shape[:batch_axis]
                tail = a.shape[batch_axis + 2:]
                pages = a.reshape(lead + (batch * nb, block_size) + tail)
                scratch = jnp.full(lead + (1, block_size) + tail,
                                   _leaf_fill(key), a.dtype)
                out[key] = jnp.concatenate([scratch, pages], axis=batch_axis)
            else:
                out[key] = a
        return out

    return {
        "lengths": cache["lengths"],
        "block_tables": jnp.asarray(tables),
        "groups": tuple(conv(g, 1) for g in cache["groups"]),
        "rem": tuple(conv(g, 0) for g in cache["rem"]),
    }


def paged_to_dense(pcache: Cache, block_size: int) -> Cache:
    """Exact inverse of ``dense_to_paged``.  Unassigned logical blocks
    (table entry -1) materialize as canonical blanks (zeros, pos = -1)."""
    tables = pcache["block_tables"]
    batch, nb = tables.shape
    plen = nb * block_size
    safe = jnp.maximum(tables, 0)
    live = tables >= 0

    def conv(g: Dict[str, Any], batch_axis: int) -> Dict[str, Any]:
        out = {}
        for key, a in g.items():
            if _is_pool_leaf(key, a, batch_axis, batch, block_size):
                idx = (slice(None),) * batch_axis + (safe,)
                gathered = a[idx]               # (..., B, nb, bs, tail)
                lshape = ((1,) * batch_axis + (batch, nb)
                          + (1,) * (gathered.ndim - batch_axis - 2))
                gathered = jnp.where(live.reshape(lshape), gathered,
                                     jnp.asarray(_leaf_fill(key), a.dtype))
                lead = gathered.shape[:batch_axis]
                tail = gathered.shape[batch_axis + 3:]
                out[key] = gathered.reshape(lead + (batch, plen) + tail)
            else:
                out[key] = a
        return out

    return {
        "lengths": pcache["lengths"],
        "groups": tuple(conv(g, 1) for g in pcache["groups"]),
        "rem": tuple(conv(g, 0) for g in pcache["rem"]),
    }


# -- per-request page moves (hand-off / migration payloads) -----------------

def _slot_index(batch_axis: int, slot) -> Tuple:
    return (slice(None),) * batch_axis + (slot,)


def gather_pages(pcache: Cache, idx: jax.Array, slot, length, *,
                 block_size: int) -> RequestState:
    """Jit-compatible core of ``extract_paged_state``: gather the pages at
    physical ids ``idx`` (traced (n,) int32) plus the slot-dense leaves of
    ``slot``.  Cost ∝ n pages, never the pool."""
    batch = int(pcache["block_tables"].shape[0])

    def conv(g: Dict[str, Any], batch_axis: int) -> Dict[str, Any]:
        out = {}
        for key, a in g.items():
            if _is_pool_leaf(key, a, batch_axis, batch, block_size):
                out[key] = a[(slice(None),) * batch_axis + (idx,)]
            elif isinstance(a, dict):
                out[key] = jax.tree.map(
                    lambda x: x[_slot_index(batch_axis, slot)], a)
            else:
                out[key] = a[_slot_index(batch_axis, slot)]
        return out

    return {
        "length": jnp.asarray(length, jnp.int32),
        "groups": tuple(conv(g, 1) for g in pcache["groups"]),
        "rem": tuple(conv(g, 0) for g in pcache["rem"]),
    }


def scatter_pages(pcache: Cache, st: RequestState, idx: jax.Array, slot, *,
                  block_size: int) -> Cache:
    """Jit-compatible core of ``insert_paged_state``: write the state's
    pages into physical blocks ``idx`` plus the slot-dense leaves, table
    row and length.  Under jit with the cache donated, these are in-place
    page writes — cost ∝ n pages, never the pool."""
    batch = int(pcache["block_tables"].shape[0])
    nb = int(pcache["block_tables"].shape[1])
    n = int(idx.shape[0])

    def conv(c: Dict[str, Any], s: Dict[str, Any],
             batch_axis: int) -> Dict[str, Any]:
        out = {}
        for key, a in c.items():
            if _is_pool_leaf(key, a, batch_axis, batch, block_size):
                out[key] = a.at[(slice(None),) * batch_axis + (idx,)].set(
                    s[key])
            elif isinstance(a, dict):
                out[key] = jax.tree.map(
                    lambda x, y: x.at[_slot_index(batch_axis, slot)].set(y),
                    a, s[key])
            else:
                out[key] = a.at[_slot_index(batch_axis, slot)].set(s[key])
        return out

    row = jnp.full((nb,), -1, jnp.int32).at[:n].set(idx.astype(jnp.int32))
    return {
        "lengths": pcache["lengths"].at[slot].set(st["length"]),
        "block_tables": pcache["block_tables"].at[slot].set(row),
        "groups": tuple(conv(c, s, 1)
                        for c, s in zip(pcache["groups"], st["groups"])),
        "rem": tuple(conv(c, s, 0)
                     for c, s in zip(pcache["rem"], st["rem"])),
    }


def extract_paged_state(pcache: Cache, slot: int, block_size: int, *,
                        table_row: Optional[np.ndarray] = None,
                        length=None, gather=gather_pages) -> RequestState:
    """One slot's state out of a paged cache: only its pages are gathered
    (cost ∝ the request's blocks), plus its slot-dense leaves.  ``gather``
    may be a jitted wrapper of ``gather_pages`` (the serving engines pass
    one) — the protocol lives here either way."""
    row = np.asarray(table_row if table_row is not None
                     else pcache["block_tables"][slot])
    phys = row[row >= 0]
    st = gather(pcache, jnp.asarray(phys, jnp.int32), slot,
                pcache["lengths"][slot] if length is None else length,
                block_size=block_size)
    st["n_blocks"] = int(len(phys))
    return st


def insert_paged_state(pcache: Cache, slot: int, st: RequestState,
                       phys_blocks: Sequence[int], block_size: int, *,
                       scatter=scatter_pages) -> Cache:
    """Write a paged request state into ``slot``: per-layer page copies into
    the given physical blocks plus slot-dense writes.  The executable form
    of the block-table hand-off.  ``scatter`` may be a jitted (donating)
    wrapper of ``scatter_pages``."""
    n = int(st["n_blocks"])
    assert len(phys_blocks) == n, (len(phys_blocks), n)
    body = {k: v for k, v in st.items() if k != "n_blocks"}
    return scatter(pcache, body,
                   jnp.asarray(np.asarray(phys_blocks, np.int32)),
                   slot, block_size=block_size)


def reset_page_positions(pcache: Cache, phys_blocks: Sequence[int],
                         block_size: int) -> Cache:
    """Invalidate (pos = -1) the given physical blocks' position entries.
    Freed blocks keep their stale K/V — harmless once masked — but stale
    *positions* would alias a new owner's live range, so every block must
    pass through here between owners.  Jit-compatible (``phys_blocks`` may
    be a traced array) — the engines run it jitted with the cache donated
    so it is an in-place write of the freed rows."""
    idx = jnp.asarray(phys_blocks).astype(jnp.int32)
    batch = int(pcache["block_tables"].shape[0])

    def conv(g: Dict[str, Any], batch_axis: int) -> Dict[str, Any]:
        a = g.get("pos")
        if a is None or not _is_pool_leaf("pos", a, batch_axis, batch,
                                          block_size):
            return g
        out = dict(g)
        out["pos"] = a.at[(slice(None),) * batch_axis + (idx,)].set(-1)
        return out

    return {**pcache,
            "groups": tuple(conv(g, 1) for g in pcache["groups"]),
            "rem": tuple(conv(g, 0) for g in pcache["rem"])}


# -- refcounted page sharing (zero-copy prefix reuse) -----------------------

class BlockPool:
    """Host-side refcounted page accounting for one paged block pool.

    A page's refcount counts its *holders*: slot block-table references
    plus Global-KV-Store holds.  ``alloc`` hands out exclusive pages
    (refcount 0 → 1), ``ref`` adds a holder to a live page (the zero-copy
    bind), and ``unref`` drops one — a page returns to the free list only
    when the last holder lets go (free-at-zero), so a shared prefix is
    HBM-resident once no matter how many slots bind it.  Pages below
    ``n_reserved`` (the scratch page) are never allocated or refcounted.
    """

    def __init__(self, n_pages: int, n_reserved: int = 1):
        assert n_pages > n_reserved >= 0
        self.n_pages = n_pages
        self.n_reserved = n_reserved
        self.refcount = np.zeros(n_pages, np.int32)
        # descending so .pop() hands out low pages first (matches the
        # pre-refcount engines' allocation order)
        self.free_list: List[int] = list(range(n_pages - 1,
                                               n_reserved - 1, -1))
        self.peak_used = 0

    @property
    def used(self) -> int:
        """Live (refcount > 0) pages."""
        return self.n_pages - self.n_reserved - len(self.free_list)

    def alloc(self, n: int) -> List[int]:
        """Take ``n`` exclusive pages off the free list (refcount 1)."""
        assert len(self.free_list) >= n, "block pool exhausted"
        pages = [self.free_list.pop() for _ in range(n)]
        for p in pages:
            assert self.refcount[p] == 0
            self.refcount[p] = 1
        self.peak_used = max(self.peak_used, self.used)
        return pages

    def ref(self, pages: Sequence[int]) -> None:
        """Add one holder to each (live) page — the zero-copy bind."""
        for p in pages:
            assert self.refcount[p] > 0, f"ref of dead page {p}"
            self.refcount[p] += 1

    def unref(self, pages: Sequence[int]) -> List[int]:
        """Drop one holder from each page; pages that hit refcount zero
        return to the free list and are reported back (free-at-zero)."""
        freed = []
        for p in pages:
            assert self.refcount[p] > 0, f"unref of free page {p}"
            self.refcount[p] -= 1
            if self.refcount[p] == 0:
                self.free_list.append(p)
                freed.append(p)
        return freed

    def check(self, holders: Optional[Sequence[Sequence[int]]] = None
              ) -> None:
        """Conservation invariant: every page is reserved, free (refcount
        0) or live (refcount > 0), with no duplicates on the free list.
        With ``holders`` (one page-list per holder: slot rows, store
        holds) also checks each page's refcount equals its holder count —
        the 'free list + Σ live table entries accounts for every page'
        property."""
        free = set(self.free_list)
        assert len(free) == len(self.free_list), "duplicate free pages"
        for p in range(self.n_reserved):
            assert self.refcount[p] == 0 and p not in free
        for p in range(self.n_reserved, self.n_pages):
            assert (self.refcount[p] == 0) == (p in free), \
                f"page {p}: refcount {self.refcount[p]} vs free list"
        assert len(self.free_list) + self.used \
            == self.n_pages - self.n_reserved
        if holders is not None:
            counts = np.zeros(self.n_pages, np.int64)
            for pages in holders:
                for p in pages:
                    counts[p] += 1
            assert np.array_equal(counts, self.refcount.astype(np.int64)), \
                "refcounts do not match holder lists"


def copy_pages(pcache: Cache, src_idx: jax.Array, dst_idx: jax.Array, *,
               block_size: int) -> Cache:
    """Copy-on-write fork: duplicate pool pages ``src_idx`` into
    ``dst_idx`` across every paged leaf.  Jit-compatible; run donated it
    is an in-place write of the destination pages only — the writer forks
    a shared page before the step touches it, readers keep the source."""
    batch = int(pcache["block_tables"].shape[0])

    def conv(g: Dict[str, Any], batch_axis: int) -> Dict[str, Any]:
        out = {}
        for key, a in g.items():
            if _is_pool_leaf(key, a, batch_axis, batch, block_size):
                sel = (slice(None),) * batch_axis
                out[key] = a.at[sel + (dst_idx,)].set(a[sel + (src_idx,)])
            else:
                out[key] = a
        return out

    return {**pcache,
            "groups": tuple(conv(g, 1) for g in pcache["groups"]),
            "rem": tuple(conv(g, 0) for g in pcache["rem"])}


def copy_pool_pages(dst: Cache, src: Cache, src_idx: jax.Array,
                    dst_idx: jax.Array, n, *, block_size: int) -> Cache:
    """Copy pages ``src_idx[:n]`` of pool ``src`` into pages
    ``dst_idx[:n]`` of pool ``dst`` (two caches of one stack) across every
    paged leaf.  Jit-compatible, with ``n`` traced: the index vectors may
    be padded to one fixed length, so the compiled shape follows the two
    pools alone.  Pages move one at a time in a loop: on the TPU a gather
    over the page axis first copies the whole source pool.  Run donated,
    the destination pages are written in place."""
    batch = int(dst["block_tables"].shape[0])

    def conv(j, d: Dict[str, Any], s: Dict[str, Any],
             batch_axis: int) -> Dict[str, Any]:
        out = dict(d)
        for key, a in d.items():
            if _is_pool_leaf(key, a, batch_axis, batch, block_size):
                page = jax.lax.dynamic_slice_in_dim(s[key], src_idx[j], 1,
                                                    batch_axis)
                out[key] = jax.lax.dynamic_update_slice_in_dim(
                    a, page, dst_idx[j], batch_axis)
        return out

    def body(j, gr):
        return (tuple(conv(j, d, s, 1)
                      for d, s in zip(gr[0], src["groups"])),
                tuple(conv(j, d, s, 0) for d, s in zip(gr[1], src["rem"])))

    groups, rem = jax.lax.fori_loop(0, n, body,
                                    (dst["groups"], dst["rem"]))
    return {**dst, "groups": groups, "rem": rem}


def split_paged_state(st: RequestState, n_head_blocks: int,
                      block_size: int) -> RequestState:
    """Drop the first ``n_head_blocks`` pages from a paged wire state.

    The bind path of zero-copy sharing: the head pages already live in
    the destination pool (the store's registered prefix) and are bound by
    reference, so only the suffix pages cross the wire.  ``length`` stays
    the full request length — the block table row is prefix + suffix."""
    n = int(st["n_blocks"])
    assert 0 <= n_head_blocks <= n, (n_head_blocks, n)
    if n_head_blocks == 0:
        return st

    def conv(g: Dict[str, Any], seq_axis: int) -> Dict[str, Any]:
        out = {}
        for key, a in g.items():
            if (key in PAGED_KEYS and hasattr(a, "shape")
                    and a.ndim == seq_axis + 2 + _LEAF_TAIL[key]
                    and a.shape[seq_axis] == n
                    and a.shape[seq_axis + 1] == block_size):
                out[key] = a[(slice(None),) * seq_axis
                             + (slice(n_head_blocks, None),)]
            else:
                out[key] = a
        return out

    return {
        "length": st["length"],
        "n_blocks": n - n_head_blocks,
        "groups": tuple(conv(g, 1) for g in st["groups"]),
        "rem": tuple(conv(g, 0) for g in st["rem"]),
    }


def page_payload(pcache: Cache, page: int, block_size: int) -> RequestState:
    """One physical page's KV as a dense per-block store payload — the
    same shape ``slice_prefix_kv`` produces for one block, so demoted
    pages re-enter through ``merge_prefix_kv`` on the fetch path
    unchanged.  Only meaningful for prefix-cacheable stacks (every
    attention cache paged at the full page space)."""
    batch = int(pcache["block_tables"].shape[0])

    def conv(g: Dict[str, Any], batch_axis: int) -> Dict[str, Any]:
        out = {}
        for key, a in g.items():
            if _is_pool_leaf(key, a, batch_axis, batch, block_size):
                out[key] = a[(slice(None),) * batch_axis + (page,)]
        return out

    return {
        "length": jnp.asarray(block_size, jnp.int32),
        "groups": tuple(conv(g, 1) for g in pcache["groups"]),
        "rem": tuple(conv(g, 0) for g in pcache["rem"]),
    }


def page_payloads(pcache: Cache, pages: jax.Array, *,
                  block_size: int) -> Tuple[RequestState, ...]:
    """``page_payload`` of every page in ``pages`` (traced (n,) int32), in
    one program.  A caller pads ``pages`` to a fixed length and keeps the
    payloads it asked for, so the compiled shape follows the pool alone,
    not the number of pages."""
    batch = int(pcache["block_tables"].shape[0])

    def conv(g: Dict[str, Any], batch_axis: int) -> Dict[str, Any]:
        return {key: a[(slice(None),) * batch_axis + (pages,)]
                for key, a in g.items()
                if _is_pool_leaf(key, a, batch_axis, batch, block_size)}

    groups = tuple(conv(g, 1) for g in pcache["groups"])
    rem = tuple(conv(g, 0) for g in pcache["rem"])
    return tuple({"length": jnp.asarray(block_size, jnp.int32),
                  "groups": tuple({k: a[:, j] for k, a in g.items()}
                                  for g in groups),
                  "rem": tuple({k: a[j] for k, a in g.items()} for g in rem)}
                 for j in range(int(pages.shape[0])))


def pages_from_payloads(payloads: Sequence[RequestState],
                        length: int) -> RequestState:
    """Stack per-block store payloads (``slice_prefix_kv`` shape, one
    block each) into a paged wire state — the store-hit entry point of the
    paged incremental prefill path.  Instead of merging fetched blocks
    into a dense row and re-gathering them every wave, the blocks become
    the request's prefix *pages* directly and ``insert_paged_state``
    scatters them into the wave pool once."""
    assert payloads, "no payloads to page"
    n = len(payloads)

    def conv(gs: Sequence[Dict[str, Any]], seq_axis: int) -> Dict[str, Any]:
        out = {}
        for key, a in gs[0].items():
            if (key in PAGED_KEYS and hasattr(a, "shape")
                    and a.ndim == seq_axis + 1 + _LEAF_TAIL[key]):
                out[key] = jnp.stack([g[key] for g in gs], axis=seq_axis)
            else:       # cross KV etc: payloads carry identical copies
                out[key] = a
        return out

    return {
        "length": jnp.asarray(length, jnp.int32),
        "n_blocks": n,
        "groups": tuple(conv([p["groups"][gi] for p in payloads], 1)
                        for gi in range(len(payloads[0]["groups"]))),
        "rem": tuple(conv([p["rem"][gi] for p in payloads], 0)
                     for gi in range(len(payloads[0]["rem"]))),
    }


# -- dense request state <-> paged request state ----------------------------

def dense_state_to_paged(st: RequestState, block_size: int, *,
                         length: Optional[int] = None) -> RequestState:
    """Reshape a dense request state into its used pages.  Blocks beyond
    the used prefix are dropped — they are masked (pos = -1) junk that the
    decode engine overwrites before ever attending to it."""
    n_tok = int(st["length"] if length is None else length)
    plen = page_len(st)      # same "pos"-leaf rule as the cache layout
    if plen is None:
        raise ValueError("request state has no attention KV to page")
    nb_slot = plen // block_size
    n_used = min(max(-(-n_tok // block_size), 0), nb_slot)

    def conv(g: Dict[str, Any], seq_axis: int) -> Dict[str, Any]:
        out = {}
        for key, a in g.items():
            if (key in PAGED_KEYS and hasattr(a, "shape")
                    and a.ndim == seq_axis + 1 + _LEAF_TAIL[key]
                    and a.shape[seq_axis] == plen):
                lead = a.shape[:seq_axis]
                tail = a.shape[seq_axis + 1:]
                pages = a.reshape(lead + (nb_slot, block_size) + tail)
                out[key] = pages[(slice(None),) * seq_axis
                                 + (slice(0, n_used),)]
            else:
                out[key] = a
        return out

    return {
        "length": jnp.asarray(n_tok, jnp.int32),
        "n_blocks": n_used,
        "groups": tuple(conv(g, 1) for g in st["groups"]),
        "rem": tuple(conv(g, 0) for g in st["rem"]),
    }


def paged_state_to_dense(ps: RequestState, block_size: int,
                         plen: int) -> RequestState:
    """Inverse of ``dense_state_to_paged``: pad back out to the full page
    space with canonical blanks."""
    nb_slot = plen // block_size
    n = int(ps["n_blocks"])

    def conv(g: Dict[str, Any], seq_axis: int) -> Dict[str, Any]:
        out = {}
        for key, a in g.items():
            if (key in PAGED_KEYS and hasattr(a, "shape")
                    and a.ndim == seq_axis + 2 + _LEAF_TAIL[key]
                    and a.shape[seq_axis] == n
                    and a.shape[seq_axis + 1] == block_size):
                pad = [(0, 0)] * a.ndim
                pad[seq_axis] = (0, nb_slot - n)
                full = jnp.pad(a, pad, constant_values=_leaf_fill(key))
                lead = full.shape[:seq_axis]
                tail = full.shape[seq_axis + 2:]
                out[key] = full.reshape(lead + (plen,) + tail)
            else:
                out[key] = a
        return out

    return {
        "length": ps["length"],
        "groups": tuple(conv(g, 1) for g in ps["groups"]),
        "rem": tuple(conv(g, 0) for g in ps["rem"]),
    }


def layer_transfer_schedule(st: RequestState,
                            base_layer: int = 0) -> List[Tuple[int, int]]:
    """Ordered per-layer (layer_index, nbytes) transfer schedule of a
    hand-off payload, in stack execution order (scan over repeats, pattern
    positions within a repeat, remainder layers last).  This is the wire
    schedule of the §4.2 layer-wise overlapped transmission; cost it with
    ``core.analytical.overlapped_schedule_time``.  ``base_layer`` offsets
    the indices for *span* states (layer_migration.split_state_spans), so
    a migrated span's schedule reports absolute stack positions."""
    sched: List[Tuple[int, int]] = []
    groups = tuple(st["groups"])
    n_rep = 0
    if groups:
        arrs = [a for a in jax.tree.leaves(groups[0]) if hasattr(a, "shape")]
        n_rep = int(arrs[0].shape[0]) if arrs else 0
        per_g = [sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(g) if hasattr(a, "dtype"))
                 // max(n_rep, 1) for g in groups]
        for r in range(n_rep):
            for gi, nbytes in enumerate(per_g):
                sched.append((base_layer + r * len(groups) + gi, nbytes))
    base = base_layer + n_rep * len(groups)
    for i, g in enumerate(st["rem"]):
        sched.append((base + i, sum(a.size * a.dtype.itemsize
                                    for a in jax.tree.leaves(g)
                                    if hasattr(a, "dtype"))))
    return sched
