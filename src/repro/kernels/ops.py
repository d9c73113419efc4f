"""Public jit'd wrappers around the Pallas kernels.

Handles padding to block multiples, the backend choice (compiled kernels
on a TPU, Pallas interpret mode on the CPU, where the tests validate the
same kernel bodies; any other backend is an error), and the
partial-combine epilogue for decode.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.attention_offload import combine_partials
from .flash_prefill import flash_prefill
from .split_kv_decode import (paged_decode_partials, paged_partials,
                              split_kv_decode_partials)


def _interpret() -> bool:
    """Interpret mode on the CPU, compiled kernels on a TPU.  Any other
    backend raises: a run that landed somewhere unexpected must not pass
    for a kernel run."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"no Pallas kernel path for backend {backend!r}")
    return backend == "cpu"


def _pad_to(x: jax.Array, axis: int, mult: int, value=0.0) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(jax.jit, static_argnames=("window", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    window: Optional[int] = None,
                    block_q: int = 256, block_k: int = 256,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Causal (sliding-window) GQA flash attention.

    q: (B, S, H, D); k, v: (B, S, KV, D).  Returns (B, S, H, D)."""
    if interpret is None:
        interpret = _interpret()
    b, s, h, d = q.shape
    pow2 = 1 << max((s - 1).bit_length(), 3)
    bq = min(block_q, pow2)
    qp = _pad_to(q, 1, bq)
    tgt = qp.shape[1]
    bk = min(block_k, tgt)
    kp = _pad_to(_pad_to(k, 1, tgt), 1, bk)   # padded keys are causal-masked
    vp = _pad_to(_pad_to(v, 1, tgt), 1, bk)
    out = flash_prefill(qp, kp, vp, window=window, block_q=bq,
                        block_k=bk, interpret=interpret)
    return out[:, :s]


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     valid: jax.Array, *,
                     block_k: int = 256,
                     interpret: Optional[bool] = None) -> jax.Array:
    """Single-token decode attention over a (ring or linear) KV cache.

    q: (B, H, D); k, v: (B, L, KV, D); valid: (B, L) bool.
    Kernel emits per-block partials; the exact softmax is reconstructed via
    combine_partials (Eq. 8–10)."""
    if interpret is None:
        interpret = _interpret()
    bk = min(block_k, k.shape[1])
    kp = _pad_to(k, 1, bk)
    vp = _pad_to(v, 1, bk)
    validp = _pad_to(valid, 1, bk, value=False)
    o, l, m = split_kv_decode_partials(q, kp, vp, validp, block_k=bk,
                                       interpret=interpret)
    n_blk = o.shape[1]
    out = combine_partials([o[:, j] for j in range(n_blk)],
                           [l[:, j] for j in range(n_blk)],
                           [m[:, j] for j in range(n_blk)])
    return out.astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("window", "scale", "soft_cap",
                                             "interpret"))
def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, pos_pages: jax.Array,
                           block_tables: jax.Array, pos_q: jax.Array, *,
                           window: Optional[int] = None,
                           scale: Optional[float] = None,
                           soft_cap: Optional[float] = None,
                           k_scale_pages: Optional[jax.Array] = None,
                           v_scale_pages: Optional[jax.Array] = None,
                           interpret: Optional[bool] = None) -> jax.Array:
    """Page-fused split-KV decode straight out of the block pool.

    The block table is fused into the kernel's index_map (scalar
    prefetch): the KV-block grid axis of the kernel IS the page axis, so
    the kernel reads pages in place — no dense gathered KV view exists —
    and the per-page partial (o, l, m) triples are exactly what migration
    ships.  Optional int8 pools dequant in-kernel via the per-entry scale
    pages; soft-capped stacks stay on the kernel path because
    ``tanh(s/c)*c`` is elementwise on pre-softmax scores, which keeps the
    split-softmax combine exact.

    q: (B, H, D); k/v_pages: (P, bs, KV, D); pos_pages: (P, bs);
    block_tables: (B, nb) (-1 = unassigned); pos_q: (B,).
    Returns (B, H, D) in q's dtype."""
    if interpret is None:
        interpret = _interpret()
    o, l, m = paged_decode_partials(
        q, k_pages, v_pages, pos_pages, block_tables, pos_q,
        window=window, scale=scale, soft_cap=soft_cap,
        k_scale_pages=k_scale_pages, v_scale_pages=v_scale_pages,
        interpret=interpret)
    nb = o.shape[1]
    out = combine_partials([o[:, j] for j in range(nb)],
                           [l[:, j] for j in range(nb)],
                           [m[:, j] for j in range(nb)])
    return out.astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("window", "scale", "soft_cap",
                                             "interpret"))
def paged_verify_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, pos_pages: jax.Array,
                           block_tables: jax.Array, pos_q: jax.Array, *,
                           window: Optional[int] = None,
                           scale: Optional[float] = None,
                           soft_cap: Optional[float] = None,
                           k_scale_pages: Optional[jax.Array] = None,
                           v_scale_pages: Optional[jax.Array] = None,
                           interpret: Optional[bool] = None) -> jax.Array:
    """Speculative verification: S queries per slot in one page-fused pass.

    Identical page streaming to ``paged_decode_attention`` — the grid and
    the bytes read are the same; only the per-page arithmetic grows by the
    verify length, which is exactly why verification sits higher on the
    roofline than single-token decode.  Per-query positions ``pos_q``
    (B, S) carry both the history horizon and the causal order among the
    in-flight speculative tokens.

    q: (B, S, H, D); k/v_pages: (P, bs, KV, D); pos_pages: (P, bs);
    block_tables: (B, nb); pos_q: (B, S).  Returns (B, S, H, D)."""
    if interpret is None:
        interpret = _interpret()
    o, l, m = paged_partials(
        q, k_pages, v_pages, pos_pages, block_tables, pos_q,
        window=window, scale=scale, soft_cap=soft_cap,
        k_scale_pages=k_scale_pages, v_scale_pages=v_scale_pages,
        interpret=interpret)
    nb = o.shape[1]
    out = combine_partials([o[:, j] for j in range(nb)],
                           [l[:, j] for j in range(nb)],
                           [m[:, j] for j in range(nb)])
    return out.astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("window", "scale", "soft_cap",
                                             "block_q", "block_k",
                                             "interpret"))
def paged_prefill_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                            k_pages: jax.Array, v_pages: jax.Array,
                            pos_pages: jax.Array, block_tables: jax.Array,
                            positions: jax.Array, *,
                            window: Optional[int] = None,
                            scale: Optional[float] = None,
                            soft_cap: Optional[float] = None,
                            block_q: int = 256, block_k: int = 256,
                            interpret: Optional[bool] = None) -> jax.Array:
    """Fused paged chunked prefill: resume-chunk queries attend over the
    already-published paged prefix IN-KERNEL (pages steered by the block
    table's scalar-prefetch index_map) plus the in-flight suffix (causal
    flash partials) — two partitions of one exact split softmax, combined
    via the Eq. 6–10 statistics.  The per-wave dense prefix re-gather is
    gone: nothing ever materializes a (B, L, KV, D) prefix view.

    q: (B, S, H, D); k, v: (B, S, KV, D) suffix keys/values;
    k/v_pages: (P, bs, KV, D); pos_pages: (P, bs); block_tables: (B, nb);
    positions: (B, S) absolute query positions.  Returns (B, S, H, D)."""
    if interpret is None:
        interpret = _interpret()
    b, s, h, d = q.shape
    # prefix partition: one partial per physical page
    po, plv, pm = paged_partials(
        q, k_pages, v_pages, pos_pages, block_tables, positions,
        window=window, scale=scale, soft_cap=soft_cap, interpret=interpret)
    # suffix partition: causal flash over the chunk itself (both axes are
    # the same token range, so relative positions encode the causal and
    # window masks exactly)
    pow2 = 1 << max((s - 1).bit_length(), 3)
    bq = min(block_q, pow2)
    qp = _pad_to(q, 1, bq)
    tgt = qp.shape[1]
    bk = min(block_k, tgt)
    kp = _pad_to(_pad_to(k, 1, tgt), 1, bk)
    vp = _pad_to(_pad_to(v, 1, tgt), 1, bk)
    so, sl, sm = flash_prefill(qp, kp, vp, window=window, scale=scale,
                               soft_cap=soft_cap, block_q=bq, block_k=bk,
                               return_partials=True, interpret=interpret)
    nb = po.shape[1]
    out = combine_partials(
        [po[:, j] for j in range(nb)] + [so[:, :s]],
        [plv[:, j] for j in range(nb)] + [sl[:, :s]],
        [pm[:, j] for j in range(nb)] + [sm[:, :s]])
    return out.astype(q.dtype)


def decode_partials(q: jax.Array, k: jax.Array, v: jax.Array,
                    valid: jax.Array, *, block_k: int = 256,
                    interpret: Optional[bool] = None):
    """Raw partials — what attention-level migration ships across devices."""
    if interpret is None:
        interpret = _interpret()
    bk = min(block_k, k.shape[1])
    kp = _pad_to(k, 1, bk)
    vp = _pad_to(v, 1, bk)
    validp = _pad_to(valid, 1, bk, value=False)
    return split_kv_decode_partials(q, kp, vp, validp, block_k=bk,
                                    interpret=interpret)
