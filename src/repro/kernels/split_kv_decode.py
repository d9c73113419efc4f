"""Split-KV / page-fused attention Pallas TPU kernel.

The kernelized form of the paper's attention-level migration primitive
(Eq. 6–10 / Fig. 4): each grid step computes attention of a row's queries
against ONE KV block and emits the partial softmax statistics (o, l, m).
The exact global softmax is reconstructed by
``core.attention_offload.combine_partials`` — locally across the block axis
(flash-decoding) or across devices (attention migration / context
parallelism), where only the tiny (o, l, m) triple crosses the interconnect.

One kernel serves every entry point: the page-fused decode step (one query
per row), speculative verification (S queries per row), the paged prefix of
a chunked-prefill resume (a whole chunk of queries per row) and the dense
split-KV decode (a linear cache viewed as one page per KV block).  The KV
block axis is embarrassingly parallel (partials are order-independent), so
every grid dimension is "parallel" — the combine owns the reduction.

Mosaic tiling: every block's last two dims are the array's own, so the
wrappers hand the kernel rank-padded views — queries grouped per KV head
``(B, KV, S*G, D)``, per-row query positions ``(B, S*G, 1)`` and page
positions ``(P, 1, bs)`` — and un-permute the partials in XLA afterwards.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(tbl_ref, q_ref, posq_ref, k_ref, v_ref, pos_ref, *rest,
                  scale: float, window: Optional[int],
                  soft_cap: Optional[float], quant: bool):
    """One (b, page-slot) grid step: the row's R = S*G query rows per KV
    head score ONE physical page.

    The block table rode in as a scalar-prefetch operand: the index_map
    already steered this step's k/v/pos blocks to the row's j-th physical
    page, so the kernel reads KV pages *in place* — no gathered linear
    view exists anywhere.  Dead slots (table entry -1) were clamped to the
    reserved scratch page by the index_map; the in-body table check masks
    them (scratch can hold pos >= 0 junk from inactive-row writes).  Each
    query row carries its own absolute position, so the causal mask among
    in-flight tokens (verify, chunk resume) falls out of the same
    ``pos <= pos_q`` comparison that masks history."""
    if quant:
        ks_ref, vs_ref, o_ref, l_ref, m_ref = rest
    else:
        o_ref, l_ref, m_ref = rest
    b_ = pl.program_id(0)
    j = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)                     # (KV, R, D)
    k = k_ref[0].astype(jnp.float32)                     # (bs, KV, D)
    v = v_ref[0].astype(jnp.float32)
    pos = pos_ref[0]                                     # (1, bs)
    pq = posq_ref[0]                                     # (R, 1)
    valid = (tbl_ref[b_, j] >= 0) & (pos >= 0) & (pos <= pq)   # (R, bs)
    if window is not None:
        valid &= pos > pq - window
    # scores: (KV, R, bs)
    s = jax.lax.dot_general(
        q, k.transpose(1, 2, 0),                         # (KV,R,D)x(KV,D,bs)
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale
    if quant:
        # per-entry K scales fold into the scores (before the soft cap),
        # mirroring masked_attention's dequant ordering
        s = s * ks_ref[0].astype(jnp.float32).T[:, None, :]
    if soft_cap is not None:
        s = jnp.tanh(s / soft_cap) * soft_cap
    s = jnp.where(valid[None], s, NEG_INF)
    m = jnp.max(s, axis=-1)                              # (KV, R)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(valid[None], p, 0.0)
    l = jnp.sum(p, axis=-1)                              # l from p BEFORE the
    if quant:                                            # V dequant — exactly
        p = p * vs_ref[0].astype(jnp.float32).T[:, None, :]   # the dense order
    o = jax.lax.dot_general(
        p, v.transpose(1, 0, 2),                         # (KV,R,bs)x(KV,bs,D)
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)              # (KV, R, D)
    o_ref[0, 0] = o
    l_ref[0, 0] = l
    m_ref[0, 0] = m


def paged_partials(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                   pos_pages: jax.Array, block_tables: jax.Array,
                   pos_q: jax.Array, *,
                   window: Optional[int] = None,
                   scale: Optional[float] = None,
                   soft_cap: Optional[float] = None,
                   k_scale_pages: Optional[jax.Array] = None,
                   v_scale_pages: Optional[jax.Array] = None,
                   interpret: bool = False
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Page-fused attention partials: S queries per row, one pass over
    the row's pages — the KV-block grid axis IS the page axis.

    q: (B, S, H, D) queries at absolute positions pos_q: (B, S) int32 (the
    decode step has S = 1; speculative verification scores the pending
    token plus S-1 proposals; a chunked-prefill resume scores the whole
    chunk against its published prefix).  k_pages/v_pages: (P, bs, KV, D)
    physical block pools; pos_pages: (P, bs) int32 (-1 = hole);
    block_tables: (B, nb) int32 (-1 = unassigned, physical page 0 is
    reserved scratch).  Optional int8 pools carry k_scale_pages /
    v_scale_pages (P, bs, KV) f32 for in-kernel dequant.

    The table is a scalar-prefetch operand so the k/v/pos index_maps
    resolve ``block_tables[b, j]`` at grid-step issue time — the kernel
    streams pages straight out of the pool with zero dense KV gather.
    Returns per-page partials o (B, nb, S, H, D), l/m (B, nb, S, H) f32 for
    ``combine_partials`` (Eq. 6–10)."""
    b, s_len, h, d = q.shape
    n_pages, bs, kv = k_pages.shape[:3]
    nb = block_tables.shape[1]
    group = h // kv
    rows = s_len * group
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    quant = k_scale_pages is not None
    kernel = functools.partial(_paged_kernel, scale=scale, window=window,
                               soft_cap=soft_cap, quant=quant)
    # query row r = s*G + g of KV head c is head c*G + g of query s
    qk = q.reshape(b, s_len, kv, group, d).transpose(0, 2, 1, 3, 4) \
        .reshape(b, kv, rows, d)
    posq = jnp.repeat(pos_q.astype(jnp.int32), group, axis=1)[..., None]

    def page(idx_fn):
        # clamp dead entries (-1) to the scratch page; the kernel masks them
        return lambda b_, j, tbl: idx_fn(jnp.maximum(tbl[b_, j], 0))

    in_specs = [
        pl.BlockSpec((1, kv, rows, d), lambda b_, j, tbl: (b_, 0, 0, 0)),
        pl.BlockSpec((1, rows, 1), lambda b_, j, tbl: (b_, 0, 0)),
        pl.BlockSpec((1, bs, kv, d), page(lambda p_: (p_, 0, 0, 0))),
        pl.BlockSpec((1, bs, kv, d), page(lambda p_: (p_, 0, 0, 0))),
        pl.BlockSpec((1, 1, bs), page(lambda p_: (p_, 0, 0))),
    ]
    operands = [qk, posq, k_pages, v_pages,
                pos_pages.reshape(n_pages, 1, bs)]
    if quant:
        in_specs += [pl.BlockSpec((1, bs, kv), page(lambda p_: (p_, 0, 0))),
                     pl.BlockSpec((1, bs, kv), page(lambda p_: (p_, 0, 0)))]
        operands += [k_scale_pages, v_scale_pages]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nb),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, kv, rows, d),
                         lambda b_, j, tbl: (b_, j, 0, 0, 0)),
            pl.BlockSpec((1, 1, kv, rows), lambda b_, j, tbl: (b_, j, 0, 0)),
            pl.BlockSpec((1, 1, kv, rows), lambda b_, j, tbl: (b_, j, 0, 0)),
        ],
    )
    o, l, m = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, nb, kv, rows, d), jnp.float32),
            jax.ShapeDtypeStruct((b, nb, kv, rows), jnp.float32),
            jax.ShapeDtypeStruct((b, nb, kv, rows), jnp.float32),
        ],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), *operands)
    o = o.reshape(b, nb, kv, s_len, group, d).transpose(0, 1, 3, 2, 4, 5) \
        .reshape(b, nb, s_len, h, d)

    def heads(x):
        return x.reshape(b, nb, kv, s_len, group).transpose(0, 1, 3, 2, 4) \
            .reshape(b, nb, s_len, h)

    return o, heads(l), heads(m)


def paged_decode_partials(q: jax.Array, k_pages: jax.Array,
                          v_pages: jax.Array, pos_pages: jax.Array,
                          block_tables: jax.Array, pos_q: jax.Array,
                          **kw) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The single-token decode step of ``paged_partials``: q (B, H, D),
    pos_q (B,).  Returns o (B, nb, H, D), l/m (B, nb, H) f32."""
    o, l, m = paged_partials(q[:, None], k_pages, v_pages, pos_pages,
                             block_tables, pos_q[:, None], **kw)
    return o[:, :, 0], l[:, :, 0], m[:, :, 0]


def split_kv_decode_partials(q: jax.Array, k: jax.Array, v: jax.Array,
                             valid: jax.Array, *,
                             block_k: int = 256,
                             scale: Optional[float] = None,
                             interpret: bool = False
                             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Split-KV decode over a linear cache: q (B, H, D); k, v (B, L, KV, D);
    valid (B, L) bool.  L must be a multiple of block_k (ops wrapper pads
    with valid=False).

    The cache is viewed as ``L / block_k`` pages per row (row b's j-th
    block is page ``b*J + j``) with position 0 where valid and -1 where
    not, so the page-fused kernel with every query at position 0 computes
    exactly the masked per-block partials.  Returns o (B, J, H, D),
    l (B, J, H), m (B, J, H) f32."""
    b, l_tot, kv, d = k.shape
    bk = min(block_k, l_tot)
    assert l_tot % bk == 0, (l_tot, bk)
    n_blk = l_tot // bk
    tables = jnp.arange(b * n_blk, dtype=jnp.int32).reshape(b, n_blk)
    pos = jnp.where(valid, 0, -1).astype(jnp.int32).reshape(b * n_blk, bk)
    return paged_decode_partials(
        q, k.reshape(b * n_blk, bk, kv, d), v.reshape(b * n_blk, bk, kv, d),
        pos, tables, jnp.zeros((b,), jnp.int32), scale=scale,
        interpret=interpret)
