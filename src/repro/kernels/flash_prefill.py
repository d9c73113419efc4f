"""Flash attention (prefill) Pallas TPU kernel.

Canonical TPU flash pattern: grid (B, H, n_q, n_k) with the KV-block axis
innermost and sequential; running (m, l, acc) live in VMEM scratch across
KV blocks and the normalized output is written once on the last KV block.

VMEM working set per grid step (bf16 in, f32 accum):
    q (bq, D) + k (bk, D) + v (bk, D) + acc (bq, D) f32 + m/l (bq,)
With bq = bk = 256, D = 128: ~0.5 MB — comfortably within 16 MB VMEM and
MXU-aligned (multiples of 128 on the contracted and lane dims).

GQA is handled by the k/v index_map (kv_head = h // group), sliding windows
by position masking; both cost nothing in the steady state.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *rest, scale: float, bq: int,
                  bk: int, n_k: int, seq_offset: int,
                  window: Optional[int], soft_cap: Optional[float],
                  partials: bool):
    """One (b, h, iq, jk) grid step."""
    if partials:
        l_ref, m_ref, m_scr, l_scr, acc_scr = rest
    else:
        m_scr, l_scr, acc_scr = rest
    iq = pl.program_id(2)
    jk = pl.program_id(3)

    @pl.when(jk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                    # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)                    # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if soft_cap is not None:
        s = jnp.tanh(s / soft_cap) * soft_cap
    # positions: queries sit at seq_offset + iq*bq + row
    pos_q = seq_offset + iq * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bk), 0)
    pos_k = jk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = pos_k <= pos_q
    if window is not None:
        mask &= pos_k > pos_q - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                     # (bq,)
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=-1)
    acc = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(jk == n_k - 1)
    def _finalize():
        if partials:
            # unnormalized (o, l, m) — combine_partials owns the division
            o_ref[0, 0] = acc_scr[...].astype(o_ref.dtype)
            l_ref[0, 0, 0] = l_scr[...]
            m_ref[0, 0, 0] = m_scr[...]
        else:
            l = jnp.maximum(l_scr[...], 1e-30)
            o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_prefill(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  window: Optional[int] = None,
                  scale: Optional[float] = None,
                  soft_cap: Optional[float] = None,
                  block_q: int = 256, block_k: int = 256,
                  seq_offset: int = 0,
                  return_partials: bool = False,
                  interpret: bool = False):
    """q: (B, S, H, D); k, v: (B, L, KV, D); S, L multiples of the blocks
    (ops.flash_attention pads).  Queries occupy positions
    seq_offset..seq_offset+S-1 of the key axis.

    ``return_partials=True`` emits the unnormalized partial-softmax triple
    (o (B,S,H,D) f32, l (B,S,H) f32, m (B,S,H) f32) instead of the
    normalized output, so the caller can combine this in-context partition
    with others (paged-prefix chunked prefill) via combine_partials."""
    b, s, h, d = q.shape
    l, kv = k.shape[1], k.shape[2]
    assert h % kv == 0
    group = h // kv
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    bq, bk = min(block_q, s), min(block_k, l)
    n_q, n_k = s // bq, l // bk

    # layouts: q (B, H, S, D); k/v (B, KV, L, D)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _flash_kernel, scale=scale, bq=bq, bk=bk, n_k=n_k,
        seq_offset=seq_offset, window=window, soft_cap=soft_cap,
        partials=return_partials)
    qspec = pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0))
    out_specs = [qspec]
    out_shape = [jax.ShapeDtypeStruct(
        (b, h, s, d), jnp.float32 if return_partials else q.dtype)]
    if return_partials:
        # (b, h, 1, s): a (1, bq) block keeps Mosaic's (8, 128) tiling rule
        lspec = pl.BlockSpec((1, 1, 1, bq),
                             lambda b_, h_, i, j: (b_, h_, 0, i))
        out_specs += [lspec, lspec]
        out_shape += [jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32)] * 2
    out = pl.pallas_call(
        kernel,
        grid=(b, h, n_q, n_k),
        in_specs=[
            qspec,
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, i, j: (b_, h_ // group, j, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, i, j: (b_, h_ // group, j, 0)),
        ],
        out_specs=out_specs if return_partials else out_specs[0],
        out_shape=out_shape if return_partials else out_shape[0],
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    if return_partials:
        o, ll, mm = out
        return (o.transpose(0, 2, 1, 3), ll[:, :, 0].transpose(0, 2, 1),
                mm[:, :, 0].transpose(0, 2, 1))
    return out.transpose(0, 2, 1, 3)
