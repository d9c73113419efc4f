"""Attention-level migration: split-KV attention with partial-softmax combine.

This is the paper's Eq. 6–10 (§4.1, Fig. 4): the KV cache is partitioned —
along the **head** axis (hot/cold GPU in the paper) or the **sequence** axis
(context-parallel long decode) — each partition computes attention locally,
and only tiny softmax statistics are exchanged to reconstruct the exact
global softmax.

The paper's formulation accumulates raw ``exp(S)``; we use the numerically
stable running-max (flash/log-sum-exp) form — identical math, bf16-safe:

    per partition j:  m_j = max(S_j),  l_j = Σ exp(S_j − m_j),
                      o_j = exp(S_j − m_j) · V_j
    combine:          M = max_j m_j
                      L = Σ_j l_j e^{m_j − M}
                      O = Σ_j o_j e^{m_j − M} / L

Three implementations, all bit-agreeing up to float assoc.:

* ``partial_attention`` / ``combine_partials`` — pure jnp building blocks
  (the ref oracle for the Pallas kernel lives in kernels/ref.py and calls
  these).
* ``split_kv_attention`` — N-way partition executed as a Python loop over
  partitions (the single-host "hot/cold device" execution used by the
  serving engine when Algorithm 1 triggers an attention-level migration).
* ``sharded_decode_attention`` — shard_map version: KV sequence sharded over
  a mesh axis; partials combined with one tiny all-gather (the multi-pod
  context-parallel path used by long_500k).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------

def partial_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      mask: Optional[jax.Array] = None,
                      scale: Optional[float] = None,
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Attention over a KV partition, returning partial stats.

    q: (B, H, D); k, v: (B, L, H, D) — heads already aligned (GQA expansion
    is done by the caller).  mask: (B, L) or (B, H, L), True = attend.
    Returns (o, l, m): o (B,H,D) un-normalized output premultiplied by
    exp(−m) softmax numerator, l (B,H) partial denominator, m (B,H) max.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhd,blhd->bhl", q, k).astype(jnp.float32) * scale
    if mask is not None:
        if mask.ndim == 2:
            mask = mask[:, None, :]
        s = jnp.where(mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1)                                   # (B,H)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    l = jnp.sum(p, axis=-1)                                   # (B,H)
    o = jnp.einsum("bhl,blhd->bhd", p, v.astype(jnp.float32))
    return o, l, m


def combine_partials(os_: Sequence[jax.Array], ls: Sequence[jax.Array],
                     ms: Sequence[jax.Array]) -> jax.Array:
    """Exact softmax reconstruction from per-partition (o, l, m)."""
    m_all = jnp.stack(list(ms))                               # (J,B,H)
    big_m = jnp.max(m_all, axis=0)                            # (B,H)
    big_m_safe = jnp.where(jnp.isfinite(big_m), big_m, 0.0)
    num = 0.0
    den = 0.0
    for o, l, m in zip(os_, ls, ms):
        w = jnp.exp(jnp.where(jnp.isfinite(m), m, -jnp.inf) - big_m_safe)
        w = jnp.where(jnp.isfinite(m), w, 0.0)
        num = num + o * w[..., None]
        den = den + l * w
    den = jnp.maximum(den, 1e-30)
    return num / den[..., None]


def expand_gqa(q: jax.Array, n_kv: int) -> jax.Array:
    """(B, H, D) queries -> grouped (B, KV, G, D) for per-KV-head partials."""
    b, h, d = q.shape
    return q.reshape(b, n_kv, h // n_kv, d)


# ---------------------------------------------------------------------------
# N-way split-KV attention (the migration execution path)
# ---------------------------------------------------------------------------

def split_kv_attention(q: jax.Array, k_parts: Sequence[jax.Array],
                       v_parts: Sequence[jax.Array],
                       masks: Optional[Sequence[Optional[jax.Array]]] = None,
                       axis: str = "seq",
                       scale: Optional[float] = None) -> jax.Array:
    """Exact attention with KV scattered across partitions.

    axis="seq":   every part holds all heads, a slice of the sequence.
                  q (B,H,D); parts (B,L_j,H,D) -> (B,H,D)
    axis="head":  paper Fig. 4 — parts hold disjoint head subsets.
                  q (B,H,D) split to match; parts (B,L,H_j,D) -> concat.
    """
    if masks is None:
        masks = [None] * len(k_parts)
    if axis == "seq":
        parts = [partial_attention(q, k, v, m, scale)
                 for k, v, m in zip(k_parts, v_parts, masks)]
        return combine_partials(*zip(*parts))
    if axis == "head":
        outs = []
        h0 = 0
        for k, v, m in zip(k_parts, v_parts, masks):
            hj = k.shape[2]
            o, l, mm = partial_attention(q[:, h0:h0 + hj], k, v, m, scale)
            outs.append(combine_partials([o], [l], [mm]))
            h0 += hj
        return jnp.concatenate(outs, axis=1)
    raise ValueError(axis)


# ---------------------------------------------------------------------------
# shard_map context-parallel decode attention (long_500k path)
# ---------------------------------------------------------------------------

def sharded_decode_attention(mesh, q: jax.Array, k: jax.Array, v: jax.Array,
                             kv_valid: jax.Array, *,
                             seq_axis: str = "data",
                             scale: Optional[float] = None) -> jax.Array:
    """Decode attention with the KV sequence sharded over ``seq_axis``.

    q: (B, H, D) replicated over seq_axis; k, v: (B, L, H, D) sharded on L;
    kv_valid: (B, L) bool sharded on L.  Output replicated.

    Each shard computes its partial (o, l, m); exact combine uses a single
    all_gather of (H·D + 2H) floats per device — the paper's "only ℓ and O
    are exchanged" property (Eq. 8–10), generalized N-way.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])

    def local(qb, kb, vb, validb):
        o, l, m = partial_attention(qb, kb, vb, validb, scale)
        # gather tiny stats from every shard; payload per shard is
        # B*(H*D + 2H) floats — independent of L.
        og = jax.lax.all_gather(o, seq_axis)           # (J,B,H,D)
        lg = jax.lax.all_gather(l, seq_axis)           # (J,B,H)
        mg = jax.lax.all_gather(m, seq_axis)
        return combine_partials(list(og), list(lg), list(mg)).astype(qb.dtype)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(None, seq_axis, None, None), P(None, seq_axis, None, None),
                  P(None, seq_axis)),
        out_specs=P(),
        check_vma=False,
    )(q, k, v, kv_valid)


# ---------------------------------------------------------------------------
# Reference (naive paper-form, for tests): single softmax over concat
# ---------------------------------------------------------------------------

def reference_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        mask: Optional[jax.Array] = None,
                        scale: Optional[float] = None) -> jax.Array:
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhd,blhd->bhl", q, k).astype(jnp.float32) * scale
    if mask is not None:
        if mask.ndim == 2:
            mask = mask[:, None, :]
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    return jnp.einsum("bhl,blhd->bhd", p, v.astype(jnp.float32))
