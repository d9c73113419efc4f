"""The plain reference forward pass, and its lower-precision control.

A straightforward ``jax.numpy`` forward of the decoder the configuration
file describes: token embedding, then per layer RMSNorm, grouped-query
attention with rotary positions and a causal mask, a residual add,
RMSNorm, and either a SwiGLU MLP or a top-k softmax-routed mixture of
SwiGLU experts (gates renormalised over the k chosen), a residual add;
then a final RMSNorm and the output head.  It imports nothing of the
program and keeps no cache: it recomputes the whole sequence.  It reads
the weights the benchmark made from the seed, in the program's layout
(see ``model.py``).  Like the program it has no Granite embedding,
attention, residual or logits multipliers: the configuration file runs
them at their neutral values and names the published ones as a departure
of the program.

``precision="float32"``: every operand widened to float32, matmuls at
``highest`` precision.  ``precision="fp8"``: the control — every matmul
operand rounded to float8 e4m3 with a scale per tensor (weights) or per
row (activations), accumulated in float32; the nearest precision below
the configuration's bfloat16.

The layers run in a ``lax.scan`` that widens one layer's weights at a
time, so the float32 pass fits beside the served bfloat16 weights.  The
mixture of experts is computed densely over all experts and masked by the
gates, which costs E/k times the active FLOPs and changes nothing in the
result.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _round8(x: jax.Array, axis) -> jax.Array:
    """Round to float8 e4m3 with the scale that maps |x|max to 448."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _mm(spec: str, a: jax.Array, w: jax.Array, fp8: bool,
        w_axes=None) -> jax.Array:
    """einsum of an activation and a weight (or a second activation)."""
    if fp8:
        a = _round8(a, -1)
        w = _round8(w, w_axes)
    return jnp.einsum(spec, a, w, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """x: (S, H, D); rotate the two halves of each head."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(cfg, p, h, pos, fp8: bool):
    hd = cfg["head_dim"]
    g = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    q = _mm("sd,dhk->shk", h, p["wq"], fp8)
    k = _mm("sd,dhk->shk", h, p["wk"], fp8)
    v = _mm("sd,dhk->shk", h, p["wv"], fp8)
    q = _rope(q, pos, cfg["rope_theta"])
    k = _rope(k, pos, cfg["rope_theta"])
    # query head j reads key/value head j // g
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = _mm("qhk,lhk->hql", q.transpose(0, 1, 2), k, fp8, w_axes=-1)
    s = s / jnp.sqrt(jnp.float32(hd))
    causal = pos[None, :, None] >= pos[None, None, :]
    s = jnp.where(causal, s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    o = _mm("hql,lhk->qhk", pr, v.transpose(0, 1, 2), fp8, w_axes=0)
    return _mm("shk,hkd->sd", o, p["wo"], fp8)


def _experts(cfg, p, h, fp8: bool):
    e, k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    logits = _mm("sd,de->se", h, p["router"], fp8)
    probs = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], idx].set(top)          # (S, E)
    a = jax.nn.silu(_mm("sd,edf->esf", h, p["w_gate"], fp8, w_axes=(1, 2)))
    u = _mm("sd,edf->esf", h, p["w_up"], fp8, w_axes=(1, 2))
    y = _mm("esf,efd->esd", a * u, p["w_down"], fp8, w_axes=(1, 2))
    return jnp.einsum("esd,se->sd", y, gates,
                      precision=jax.lax.Precision.HIGHEST)


def _mlp(p, h, fp8: bool):
    a = jax.nn.silu(_mm("sd,df->sf", h, p["w_gate"], fp8))
    u = _mm("sd,df->sf", h, p["w_up"], fp8)
    return _mm("sf,fd->sd", a * u, p["w_down"], fp8)


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _forward(params, tokens, read_at, *, cfg_items, precision):
    cfg = dict(cfg_items)
    fp8 = precision == "fp8"
    eps = cfg["rms_norm_eps"]
    f32 = functools.partial(jax.tree.map, lambda a: a.astype(jnp.float32))
    pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = params["embed"][tokens].astype(jnp.float32)

    def layer(x, p):
        p = f32(p)
        x = x + _attention(cfg, p["attn"], _rms(x, p["norm1"], eps), pos,
                           fp8)
        h = _rms(x, p["norm2"], eps)
        x = x + (_experts(cfg, p["ffn"], h, fp8) if cfg["num_local_experts"]
                 else _mlp(p["ffn"], h, fp8))
        return x, None

    x, _ = jax.lax.scan(layer, x, params["groups"][0])
    x = _rms(x[read_at], params["out_norm"].astype(jnp.float32), eps)
    if cfg["tie_word_embeddings"]:
        return _mm("sd,vd->sv", x, params["embed"].astype(jnp.float32),
                   fp8)
    return _mm("sd,dv->sv", x, params["unembed"].astype(jnp.float32), fp8)


def logits(cfg: Dict[str, Any], params, tokens, read_at,
           precision: str = "float32") -> jax.Array:
    """Logits (n, vocab) float32 at positions ``read_at`` (n,) of the token
    sequence ``tokens`` (S,).  Positions after the last one read may hold
    padding: the mask is causal."""
    keys = ("head_dim", "num_attention_heads", "num_key_value_heads",
            "rope_theta", "rms_norm_eps", "num_local_experts",
            "num_experts_per_tok", "tie_word_embeddings")
    items = tuple((k, cfg.get(k) or 0) for k in keys)
    if precision not in ("float32", "fp8"):
        raise ValueError(f"unknown precision {precision!r}")
    return _forward(params, jnp.asarray(tokens, jnp.int32),
                    jnp.asarray(read_at, jnp.int32), cfg_items=items,
                    precision=precision)
