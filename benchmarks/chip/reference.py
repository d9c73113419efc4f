"""The plain reference forward pass, and its lower-precision control.

A straightforward ``jax.numpy`` forward of the model the configuration
file describes, kept in the architecture module its ``model_type`` names
(``archs/``), over the float helpers below.  It imports nothing of the
program and keeps no cache: it recomputes the whole sequence.  It reads
the weights the benchmark made from the seed, in the program's layout
(see ``model.py``).

``precision="float32"``: every operand widened to float32, matmuls at
``highest`` precision.  ``precision="fp8"``: the control — every matmul
operand rounded to float8 e4m3 with a scale per tensor (weights) or per
row (activations), accumulated in float32; the nearest precision below
the configuration's bfloat16.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from . import archs

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


def logits(cfg: Dict[str, Any], params, tokens, read_at,
           precision: str = "float32") -> jax.Array:
    """Logits (n, vocab) float32 at positions ``read_at`` (n,) of the token
    sequence ``tokens`` (S,).  Positions after the last one read may hold
    padding: the mask is causal."""
    if precision not in ("float32", "fp8"):
        raise ValueError(f"unknown precision {precision!r}")
    return archs.of(cfg).logits(cfg, params, jnp.asarray(tokens, jnp.int32),
                                jnp.asarray(read_at, jnp.int32), precision)
