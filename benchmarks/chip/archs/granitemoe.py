"""IBM Granite's decoder (``model_type`` granitemoe; dense ``granite``
re-exports this module): grouped-query attention with rotary positions,
then either a SwiGLU MLP or a top-k softmax-routed mixture of SwiGLU
experts, pre-norm, in one stack of layers.

The program runs a plain pre-norm decoder: it has no embedding,
attention, residual or logits multipliers, so a configuration states
them at their neutral values (1, 1/sqrt(head_dim), 1, 1).  That is a
departure of the program, not a cut of scale; it is listed under
``reduced`` only because every key whose value differs from the source
must be.

The reference (``logits``): token embedding, then per layer RMSNorm,
grouped-query attention with rotary positions and a causal mask, a
residual add, RMSNorm, and either a SwiGLU MLP or a top-k softmax-routed
mixture of SwiGLU experts (gates renormalised over the k chosen), a
residual add; then a final RMSNorm and the output head.  Like the program
it has no Granite multipliers.  The layers run in a ``lax.scan`` that
widens one layer's weights at a time, so the float32 pass fits beside the
served bfloat16 weights.  The mixture of experts is computed densely over
all experts and masked by the gates, which costs E/k times the active
FLOPs and changes nothing in the result.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..flops import DTYPE_BYTES
from ..model import dtype
from ..reference import _mm, _rms, _rope


# --- the program's config and layout ---------------------------------

def n_experts(cfg: Dict[str, Any]) -> int:
    return int(cfg.get("num_local_experts") or 0)


def program_config(cfg: Dict[str, Any]):
    """The program's ``ModelConfig`` for this configuration file."""
    from repro.models.config import Activation, Family, ModelConfig

    check_runnable(cfg)
    moe = n_experts(cfg) > 0
    return ModelConfig(
        name=cfg["name"], family=Family.MOE if moe else Family.DENSE,
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        activation=Activation.SWIGLU, rope_theta=float(cfg["rope_theta"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        n_experts=n_experts(cfg), top_k=int(cfg.get("num_experts_per_tok")
                                            or 0),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        source=cfg["source"])


NEUTRAL = {"embedding_multiplier": lambda c: 1.0,
           "attention_multiplier": lambda c: 1.0 / math.sqrt(c["head_dim"]),
           "residual_multiplier": lambda c: 1.0,
           "logits_scaling": lambda c: 1.0}


def check_runnable(cfg: Dict[str, Any]) -> None:
    """Refuse a configuration the program cannot compute as stated."""
    for key, neutral in NEUTRAL.items():
        if key in cfg and not math.isclose(cfg[key], neutral(cfg)):
            raise ValueError(f"{cfg['name']}: the program has no {key}; "
                             f"state {neutral(cfg)} and list the published "
                             f"value under reduced")
    if cfg["hidden_act"] != "silu" or cfg.get("attention_bias"):
        raise ValueError(f"{cfg['name']}: the program runs SwiGLU without "
                         f"biases")


def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """(shape, dtype) of every leaf, in the program's layout."""
    dt = dtype(cfg)
    n, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    f, v, e = cfg["intermediate_size"], cfg["vocab_size"], n_experts(cfg)
    if e:
        ffn = {"router": ((n, d, e), jnp.float32),
               "w_gate": ((n, e, d, f), dt), "w_up": ((n, e, d, f), dt),
               "w_down": ((n, e, f, d), dt)}
    else:
        ffn = {"w_gate": ((n, d, f), dt), "w_up": ((n, d, f), dt),
               "w_down": ((n, f, d), dt)}
    layer = {"norm1": ((n, d), dt),
             "attn": {"wq": ((n, d, h, hd), dt), "wk": ((n, d, kv, hd), dt),
                      "wv": ((n, d, kv, hd), dt), "wo": ((n, h, hd, d), dt)},
             "norm2": ((n, d), dt), "ffn": ffn}
    tree = {"embed": ((v, d), dt), "out_norm": ((d,), dt),
            "groups": (layer,), "rem": ()}
    if not cfg["tie_word_embeddings"]:
        tree["unembed"] = ((d, v), dt)
    return tree


def is_gain(leaf_name: str) -> bool:
    return leaf_name.startswith("norm") or leaf_name == "out_norm"


# --- the plain reference ---------------------------------------------

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
           precision: str) -> jax.Array:
    keys = ("head_dim", "num_attention_heads", "num_key_value_heads",
            "rope_theta", "rms_norm_eps", "num_local_experts",
            "num_experts_per_tok", "tie_word_embeddings")
    items = tuple((k, cfg.get(k) or 0) for k in keys)
    return _forward(params, tokens, read_at, cfg_items=items,
                    precision=precision)


# --- useful work -----------------------------------------------------

def _dims(cfg: Dict[str, Any]):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], int(cfg.get("num_local_experts") or 0),
            int(cfg.get("num_experts_per_tok") or 0),
            cfg["num_hidden_layers"], cfg["vocab_size"])


def token_flops(cfg: Dict[str, Any]) -> float:
    """Operations one token needs in every layer outside attention's
    context term: q/k/v/o projections and the feed-forward (router plus
    the top-k experts, or the dense MLP), summed over the layers."""
    d, h, kv, hd, f, e, k, n, _ = _dims(cfg)
    proj = 2 * d * (h + 2 * kv) * hd + 2 * h * hd * d
    ffn = (2 * d * e + k * 6 * d * f) if e else 6 * d * f
    return float(n * (proj + ffn))


def attention_flops(cfg: Dict[str, Any], queries: float,
                    keys_per_query: float) -> float:
    """Scores and weighted values of ``queries`` queries each reading
    ``keys_per_query`` keys, over every layer."""
    _, h, _, hd, _, _, _, n, _ = _dims(cfg)
    return float(n * 4 * h * hd * queries * keys_per_query)


def head_flops(cfg: Dict[str, Any]) -> float:
    d, *_, v = _dims(cfg)
    return float(2 * d * v)


def _kv_bytes_per_token(cfg: Dict[str, Any]) -> float:
    _, _, kv, hd, *_ = _dims(cfg)
    # k and v at the cache dtype, plus one int32 position per token
    return 2 * kv * hd * DTYPE_BYTES[cfg["dtype"]] + 4


def paged_attention_cost(cfg: Dict[str, Any], queries: int,
                         held: int) -> Dict[str, float]:
    """The page-fused attention kernel's work for one row in one call of
    every layer: ``queries`` queries against ``held`` tokens held in
    pages.  Bytes: the held tokens' keys, values and positions read once,
    the queries read at the cache dtype and one float32 (o, l, m) result
    per query and head written."""
    _, h, _, hd, _, _, _, n, _ = _dims(cfg)
    q_bytes = queries * h * hd * DTYPE_BYTES[cfg["dtype"]]
    out_bytes = queries * h * (hd + 2) * 4
    return {"flops": attention_flops(cfg, queries, held),
            "bytes": float(n * (held * _kv_bytes_per_token(cfg)
                                + q_bytes + out_bytes))}
