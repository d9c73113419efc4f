"""A model configuration file, the program's config built from it, and the
random weights the benchmark makes from the seed.

A configuration file (``configs/<name>.json``) holds the published keys
under their published names, with the values as run; the keys changed
from the source are listed under ``reduced``.  ``program_config`` maps
them onto the program's ``ModelConfig``.

The weights are made by the benchmark, not by the program, in the
program's parameter layout (stacked layers under ``groups[0]``), on the
device, in the served dtype, in one jitted call from the seed.  Every
matrix is N(0, ``initializer_range``) and every norm gain 1, as the
published initializers give.

The program runs a plain pre-norm decoder: it has no embedding,
attention, residual or logits multipliers, so a configuration states
them at their neutral values (1, 1/sqrt(head_dim), 1, 1).  That is a
departure of the program, not a cut of scale; it is listed under
``reduced`` only because every key whose value differs from the source
must be.
"""
from __future__ import annotations

import functools
import json
import math
import pathlib
from typing import Any, Dict

import jax
import jax.numpy as jnp

HERE = pathlib.Path(__file__).resolve().parent

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def load(name: str) -> Dict[str, Any]:
    """The configuration file ``configs/<name>.json`` as a dict."""
    with open(HERE / "configs" / f"{name}.json") as f:
        cfg = json.load(f)
    if cfg["name"] != name:
        raise ValueError(f"configs/{name}.json names {cfg['name']!r}")
    return cfg


def dtype(cfg: Dict[str, Any]):
    return DTYPES[cfg["dtype"]]


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


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def make_weights(cfg: Dict[str, Any], seed: int):
    """Random weights from ``seed`` on the default device, one jitted call."""
    check_runnable(cfg)
    leaves, treedef = jax.tree.flatten_with_path(param_shapes(cfg),
                                                 is_leaf=_is_leaf)
    vals = _build(tuple((str(getattr(p[-1], "key", p[-1])), s, d)
                        for p, (s, d) in leaves),
                  float(cfg["initializer_range"]),
                  jax.random.key(seed, impl="rbg"))
    return jax.tree.unflatten(treedef, vals)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _build(leaves, std, key):
    out = []
    for i, (name, shape, dt) in enumerate(leaves):
        if name.startswith("norm") or name == "out_norm":
            # the program's norms scale by (1 + w): gain 1
            out.append(jnp.zeros(shape, dt))
        else:
            k = jax.random.fold_in(key, i)
            out.append((jax.random.normal(k, shape, dt)
                        * jnp.asarray(std, dt)).astype(dt))
    return out


def n_params(cfg: Dict[str, Any]) -> int:
    leaves = jax.tree.leaves(param_shapes(cfg), is_leaf=_is_leaf)
    return sum(math.prod(s) for s, _ in leaves)
