"""A model configuration file, the program's config built from it, and the
random weights the benchmark makes from the seed.

A configuration file (``configs/<name>.json``) holds the published keys
under their published names, with the values as run; the keys changed
from the source are listed under ``reduced``.  Its ``model_type`` names
the architecture module (``archs/``) that maps it onto the program's
``ModelConfig`` and lays out its weights.

The weights are made by the benchmark, not by the program, in the
program's parameter layout, on the device, in the served dtype, in one
jitted call from the seed.  Every matrix is N(0, ``initializer_range``)
and every norm gain 1, as the published initializers give.
"""
from __future__ import annotations

import functools
import json
import math
import pathlib
from typing import Any, Dict

import jax
import jax.numpy as jnp

from . import archs

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


def program_config(cfg: Dict[str, Any]):
    """The program's ``ModelConfig`` for this configuration file; refuses
    a configuration the program cannot compute as stated."""
    return archs.of(cfg).program_config(cfg)


def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """(shape, dtype) of every leaf, in the program's layout."""
    return archs.of(cfg).param_shapes(cfg)


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def make_weights(cfg: Dict[str, Any], seed: int):
    """Random weights from ``seed`` on the default device, one jitted call."""
    arch = archs.of(cfg)
    arch.program_config(cfg)
    leaves, treedef = jax.tree.flatten_with_path(arch.param_shapes(cfg),
                                                 is_leaf=_is_leaf)
    vals = _build(tuple((arch.is_gain(str(getattr(p[-1], "key", p[-1]))),
                         s, d) for p, (s, d) in leaves),
                  float(cfg["initializer_range"]),
                  jax.random.key(seed, impl="rbg"))
    return jax.tree.unflatten(treedef, vals)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _build(leaves, std, key):
    out = []
    for i, (gain, shape, dt) in enumerate(leaves):
        if gain:
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
