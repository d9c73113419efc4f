"""What the benchmark knows of each architecture, one module per
``model_type``.

A configuration file's ``model_type`` (its published value) names the
module ``archs/<model_type>.py``, with ``-`` and ``.`` read as ``_``.  A
new architecture is one new file here; nothing lists the modules.  Each
module holds, for configurations of its ``model_type``:

- ``program_config(cfg)``: the program's ``ModelConfig``.  It first
  refuses, with a ``ValueError``, a configuration that the program cannot
  compute as stated.
- ``param_shapes(cfg)``: (shape, dtype) of every weight, in the
  program's parameter layout and leaf order, with as many layer groups as
  the program stacks.  ``model.make_weights`` fills the leaves in
  flattening order.
- ``is_gain(leaf_name)``: whether a leaf (named by its last key) is a
  norm gain, which starts at zero because the program's norms scale by
  (1 + w); every other leaf is N(0, ``initializer_range``).
- ``logits(cfg, params, tokens, read_at, precision)``: the plain
  reference (``reference.py``): logits (n, vocab) float32 at positions
  ``read_at`` of ``tokens``, at ``precision`` "float32" or "fp8" (the
  control), importing nothing of the program.
- ``token_flops(cfg)``, ``attention_flops(cfg, queries,
  keys_per_query)``, ``head_flops(cfg)`` and ``paged_attention_cost(cfg,
  queries, held)``: useful work, as ``flops.py`` defines it.
"""
from __future__ import annotations

import importlib
from typing import Any, Dict


def of(cfg: Dict[str, Any]):
    """The architecture module of the configuration ``cfg``."""
    kind = cfg.get("model_type")
    if not kind:
        raise ValueError(f"{cfg.get('name')}: the configuration states no "
                         f"model_type")
    mod = kind.replace("-", "_").replace(".", "_")
    try:
        return importlib.import_module(f"{__name__}.{mod}")
    except ModuleNotFoundError as e:
        if e.name != f"{__name__}.{mod}":
            raise
    raise ValueError(f"{cfg.get('name')}: no architecture module for "
                     f"model_type {kind!r}; add "
                     f"benchmarks/chip/archs/{mod}.py")
