"""Where the entry points keep JAX's persistent compilation cache.

Called by ``chip_smoke.py``, ``launch/serve.py`` and ``benchmarks/run.py``
before their first compile — never on library import, so tests and library
users keep JAX's own defaults.
"""
from __future__ import annotations

import os
import pathlib

# the checkout root (src/repro/launch/ -> three levels up)
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, places it: JAX reads the
    variable itself, so no other path is set here.  Otherwise the cache
    lives at ``<checkout>/.jax_cache`` — a fixed path, because the path is
    part of what a later run must find again."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
