"""Seconds JAX spent tracing, lowering and compiling, and the programs it
built, from JAX's own monitoring events (copied from the program's
``chip_smoke.py``).  A program counts once per process, when its first
call builds it: compiled, or loaded from the persistent compilation cache
(``names`` lists every program in order, with whether it was loaded)."""
from __future__ import annotations

from typing import List, Tuple

import jax

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileTally:
    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        self.names: List[Tuple[str, bool]] = []
        self._hit = False
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_hit)

    def _on_hit(self, event: str, **_) -> None:
        if event == _CACHE_HIT:
            self._hit = True

    def _on_event(self, event: str, seconds: float, **kw) -> None:
        if event in _COMPILE_EVENTS:
            self.seconds += seconds
        if event == _COMPILE_EVENTS[-1]:
            self.programs += 1
            self.names.append((str(kw.get("fun_name", "?")), self._hit))
            self._hit = False
