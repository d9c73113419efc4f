"""Seconds JAX spent tracing, lowering and compiling, and the number of
programs compiled, from JAX's own monitoring events (copied from the
program's ``chip_smoke.py``).  A program loaded from the persistent
compilation cache is not compiled and does not count."""
from __future__ import annotations

import jax

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileTally:
    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, seconds: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.seconds += seconds
            self.programs += event == _COMPILE_EVENTS[-1]
