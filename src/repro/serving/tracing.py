"""Profiler spans at the boundaries of the serving layers.

A span is a ``jax.profiler.TraceAnnotation`` named ``serve.<name>``: it
lands on the host plane of a profiler trace, on the clock the device's
programs are recorded on, with its keyword stats attached, so Perfetto or
TensorBoard show what the host was doing while the device ran or sat
idle.  The profiler session is the only switch: with none recording,
``span`` returns one shared do-nothing context.

A span never waits for the device.  Its stats are ints and strings the
caller already holds, and a span around a sync names a wait the code
makes anyway.  ``docs/serving.md`` ("Tracing") lists the spans.
"""
from __future__ import annotations

import jax

_recording = jax.profiler.TraceAnnotation.is_enabled


class _Off:
    """The span when no profiler session records."""

    __slots__ = ()

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set_metadata(self, **stats) -> None:
        """Stats known only once the span is open (none recorded here)."""


OFF = _Off()


def span(name: str, **stats):
    """``serve.<name>`` with ``stats`` while a profiler session records,
    else ``OFF``.  Stats known later go in through ``set_metadata``."""
    if _recording():
        return jax.profiler.TraceAnnotation("serve." + name, **stats)
    return OFF
