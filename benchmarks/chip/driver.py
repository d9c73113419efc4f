"""The client side: drives ``Server.submit`` / ``Server.step`` in wall time
and records what a client sees, plus the host spans the per-layer
metrics read.

The orchestrator orders its events on its own virtual clock, but engine
work runs inside ``Server.step`` and blocks on the device there, so a
token becomes visible to the client when the ``step`` that committed it
returns.  The driver stamps each token with the host clock at that
moment.  Requests are submitted at their due time; latencies run from
the due time, so a late generator shows as waiting.

Spans: the driver wraps, on the engine instances it built,
``PrefillEngine.prefill_waves`` (one span per wave; the first wave of a
batch is when its requests leave the router's queue) and
``DecodeEngine.step`` (one span per decode iteration), and opens a span around each
``Server.step`` and ``Server.submit``.  With tracing on, each span is
also a ``jax.profiler.TraceAnnotation`` named ``bench.<kind>``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional

now = time.perf_counter


@dataclasses.dataclass
class Record:
    """One request as its client saw it."""
    rid: int
    due: float                       # host clock
    segment: str                     # ramp | window | tail
    req: object                      # the program's Request
    prompt_len: int
    tokens: List[float] = dataclasses.field(default_factory=list)
    first_wave: Optional[float] = None
    batch: Optional[int] = None
    seen: int = 0

    @property
    def done(self) -> bool:
        return self.req.outcome is not None


@dataclasses.dataclass
class Span:
    kind: str
    start: float
    end: float = 0.0
    batch: Optional[int] = None      # prefill waves: their batch


class Spans:
    """Host spans, and the profiler annotations that mirror them."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.log: List[Span] = []
        self.recording = True

    @contextlib.contextmanager
    def span(self, kind: str, **kw):
        sp = Span(kind, now(), **kw)
        if self.annotate:
            import jax
            with jax.profiler.TraceAnnotation(f"bench.{kind}"):
                yield sp
        else:
            yield sp
        sp.end = now()
        if self.recording:
            self.log.append(sp)


class Driver:
    def __init__(self, server, spans: Spans):
        self.server = server
        self.spans = spans
        self.records: Dict[int, Record] = {}
        self.live: Dict[int, Record] = {}
        self._batch = 0
        backend = server.backend
        for m in backend.members:
            if m.prefill is not None:
                self._wrap_prefill(m.prefill)
            if m.decode is not None:
                self._wrap_decode(m.decode)

    # -- engine wraps ------------------------------------------------------
    def _wrap_prefill(self, engine) -> None:
        inner = engine.prefill_waves
        drv = self

        def prefill_waves(reqs, *a, **kw):
            drv._batch += 1
            batch = drv._batch
            gen = inner(reqs, *a, **kw)
            first = True
            while True:
                with drv.spans.span("prefill_wave", batch=batch) as sp:
                    try:
                        wave = next(gen)
                    except StopIteration:
                        return
                    if first:
                        for r in reqs:
                            rec = drv.records.get(r.rid)
                            if rec is not None:
                                rec.first_wave = sp.start
                                rec.batch = batch
                        first = False
                yield wave

        engine.prefill_waves = prefill_waves

    def _wrap_decode(self, engine) -> None:
        inner = engine.step
        drv = self

        def step():
            with drv.spans.span("decode_step"):
                return inner()

        engine.step = step

    # -- client side -------------------------------------------------------
    def submit(self, rec: Record) -> None:
        with self.spans.span("submit"):
            self.server.submit(rec.req)
        self.records[rec.rid] = rec
        self.live[rec.rid] = rec

    def step(self) -> List[Record]:
        """One ``Server.step``; stamps every token it made visible and
        returns the requests that finished."""
        with self.spans.span("step"):
            self.server.step()
        t = now()
        finished = []
        for rid, rec in list(self.live.items()):
            n = len(rec.req.generated)
            if n > rec.seen:
                rec.tokens += [t] * (n - rec.seen)
                rec.seen = n
            if rec.done:
                finished.append(rec)
                del self.live[rid]
        return finished

    def busy(self) -> bool:
        return bool(self.live)


def open_loop(drv: Driver, schedule: List[Record], window: tuple,
              max_wait: float, on_open=None, on_close=None) -> None:
    """Submit each record at its due time and step while work is in
    flight, until the window has closed and every request due in it has
    its first token (or ``max_wait`` seconds after the close)."""
    i, opened, closed = 0, False, False
    w_open, w_close = window
    pending_first = {r.rid for r in schedule if r.segment == "window"}
    while True:
        t = now()
        if not opened and t >= w_open:
            opened = True
            on_open and on_open()
        if not closed and t >= w_close:
            closed = True
            on_close and on_close()
        if closed and (not pending_first or t >= w_close + max_wait):
            return
        while i < len(schedule) and schedule[i].due <= t:
            drv.submit(schedule[i])
            i += 1
        if drv.busy():
            drv.step()
            pending_first = {rid for rid in pending_first
                             if not drv.records.get(rid)
                             or not (drv.records[rid].tokens
                                     or drv.records[rid].done)}
        else:
            nxt = schedule[i].due if i < len(schedule) else w_close
            if opened and not closed:
                nxt = min(nxt, w_close)
            elif not opened:
                nxt = min(nxt, w_open)
            time.sleep(max(0.0, min(nxt - t, 0.05)))
