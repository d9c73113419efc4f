"""The reduction from the profiler's trace of the window to what the
per-layer metrics read: device busy time, kernel time, and device time
attributed to the host spans the driver placed.

The trace holds, on one clock, the device's operations (the ``XLA Ops``
line of each ``/device:TPU:<n>`` plane) and the host's
``jax.profiler.TraceAnnotation`` spans (``bench.<kind>`` events on the
host plane).  Prefill and decode programs carry the same jit name, so
device work is attributed by host span: each execution of a compiled
program (an ``XLA Modules`` event) belongs to the innermost driver span
open when it started on the device, or, when none is, to the last one
that had opened before; every operation inside it belongs with it.  A
program starts on the device as soon as it is dispatched when the device
is idle, and the driver's decode step waits for the device at its end,
so the span open at a program's start is, but for page movers queued
behind a prefill forward, the span that dispatched it.

Busy time is the union of the operation intervals; idle gaps are the
stretches of the window in which no operation ran, each piece of them
attributed to the innermost span open over it.
"""
from __future__ import annotations

import bisect
import glob
import os
import shutil
import tempfile
from typing import Dict, List, Optional, Tuple

import numpy as np

SPAN_PREFIX = "bench."
LEAF_KINDS = ("prefill_wave", "decode_step", "submit")


def union_length(starts: np.ndarray, ends: np.ndarray) -> float:
    """Total length covered by the intervals [starts, ends)."""
    if not len(starts):
        return 0.0
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    run_end = np.maximum.accumulate(e)
    # a new covered stretch starts where an interval begins after every
    # earlier one has ended
    new = np.empty(len(s), bool)
    new[0] = True
    new[1:] = s[1:] > run_end[:-1]
    idx = np.flatnonzero(new)
    seg_end = np.append(run_end[idx[1:] - 1], run_end[-1])
    return float(np.sum(seg_end - s[idx]))


def idle_gaps(starts: np.ndarray, ends: np.ndarray, lo: float,
              hi: float) -> List[Tuple[float, float]]:
    """Stretches of [lo, hi) in which no interval is open."""
    out = []
    if not len(starts):
        return [(lo, hi)]
    order = np.argsort(starts, kind="stable")
    cur = lo
    for s, e in zip(starts[order], ends[order]):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


class Trace:
    """A reduced trace: times in seconds on the trace's clock."""

    def __init__(self, ops: Dict[str, np.ndarray], modules: Dict[str,
                 np.ndarray], spans: List[Tuple[str, float, float]],
                 n_devices: int):
        self.ops = ops              # start, end, name, kernel, device
        self.modules = modules      # start, end
        self.spans = sorted(spans, key=lambda s: s[1])
        self.n_devices = max(n_devices, 1)
        win = [s for s in self.spans if s[0] == "window"]
        self.lo, self.hi = ((win[0][1], win[0][2]) if win else
                            (float(ops["start"].min()) if len(ops["start"])
                             else 0.0,
                             float(ops["end"].max()) if len(ops["end"])
                             else 0.0))
        self._attribute()

    # -- window ------------------------------------------------------------
    def window_s(self) -> float:
        return self.hi - self.lo

    def _clip(self, s, e):
        return np.clip(s, self.lo, self.hi), np.clip(e, self.lo, self.hi)

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        s, e = self._clip(self.ops["start"], self.ops["end"])
        total = 0.0
        for dev in np.unique(self.ops["device"]):
            m = self.ops["device"] == dev
            total += union_length(s[m], e[m])
        return total / self.n_devices

    # -- attribution -------------------------------------------------------
    def _leaf_spans(self):
        return [s for s in self.spans if s[0] in LEAF_KINDS]

    def span_at(self, t: float, leaf_only: bool = True) -> Optional[tuple]:
        """The innermost span open at ``t`` (leaf kinds first), else the
        last leaf span opened before ``t``."""
        leaves = self._leaf_spans()
        starts = [s[1] for s in leaves]
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0:
            for j in range(i, max(i - 8, -1), -1):
                if leaves[j][1] <= t < leaves[j][2]:
                    return leaves[j]
        if not leaf_only:
            opened = [s for s in self.spans
                      if s[1] <= t < s[2] and s[0] != "window"]
            if opened:
                return max(opened, key=lambda s: s[1])
        return leaves[i] if i >= 0 else None

    def _attribute(self) -> None:
        """Index of the owning span (into self.spans) for every op."""
        index = {id(s): k for k, s in enumerate(self.spans)}
        mod_owner = []
        for s in self.modules["start"]:
            sp = self.span_at(float(s))
            mod_owner.append(index[id(sp)] if sp is not None else -1)
        mod_owner = np.asarray(mod_owner, np.int64)
        # each op belongs with the module execution that contains it
        owner = np.full(len(self.ops["start"]), -1, np.int64)
        if len(self.modules["start"]):
            order = np.argsort(self.modules["start"])
            ms = self.modules["start"][order]
            k = np.searchsorted(ms, self.ops["start"], side="right") - 1
            ok = k >= 0
            owner[ok] = mod_owner[order][k[ok]]
        self.owner = owner

    def span_ops(self, spans: List[tuple], kernel: bool = False) -> float:
        """Device seconds of the ops owned by ``spans`` (only the
        page-fused kernel's with ``kernel``), as the union per device."""
        index = {id(s): k for k, s in enumerate(self.spans)}
        ids = np.asarray([index[id(s)] for s in spans], np.int64)
        m = np.isin(self.owner, ids)
        if kernel:
            m &= self.ops["kernel"]
        total = 0.0
        for dev in np.unique(self.ops["device"][m]):
            d = m & (self.ops["device"] == dev)
            total += union_length(self.ops["start"][d], self.ops["end"][d])
        return total

    # -- breakdown ---------------------------------------------------------
    def _containers(self) -> np.ndarray:
        """Ops whose interval holds the next op on the same device (a
        ``while`` around its body): left out of the per-op sums, which
        would count their body twice."""
        out = np.zeros(len(self.ops["start"]), bool)
        for dev in np.unique(self.ops["device"]):
            idx = np.flatnonzero(self.ops["device"] == dev)
            idx = idx[np.argsort(self.ops["start"][idx], kind="stable")]
            st, en = self.ops["start"][idx], self.ops["end"][idx]
            out[idx[:-1]] = st[1:] < en[:-1]
        return out

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        s, e = self._clip(self.ops["start"], self.ops["end"])
        by_name: Dict[str, float] = {}
        leaf = ~self._containers()
        for n, a, b in zip(self.ops["name"][leaf], s[leaf], e[leaf]):
            by_name[n] = by_name.get(n, 0.0) + float(b - a)
        ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        d0 = self.ops["device"] == np.min(self.ops["device"]) \
            if len(self.ops["device"]) else np.zeros(0, bool)
        by_span: Dict[str, float] = {}
        cuts = sorted({t for sp in self.spans if sp[0] != "window"
                       for t in sp[1:]})
        for a, b in idle_gaps(s[d0], e[d0], self.lo, self.hi):
            # split the gap where spans open or close, and name each piece
            # by the innermost span open over it
            inner = cuts[bisect.bisect_right(cuts, a):
                         bisect.bisect_left(cuts, b)]
            for lo, hi in zip([a] + inner, inner + [b]):
                sp = self.span_at(lo, leaf_only=False)
                name = (sp[0] if sp is not None and sp[1] <= lo < sp[2]
                        else "no span open")
                by_span[name] = by_span.get(name, 0.0) + (hi - lo)
        gaps = sorted(by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


class Tracer:
    """Profiler over the window; ``stop`` returns the reduced Trace.  The
    raw trace goes to a temporary directory and is deleted once read."""

    def __init__(self):
        self.dir = None
        self._window = None

    def start(self) -> None:
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def open_window(self) -> None:
        """Mark the window's start on the trace's clock."""
        import jax
        self._window = jax.profiler.TraceAnnotation(SPAN_PREFIX + "window")
        self._window.__enter__()

    def close_window(self) -> None:
        self._window.__exit__(None, None, None)

    def stop(self) -> Trace:
        """Stop the profiler (after the driver has stopped serving, so
        writing the trace delays no request) and reduce the trace."""
        import jax
        jax.profiler.stop_trace()
        try:
            return read(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def is_page_fused(op: str) -> bool:
    """The page-fused attention kernel: a Pallas TPU custom call whose
    first operand is the scalar-prefetched block table (int32).  The
    trace names an operation by its HLO text, and the wrapper that
    launched it names the instruction, so the kernel is known by its
    signature rather than its name."""
    head, _, args = op.partition("custom-call(")
    return 'custom_call_target="tpu_custom_call"' in args \
        and args.startswith("s32[")


def op_name(op: str) -> str:
    """An operation's short name for the breakdown: its HLO instruction
    name without the numeric suffix (``%fusion.123 = ...`` -> fusion)."""
    name = op.split(" = ", 1)[0].lstrip("%")
    base = name.rstrip("0123456789")
    return base.rstrip(".") or name


def read(trace_dir: str) -> Trace:
    """Reduce the ``.xplane.pb`` under ``trace_dir``.

    Device timestamps are moved onto the host's clock: a program's
    completion callback on the host (``CompleteCallbacks``, carrying the
    program's ``run_id``) comes after the program ends on the device, and
    the smallest such lag over the trace is taken as the clock offset."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise RuntimeError(f"no trace under {trace_dir}")
    pd = ProfileData.from_file(sorted(paths)[-1])
    ops = {"start": [], "end": [], "name": [], "kernel": [], "device": []}
    mods = {"start": [], "end": [], "run": []}
    done_at: Dict[int, float] = {}
    spans = []
    n_dev = 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = int(plane.name.rsplit(":", 1)[1])
            n_dev += 1
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        text = ev.name
                        ops["start"].append(ev.start_ns * 1e-9)
                        ops["end"].append((ev.start_ns + ev.duration_ns)
                                          * 1e-9)
                        ops["name"].append(op_name(text))
                        ops["kernel"].append(is_page_fused(text))
                        ops["device"].append(dev)
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        mods["start"].append(ev.start_ns * 1e-9)
                        mods["end"].append((ev.start_ns + ev.duration_ns)
                                           * 1e-9)
                        mods["run"].append(dict(ev.stats).get("run_id", -1))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name[len(SPAN_PREFIX):],
                                      ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9))
                    elif ev.name == "CompleteCallbacks":
                        run = dict(ev.stats).get("run_id")
                        if run is not None:
                            done_at[run] = ev.start_ns * 1e-9
    lags = [done_at[r] - e for r, e in zip(mods["run"], mods["end"])
            if r in done_at]
    offset = min(lags) if lags else 0.0
    ops_arr = {"start": np.asarray(ops["start"], np.float64) + offset,
               "end": np.asarray(ops["end"], np.float64) + offset,
               "name": np.asarray(ops["name"], object),
               "kernel": np.asarray(ops["kernel"], bool),
               "device": np.asarray(ops["device"], np.int64)}
    mods_arr = {"start": np.asarray(mods["start"], np.float64) + offset,
                "end": np.asarray(mods["end"], np.float64) + offset}
    tr = Trace(ops_arr, mods_arr, spans, n_dev)
    tr.clock_offset = offset
    return tr
