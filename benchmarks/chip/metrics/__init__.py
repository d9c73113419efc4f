"""The metrics of one run.

End-to-end metrics (``--trace 0``) are computed here from the host clock.
Each per-layer metric (``--trace 1``) is a reader of its own,
``metrics/<name>.py``, found by the metric's name in ``BENCHMARK.json``:
it defines ``read(run) -> float | None`` over the ``harness.Run``, and a
reader that finds nothing to read returns None, which leaves the metric
out of the line.  A later change adds a metric by adding its entry and
its reader.
"""
from __future__ import annotations

import importlib.util
import pathlib
from typing import Any, Dict, Optional

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent


def percentile(values, q: float) -> Optional[float]:
    """The q-th percentile (linear interpolation), None when empty."""
    if not len(values):
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def ttft_ms(run) -> list:
    """Due time to first visible token, per request due in the window."""
    return [(r.tokens[0] - r.due) * 1e3 for r in run.window_records()
            if r.tokens]


def gaps_ms(run) -> list:
    """Every gap between consecutive tokens of a request that ends in the
    window."""
    out = []
    for r in run.records.values():
        t = np.asarray(r.tokens)
        if len(t) > 1:
            g = np.diff(t)
            out += list(g[(t[1:] >= run.window[0])
                          & (t[1:] < run.window[1])] * 1e3)
    return out


END_TO_END = {
    "ttft_p95_ms": lambda run: percentile(ttft_ms(run), 95),
    "tbt_p99_ms": lambda run: percentile(gaps_ms(run), 99),
    "setup_s": lambda run: run.setup_s,
}


def _reader(name: str):
    path = HERE / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.chip.metrics._{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _applies(metric: Dict[str, Any], cell: str, bench) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    moves = metric.get("moves")
    if moves is None:
        return True
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    return _applies(e2e[moves], cell, bench)


def result(bench: Dict[str, Any], cell: Dict[str, Any], out: Dict[str, Any],
           trace: bool) -> Dict[str, Any]:
    run, check = out["run"], out["check"]
    from ..correctness import lost

    window = run.window_records()
    res: Dict[str, Any] = {"correct": check["correct"],
                           "attempted": len(window), "failed": lost(run),
                           "metrics": {}}
    group = bench["per_layer"] if trace else bench["end_to_end"]
    for m in group:
        if not _applies(m, cell["name"], bench):
            continue
        value = (_reader(m["name"])(run) if trace
                 else END_TO_END[m["name"]](run))
        if value is not None:
            res["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    if trace and run.trace is not None:
        res["device_trace"] = {"busy_s": run.trace.busy_s(),
                               "window_s": run.trace.window_s()}
        res["breakdown"] = run.trace.breakdown()
    return res
