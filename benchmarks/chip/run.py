"""Chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs from the root of a checkout on a machine that holds the chips the
cell asks for, and exits non-zero without printing a result when JAX
finds no accelerator or fewer chips.  Serves the cell's traffic through
the program's ``Server`` over its ``Orchestrator`` (1 prefill + 1 decode
member on device 0), measures ``--seconds`` of it, checks the served
tokens against the plain reference, and prints, as the last line of
standard output, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics with ``--trace 1``), ``device``, with ``--trace 1``
``breakdown``, and last ``compared``: each number the check compared,
beside its limit.  The line before it counts the programs compiled
inside the window.

JAX's persistent compilation cache is where the program's
``enable_compile_cache`` puts it: ``JAX_COMPILATION_CACHE_DIR`` when set,
else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def find_cell(name: str) -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return bench, cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def setup_jax(cell: dict):
    """Turn JAX's persistent compilation cache on where the program keeps
    it (``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``)
    for every program, however small, and return the devices, or None
    (after saying why) when they are not the accelerator chips the cell
    asks for."""
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    if devices[0].platform == "cpu" or len(devices) < cell["chips"]:
        _log(f"needs {cell['chips']} accelerator chip(s); JAX found "
             f"{len(devices)} {devices[0].platform} device(s)")
        return None
    return devices


def main(argv=None) -> int:
    args = parse(argv)
    bench, cell = find_cell(args.workload)
    devices = setup_jax(cell)
    if devices is None:
        return 1
    from benchmarks.chip import harness, metrics, peaks

    dev = devices[0]
    pk = peaks.peaks(dev.device_kind)
    _log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
         f"cell {cell['name']} seed {args.seed} {args.seconds}s "
         f"trace {args.trace}")
    cfg, mix, cellfile = harness.load(cell)
    out = harness.run_cell(args.seed, args.seconds, bool(args.trace),
                           cfg=cfg, mix=mix, cellfile=cellfile,
                           t_process=T_PROCESS, peaks=pk, log=_log)
    result = metrics.result(bench, cell, out, bool(args.trace))
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(devices),
                        "memory_peak_bytes": out["run"].memory_peak_bytes,
                        **result.pop("device_trace", {})}
    compared = out["check"]["compared"]
    for name, c in compared.items():
        _log(f"compared {name}: {c['value']} limit {c['limit']}")
    result["compared"] = compared
    print(f"compiles_in_window: {out['run'].compiles_in_window}", flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
