"""Knee sweep: the highest fixed rate an open-loop cell sustains.

    python3 benchmarks/chip/knee.py --workload <cell> --seed <n> \
        --seconds <s> --rates 2,3,4,6,8 [--ttft-ms 1000 --tpot-ms 100]

One process sets the cell up once, then serves a measured window at each
offered rate on a fresh server, and prints one JSON line per rate: the
requests due in the window, the share that met both limits (first token
within ``--ttft-ms`` of its due time, mean gap between its tokens within
``--tpot-ms``; a request with no first token misses), TTFT and gap
percentiles, and the backlog: requests due in the window still waiting
for their first token when it closed, and the median TTFT of the
window's last quarter over its first quarter (above 2: a growing queue).
The knee is the highest rate with at least 90% attainment and no growing
backlog; the cell's rate is set at 0.8 of it.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

from run import ROOT, _log, find_cell, setup_jax  # noqa: E402


def summarize(run, rate: float, ttft_ms: float, tpot_ms: float) -> dict:
    recs = sorted(run.window_records(), key=lambda r: r.due)
    close = run.window[1]
    ttft = [(r.tokens[0] - r.due) * 1e3 if r.tokens else float("inf")
            for r in recs]
    tpot = [float(np.mean(np.diff(r.tokens))) * 1e3 if len(r.tokens) > 1
            else 0.0 for r in recs]
    met = [a <= ttft_ms and b <= tpot_ms for a, b in zip(ttft, tpot)]
    q = max(len(recs) // 4, 1)
    fin = [t for t in ttft if np.isfinite(t)]
    gaps = [g * 1e3 for r in recs for g in np.diff(r.tokens)]
    return {
        "rate_per_s": rate, "due": len(recs),
        "attainment": float(np.mean(met)) if met else None,
        "ttft_p50_ms": float(np.percentile(fin, 50)) if fin else None,
        "ttft_p95_ms": float(np.percentile(ttft, 95)) if ttft else None,
        "tbt_p99_ms": float(np.percentile(gaps, 99)) if gaps else None,
        "waiting_at_close": sum(1 for r in recs if r.due < close and
                                (not r.tokens or r.tokens[0] > close)),
        "ttft_trend": (float(np.median(ttft[-q:]) / np.median(ttft[:q]))
                       if len(recs) >= 8 else None),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--ttft-ms", type=float, default=1000.0)
    ap.add_argument("--tpot-ms", type=float, default=100.0)
    args = ap.parse_args(argv)
    bench_json, cell = find_cell(args.workload)
    devices = setup_jax(cell)
    if devices is None:
        return 1
    from benchmarks.chip import harness

    cfg, mix, cellfile = harness.load(cell)
    rates = [float(r) for r in args.rates.split(",")]
    bench = harness.Bench(cfg, mix, args.seed, log=_log)
    bench.warm(rates, args.seconds)
    _log(f"set up in {time.perf_counter() - T_PROCESS:.1f}s")
    for rate in rates:
        run = bench.serve(cellfile, args.seconds, rate=rate)
        line = summarize(run, rate, args.ttft_ms, args.tpot_ms)
        line["compiles_in_window"] = run.compiles_in_window
        print(json.dumps(line), flush=True)
        if line["attainment"] is not None and line["attainment"] < 0.5:
            break
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(ROOT))
    sys.exit(main())
