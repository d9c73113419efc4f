"""Readings that set the correctness limit of a cell.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 11,12,13 --control-seeds 11,12,13 --seconds <s>

One process sets the cell up once; for each seed it makes that seed's
weights, serves a window of ``--seconds`` at the cell's own load, and
prints one JSON line with the numbers ``correctness.check`` can compare
(the widest and the mean gap between a served token's logit and the
float32 reference's best, and the share of served tokens that are not the
reference's first choice), whether the check passed, and for a control
seed the same check run on the control: the reference computed in float8
put in the program's place, reading at each compared position the token
float8 ranks first, which has to come out not correct.  The program's
readings over a dozen seeds give the lower end of each limit, the
control's the upper end (``PERF.md``).
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import ROOT, _log, find_cell, setup_jax  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    _, cell = find_cell(args.workload)
    if setup_jax(cell) is None:
        return 1
    from benchmarks.chip import correctness, harness

    cfg, mix, cellfile = harness.load(cell)
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    bench = harness.Bench(cfg, mix, seeds[0], log=_log)
    bench.warm([float(cellfile["rate_per_s"])], args.seconds)
    for seed in seeds + sorted(controls - set(seeds)):
        if seed != bench.seed:
            bench.reseed(seed)
        run = bench.serve(cellfile, args.seconds)
        t = time.perf_counter()
        prog = correctness.check(run, bench.params, seed, log=_log)
        line = {"seed": seed, "requests": prog["requests"],
                "program": prog["readings"], "correct": prog["correct"],
                "compared": prog["compared"],
                "reference_s": time.perf_counter() - t,
                "compiles_in_window": run.compiles_in_window}
        if seed in controls:
            ctl = correctness.check(run, bench.params, seed, log=_log,
                                    control=True)
            line["control"] = ctl["readings"]
            line["control_correct"] = ctl["correct"]
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(ROOT))
    sys.exit(main())
