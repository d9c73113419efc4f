"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only <name>] [--out DIR]
                                            [--smoke]

Each module prints ``<figure>,<name>,...`` CSV rows; a module whose
``main()`` returns a dict additionally gets it written as machine-readable
``BENCH_<name>.json`` under ``--out`` (throughput, TTFT/TPOT p50/p99, SLO
attainment per scenario — the artifact CI's bench-smoke job checks).
``--smoke`` (or env ``BENCH_SMOKE=1``) shrinks workloads for fast CI runs.
The roofline/dry-run tables live in experiments/dryrun (produced by
repro.launch.dryrun) and are summarized by benchmarks/roofline_report.py.
"""
import argparse
import json
import os
import pathlib
import sys
import time

from repro.launch.compile_cache import enable_compile_cache

from . import (bench_attention, bench_autoscale, bench_chunked_prefill,
               bench_decode_attention, bench_layer_span, bench_migration,
               bench_orchestrator, bench_paged_handoff, bench_pipeline,
               bench_prefix_reuse, bench_quant_kv, bench_scheduler,
               bench_speculation, bench_throughput, bench_utilization)

ALL = {
    "pipeline": bench_pipeline,       # Fig. 6 / Eq. 12-17
    "migration": bench_migration,     # Eq. 4 / Eq. 11
    "scheduler": bench_scheduler,     # FIFO vs WFQ flood-vs-interactive A/B
    "autoscale": bench_autoscale,     # elastic vs static diurnal A/B
    "orchestrator": bench_orchestrator,  # Fig. 2a live, time-domain + SLOs
    "paged_handoff": bench_paged_handoff,  # block moves vs row surgery
    "prefix_reuse": bench_prefix_reuse,  # shared vs copy vs recompute
    "layer_span": bench_layer_span,   # span move vs whole-instance re-roll
    "utilization": bench_utilization, # Fig. 2b
    "attention": bench_attention,     # kernels (flash prefill / split-KV)
    "decode_attention": bench_decode_attention,  # page-fused vs two-step
    "chunked_prefill": bench_chunked_prefill,    # paged vs dense resumes
    "quant_kv": bench_quant_kv,       # int8 KV pages
    "speculation": bench_speculation,  # lookahead/draft verify A/B
    "throughput": bench_throughput,   # Fig. 8-11
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=sorted(ALL))
    ap.add_argument("--out", default=".",
                    help="directory for BENCH_<name>.json artifacts")
    ap.add_argument("--smoke", action="store_true",
                    help="shrink workloads (sets BENCH_SMOKE=1)")
    args = ap.parse_args()
    if args.smoke:
        os.environ["BENCH_SMOKE"] = "1"
    enable_compile_cache()
    out_dir = pathlib.Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    names = [args.only] if args.only else list(ALL)
    for name in names:
        t0 = time.time()
        print(f"# === {name} ===")
        res = ALL[name].main()
        if isinstance(res, dict):
            path = out_dir / f"BENCH_{name}.json"
            res = dict(res, bench=name,
                       smoke=bool(int(os.environ.get("BENCH_SMOKE", "0"))),
                       wall_seconds=round(time.time() - t0, 3))
            path.write_text(json.dumps(res, indent=2, sort_keys=True))
            print(f"# wrote {path}", file=sys.stderr)
        print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
