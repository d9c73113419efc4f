"""Live-engine router A/B under SLOs (Fig. 2a on real engines, in the
time domain).

The same prefix-skewed workload runs through the event-driven virtual-clock
orchestrator under

* ``load_aware``   — queue-delay-aware LoadAwareRouter + one Global KV
  Cache Store shared by every prefill instance (the BanaServe decoupling),
* ``prefix_aware`` — PrefixAwareRouter + per-instance private caches (the
  cache-locality coupling of Fig. 2a), and
* ``round_robin``  — locality- and load-blind control.

Migration is off in all modes so the columns isolate the *routing* policy.
Since the virtual-clock refactor the A/B is a time-domain claim: TTFT/TPOT
percentiles, SLO attainment and goodput per mode — the prefix-aware
baseline concentrates the hot prefixes' queueing delay on few instances,
which load-aware routing avoids (checked by the emitted ``winner`` field:
load_aware must not lose attainment/p99-TTFT to prefix_aware on this
workload).  Chunked prefill is on, so long prompts never stall decode.

    PYTHONPATH=src python -m benchmarks.run --only orchestrator

``benchmarks/run.py`` writes the returned payload to
``BENCH_orchestrator.json``; ``BENCH_SMOKE=1`` shrinks the workload for
the CI bench-smoke job.
"""
import os
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).parent.parent / "src"))

import jax

from repro.core.analytical import TPU_V5E
from repro.models import transformer as T
from repro.models.config import Family, ModelConfig
from repro.serving.api import Server
from repro.serving.engine import EngineConfig
from repro.serving.orchestrator import Orchestrator, OrchestratorConfig
from repro.serving.request import SLO
from repro.serving.workload import WorkloadConfig, generate

CFG = ModelConfig(name="bench", family=Family.DENSE, n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128)

MODES = {
    "load_aware": dict(router="load_aware", global_store=True),
    "prefix_aware": dict(router="prefix_aware", global_store=False),
    "round_robin": dict(router="round_robin", global_store=False),
}

KEEP = ("throughput_tok_s", "p50_ttft_s", "p99_ttft_s", "p50_tpot_s",
        "p99_tpot_s", "slo_attainment", "goodput_tok_s",
        "prefill_token_skew", "store_hit_rate", "virtual_time_s", "events")


def main() -> dict:
    smoke = bool(int(os.environ.get("BENCH_SMOKE", "0")))
    params = T.init(CFG, jax.random.PRNGKey(0))
    ecfg = EngineConfig(max_len=96, max_batch=4, block_size=8)
    # SLO targets sit between the balanced and the skewed regimes' p99s,
    # so attainment separates the routers instead of saturating at 0/1
    slo = SLO(ttft_s=2.2e-6, tpot_s=1.5e-6)
    # prefill-bound shape (long prompts, near-zero generation): under the
    # roofline model one decode token costs ~a 150-token prefill, so the
    # routing A/B only shows in the time domain when TTFT dominates
    wl = WorkloadConfig(kind="synthetic", rps=5e7,
                        n_requests=12 if smoke else 32,
                        vocab_size=128, max_new_tokens=2, prefix_share=0.9,
                        n_prefix_groups=1, prefix_zipf=2.0, seed=2,
                        prompt_len_lo=48, prompt_len_hi=80)
    print("fig2a_live,mode,throughput_tok_s,p50_ttft_us,p99_ttft_us,"
          "p50_tpot_us,p99_tpot_us,slo_attainment,goodput_tok_s,"
          "prefill_token_skew,store_hit_rate")
    results = {}
    for mode, kw in MODES.items():
        s = None
        for _warm in (True, False):          # warmup shares the jit cache
            # backend-agnostic drive: every mode goes through the Server
            # front door (the same surface the sim benches use)
            server = Server(Orchestrator(CFG, params, OrchestratorConfig(
                hw=TPU_V5E,
                n_prefill=3, n_decode=3, engine=ecfg, migration=False,
                chunk_tokens=16, slo=slo, **kw)))
            s = server.run(generate(wl))
        results[mode] = {k: s[k] for k in KEEP}
        print(f"fig2a_live,{mode},{s['throughput_tok_s']:.1f},"
              f"{s['p50_ttft_s'] * 1e6:.2f},{s['p99_ttft_s'] * 1e6:.2f},"
              f"{s['p50_tpot_s'] * 1e6:.2f},{s['p99_tpot_s'] * 1e6:.2f},"
              f"{s['slo_attainment']:.3f},{s['goodput_tok_s']:.1f},"
              f"{s['prefill_token_skew']:.3f},{s['store_hit_rate']:.3f}")
    la, pa = results["load_aware"], results["prefix_aware"]
    winner = (la["slo_attainment"] >= pa["slo_attainment"]
              and la["p99_ttft_s"] <= pa["p99_ttft_s"])
    print(f"# load_aware beats prefix_aware on prefix-skewed: {winner}")
    return {"figure": "fig2a_live", "slo": {"ttft_s": slo.ttft_s,
                                            "tpot_s": slo.tpot_s},
            "workload": {"rps": wl.rps, "n_requests": wl.n_requests,
                         "prefix_share": wl.prefix_share},
            "scenarios": results,
            "load_aware_beats_prefix_aware": winner}


if __name__ == "__main__":
    main()
