"""The correctness check decides ``correct`` the way it must: a run of the
program passes it, its float8 control does not, and a run whose timed
path alters a token where it is produced does not either: a decoded token,
or a first token (made by the prefill member and handed to the decode
member).

Runs drive the whole harness (set-up, warm-up, a measured window through
``Server``, the check and the result line) on the CPU at a tiny size;
only the look for a chip is skipped.  The tiny model's limit, 0.012, sits
between the readings of the seed the tests run: there the program's
widest gap reads 0 and the control's 0.078.  Over seeds 1-12 the program
reads 0-0.0072 and the control 0-0.078: at this size float8 does not
always change a token, so the tests keep to one seed.
"""
import pathlib
import sys
import time

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import correctness, harness, metrics  # noqa: E402

SEED = 2
LIMIT = 0.012
CFG = dict(name="tiny", source="test", model_type="granitemoe",
           hidden_size=64, num_hidden_layers=2,
           num_attention_heads=4, num_key_value_heads=2, head_dim=16,
           intermediate_size=32, num_local_experts=4, num_experts_per_tok=2,
           vocab_size=8192, hidden_act="silu", rms_norm_eps=1e-6,
           rope_theta=10000.0, tie_word_embeddings=True,
           initializer_range=0.05, dtype="bfloat16")
MIX = {"name": "tiny-open", "generator_seed": 1,
       "prefix_groups": 3, "prefix_len": [20, 40], "prefix_zipf": 1.1,
       "prefix_share": 0.8,
       "prompt": {"median": 12, "sigma": 0.8, "min": 4, "max": 20},
       "output": {"median": 16, "sigma": 0.3, "min": 8, "max": 24},
       "max_total": 60, "ramp_s": 1,
       "serving": {"n_prefill": 1, "n_decode": 1, "max_len": 64,
                   "max_batch": 4, "block_size": 16, "prefill_chunk": 2,
                   "chunk_tokens": 32}}
CELL = {"name": "tiny.tiny-open", "config": "tiny", "traffic": "tiny-open",
        "chips": 1}
BENCH = {"end_to_end": [
    {"name": "ttft_p95_ms", "unit": "ms"},
    {"name": "tbt_p99_ms", "unit": "ms"},
    {"name": "setup_s", "unit": "s"}], "per_layer": []}


def run(seed=SEED):
    from repro.core import analytical as A
    return harness.run_cell(
        seed, 5.0, False, cfg=CFG, mix=MIX,
        cellfile={"rate_per_s": 2.0, "limits": {"max_logit_gap": LIMIT}},
        t_process=time.perf_counter(),
        peaks={"flops": 1.0, "hbm_bw": 1.0}, hw=A.TPU_V5E)


@pytest.fixture(scope="module")
def program():
    return run()


def test_program_run_is_correct(program):
    check = program["check"]
    assert check["correct"], check["compared"]
    assert check["compared"]["tokens_compared"]["value"] \
        >= correctness.MIN_TOKENS
    line = metrics.result(BENCH, CELL, program, trace=False)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"ttft_p95_ms", "tbt_p99_ms", "setup_s"}
    assert check["compared"]["lost_requests"]["value"] == 0
    # the store served some prompt tokens: the shared-prefix path ran
    assert any(r.req.cached_tokens for r in program["run"].records.values())


def test_float8_control_is_not_correct(program):
    """The reference in float8, in the program's place: at each compared
    position the token float8 ranks first, read on the float32 logits."""
    params = harness.model.make_weights(CFG, SEED)
    check = correctness.check(program["run"], params, SEED, control=True)
    assert not check["correct"]
    assert check["compared"]["max_logit_gap"]["value"] > LIMIT


def test_altered_token_is_not_correct(monkeypatch):
    """Every token the decode step commits is replaced by the next id."""
    from repro.serving.engine import DecodeEngine

    commit = DecodeEngine.commit

    def altered(self, nxt):
        return commit(self, (np.asarray(nxt) + 1) % CFG["vocab_size"])

    monkeypatch.setattr(DecodeEngine, "commit", altered)
    out = run()
    assert not out["check"]["correct"]
    assert out["check"]["compared"]["max_logit_gap"]["value"] > 100 * LIMIT


def test_altered_first_token_is_not_correct(monkeypatch):
    """Every first token is replaced by the next id where the decode
    member takes the request over from the prefill member."""
    from repro.serving.engine import DecodeEngine

    insert = DecodeEngine.insert

    def altered(self, req, state, first_token, shared_pages=None):
        return insert(self, req, state,
                      (int(first_token) + 1) % CFG["vocab_size"],
                      shared_pages=shared_pages)

    monkeypatch.setattr(DecodeEngine, "insert", altered)
    out = run()
    assert not out["check"]["correct"]
    assert out["check"]["compared"]["max_logit_gap"]["value"] > 100 * LIMIT
