"""Operation and byte counts against hand counts at granite-moe-3b-a800m's
shapes and at those of a 12-layer stage of Granite-8B-Code (arXiv:2405.04324:
d 4096, 32/8 heads of 128, d_ff 14336, vocab 49152)."""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import flops, model  # noqa: E402

MOE = model.load("granite-moe-3b-a800m")
DENSE = {"model_type": "granite", "num_hidden_layers": 12,
         "hidden_size": 4096, "num_attention_heads": 32,
         "num_key_value_heads": 8, "head_dim": 128, "intermediate_size": 14336,
         "num_local_experts": 0, "num_experts_per_tok": 0,
         "vocab_size": 49152, "tie_word_embeddings": False,
         "dtype": "bfloat16"}


def test_token_flops_moe_counts_top_k_experts():
    # per layer: q,k,v,o projections 2*1536*(24+16)*64 + 2*24*64*1536,
    # router 2*1536*40, eight SwiGLU experts 8*6*1536*512
    per_layer = (2 * 1536 * 40 * 64 + 2 * 24 * 64 * 1536
                 + 2 * 1536 * 40 + 8 * 6 * 1536 * 512)
    assert per_layer == 50_454_528
    assert flops.token_flops(MOE) == 32 * per_layer
    # twice the ~0.8 B active parameters of "a800m"
    assert 1.55e9 < flops.token_flops(MOE) < 1.65e9


def test_token_flops_dense_stage():
    # 218.1 M parameters a layer, two operations each
    per_layer = 2 * 4096 * 48 * 128 + 2 * 32 * 128 * 4096 \
        + 6 * 4096 * 14336
    assert per_layer == 436_207_616
    assert flops.token_flops(DENSE) == 12 * per_layer


@pytest.mark.parametrize("cfg,per_key", [(MOE, 32 * 4 * 24 * 64),
                                         (DENSE, 12 * 4 * 32 * 128)])
def test_attention_and_head(cfg, per_key):
    assert flops.attention_flops(cfg, 1, 100) == 100 * per_key
    assert flops.head_flops(cfg) == 2 * cfg["hidden_size"] \
        * cfg["vocab_size"]


def test_prefill_flops_skips_store_tokens():
    # positions 256..299 attend over 257..300 keys: 12254 in all
    keys = sum(t + 1 for t in range(256, 300))
    assert keys == 12254
    want = 44 * flops.token_flops(MOE) + flops.attention_flops(MOE, 1, keys) \
        + flops.head_flops(MOE)
    assert flops.prefill_flops(MOE, 300, 256) == want
    # a whole prompt from scratch costs more than its tail
    assert flops.prefill_flops(MOE, 300, 0) > want


def test_paged_attention_cost_counts_held_tokens():
    got = flops.paged_attention_cost(MOE, 1, 100)
    assert got["flops"] == 32 * 4 * 24 * 64 * 100
    # per layer: k and v of 100 tokens (8 heads x 64, bf16) and their
    # positions, one bf16 query of 24 x 64, an f32 (o, l, m) per head
    per_layer = 100 * (2 * 8 * 64 * 2 + 4) + 24 * 64 * 2 + 24 * 66 * 4
    assert got["bytes"] == 32 * per_layer
    dense = flops.paged_attention_cost(DENSE, 4, 512)
    assert dense["flops"] == 12 * 4 * 32 * 128 * 4 * 512
    assert dense["bytes"] == 12 * (512 * (2 * 8 * 128 * 2 + 4)
                                   + 4 * 32 * 128 * 2 + 4 * 32 * 130 * 4)


@pytest.mark.parametrize("prompt,cached,chunk,want", [
    (600, 0, 256, [(256, 256), (512, 88)]),
    (600, 96, 256, [(96, 256), (352, 248)]),
    (200, 0, 256, []),
    (200, 160, 256, [(160, 40)]),
])
def test_chunked_prefix_work(prompt, cached, chunk, want):
    assert flops.chunked_prefix_work(prompt, cached, chunk) == want
