"""The plain reference computes the program's model, from the weights the
benchmark makes, in the program's layout; its float8 control does not."""
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import model, reference  # noqa: E402


def tiny(moe: bool, dtype: str = "float32") -> dict:
    return dict(name="tiny", source="test",
                model_type="granitemoe" if moe else "granite", hidden_size=64,
                num_hidden_layers=3, num_attention_heads=4,
                num_key_value_heads=2, head_dim=16, intermediate_size=32,
                num_local_experts=4 if moe else 0,
                num_experts_per_tok=2 if moe else 0, vocab_size=97,
                hidden_act="silu", rms_norm_eps=1e-6, rope_theta=10000.0,
                tie_word_embeddings=moe, initializer_range=0.02,
                dtype=dtype)


@pytest.mark.parametrize("moe", [True, False], ids=["moe_tied", "dense"])
def test_weights_in_the_program_layout(moe):
    from repro.models import transformer as T

    cfg = tiny(moe, "bfloat16")
    params = model.make_weights(cfg, 2**31 + 3)
    want = jax.eval_shape(lambda k: T.init(model.program_config(cfg), k,
                                           jnp.bfloat16),
                          jax.random.PRNGKey(0))
    assert jax.tree.structure(want) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(params)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    again = model.make_weights(cfg, 2**31 + 3)
    other = model.make_weights(cfg, 2**31 + 4)
    assert all(bool(jnp.array_equal(a, b)) for a, b in
               zip(jax.tree.leaves(params), jax.tree.leaves(again)))
    assert not jnp.array_equal(params["embed"], other["embed"])
    assert model.n_params(cfg) == sum(a.size for a in jax.tree.leaves(params))


@pytest.mark.parametrize("moe", [True, False], ids=["moe_tied", "dense"])
def test_reference_matches_program_forward(moe):
    from repro.models import transformer as T

    cfg = tiny(moe)
    params = model.make_weights(cfg, 7)
    toks = np.random.default_rng(0).integers(0, 97, 40)
    with jax.default_matmul_precision("highest"):
        want, _, _ = T.apply(model.program_config(cfg), params,
                             jnp.asarray(toks)[None], mode="train")
    read = np.arange(40)
    got = reference.logits(cfg, params, toks, read)
    assert float(jnp.max(jnp.abs(got - want[0]))) < 1e-5
    low = reference.logits(cfg, params, toks, read, precision="fp8")
    assert float(jnp.max(jnp.abs(low - got))) > 1e-3
    # causal: padding after the last position read changes nothing
    padded = np.concatenate([toks[:30], np.zeros(10, toks.dtype)])
    part = reference.logits(cfg, params, padded, np.arange(30))
    assert float(jnp.max(jnp.abs(part - got[:30]))) < 1e-5


def test_program_refuses_a_multiplier_it_lacks():
    cfg = dict(tiny(True), residual_multiplier=0.22)
    with pytest.raises(ValueError, match="residual_multiplier"):
        model.program_config(cfg)
    model.program_config(dict(tiny(True), attention_multiplier=0.25))
