"""Architectures as files: a configuration's ``model_type`` names its module
under ``benchmarks/chip/archs/``, and the granite cell reads what it read
before the modules were split out.

The parity values were computed on the tree before the split (the commit
whose ``model.py``, ``reference.py`` and ``flops.py`` held Granite's code):
the sha256 of the weights' leaf bytes, the reference's logits at float32
and float8 (``data/archs_parity.npz``) and the work counts of
granite-moe-3b-a800m.
"""
import hashlib
import importlib
import json
import pathlib
import sys

import jax
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import archs, flops, model, reference  # noqa: E402
from test_chipbench_reference import tiny  # noqa: E402

PARENT = np.load(pathlib.Path(__file__).parent / "data" / "archs_parity.npz")
WEIGHTS_SHA256 = {
    "moe_tied":
        "c9f3195e823e53e3337bbac2cd8665fac9c275b3eb013446ab7520d860340cba",
    "dense":
        "9901e3e43e5684725ff9dcf0c86dc1c3683951d9482eeafbd9d64a42996e2dae"}
CONTRACT = ("program_config", "param_shapes", "is_gain", "logits",
            "token_flops", "attention_flops", "head_flops",
            "paged_attention_cost")


def _sha256(params) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(params):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("moe", [True, False], ids=["moe_tied", "dense"])
def test_weights_equal_the_parents(moe):
    tag = "moe_tied" if moe else "dense"
    assert _sha256(model.make_weights(tiny(moe), 7)) == WEIGHTS_SHA256[tag]


@pytest.mark.parametrize("precision", ["float32", "fp8"])
@pytest.mark.parametrize("moe", [True, False], ids=["moe_tied", "dense"])
def test_reference_equals_the_parents(moe, precision):
    cfg = tiny(moe)
    toks = np.random.default_rng(0).integers(0, 97, 40)
    got = reference.logits(cfg, model.make_weights(cfg, 7), toks,
                           np.arange(40), precision=precision)
    want = PARENT[f"{'moe_tied' if moe else 'dense'}_{precision}"]
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6,
                               atol=1e-6 * float(np.abs(want).max()))


def test_work_counts_equal_the_parents():
    cfg = model.load("granite-moe-3b-a800m")
    assert flops.token_flops(cfg) == 1614544896.0
    assert flops.paged_attention_cost(cfg, 256, 512) == {
        "flops": 25769803776.0, "bytes": 110690304.0}
    assert flops.prefill_flops(cfg, 700, 256) == 758779094016.0


TOY = '''
import jax.numpy as jnp


def program_config(cfg):
    if cfg["hidden_act"] != "relu":
        raise ValueError("the toy runs relu")
    return ("toy", cfg["hidden_size"])


def param_shapes(cfg):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed": ((v, d), jnp.float32), "scale": ((d,), jnp.float32),
            "unembed": ((d, v), jnp.float32)}


def is_gain(leaf_name):
    return leaf_name == "scale"


def logits(cfg, params, tokens, read_at, precision):
    x = params["embed"][tokens[read_at]] * (1.0 + params["scale"])
    return x @ params["unembed"]


def token_flops(cfg):
    return 2.0 * cfg["hidden_size"]


def attention_flops(cfg, queries, keys_per_query):
    return 3.0 * queries * keys_per_query


def head_flops(cfg):
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def paged_attention_cost(cfg, queries, held):
    return {"flops": attention_flops(cfg, queries, held),
            "bytes": 4.0 * held}
'''


def test_a_new_architecture_is_one_file(tmp_path, monkeypatch):
    """A module dropped beside the others, and a configuration naming it,
    reach the weights, parameter count, reference and work counts."""
    (tmp_path / "toy_arch_v1.py").write_text(TOY)
    monkeypatch.setattr(archs, "__path__",
                        list(archs.__path__) + [str(tmp_path)])
    importlib.invalidate_caches()
    cfg = dict(name="toy", model_type="toy-arch.v1", hidden_size=8,
               vocab_size=11, hidden_act="relu", initializer_range=0.5,
               dtype="float32")
    try:
        assert archs.of(cfg).__file__ == str(tmp_path / "toy_arch_v1.py")
        params = model.make_weights(cfg, 3)
        assert {k: v.shape for k, v in params.items()} == {
            "embed": (11, 8), "scale": (8,), "unembed": (8, 11)}
        assert not np.any(params["scale"]) and np.all(params["embed"])
        assert model.n_params(cfg) == 11 * 8 + 8 + 8 * 11
        toks, read = np.arange(10) % 11, np.array([2, 5, 9])
        want = params["embed"][toks[read]] @ params["unembed"]
        np.testing.assert_array_equal(
            reference.logits(cfg, params, toks, read), want)
        # 6 new tokens, (10*11 - 4*5) / 2 = 45 keys, the head once
        assert flops.prefill_flops(cfg, 10, 4) == 6 * 16 + 3 * 45 + 176
        assert flops.paged_attention_cost(cfg, 2, 5) == {"flops": 30.0,
                                                         "bytes": 20.0}
        with pytest.raises(ValueError, match="relu"):
            model.make_weights(dict(cfg, hidden_act="silu"), 3)
    finally:
        sys.modules.pop(f"{archs.__name__}.toy_arch_v1", None)


def test_every_benchmark_config_resolves():
    with open(ROOT / "BENCHMARK.json") as f:
        configs = json.load(f)["configs"]
    for entry in configs:
        arch = archs.of(model.load(entry["name"]))
        assert all(callable(getattr(arch, n, None)) for n in CONTRACT), \
            arch.__name__


@pytest.mark.parametrize("cfg,message", [
    (dict(name="x", model_type="no-such.arch"),
     "add benchmarks/chip/archs/no_such_arch.py"),
    (dict(name="x"), "states no model_type"),
])
def test_unknown_model_type_names_the_file_to_add(cfg, message):
    with pytest.raises(ValueError, match=message):
        archs.of(cfg)
