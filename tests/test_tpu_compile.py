"""The main-path Pallas kernels compile for a TPU v5e chip.

Interpret mode (tests/test_kernels.py, tests/test_paged_kernels.py) checks
what the kernels compute; it cannot see Mosaic's tiling rules or its VMEM
budget.  These tests hand the installed TPU compiler a described (not
attached) v5e chip and compile each kernel at granite-moe-3b-a800m's
attention widths — 24 query heads, 8 KV heads, head_dim 64, 16-token pages,
bf16, batch 8 — through the same ``kernels.ops`` wrappers the model calls.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

H, KV, D, BS, B = 24, 8, 64, 16, 8
MAX_LEN = 1024                     # the one-chip serving fleet's max_len
NB = MAX_LEN // BS                 # pages per row
P = 1 + B * NB                     # pool pages (page 0 is scratch)
CHUNK = 256                        # chunked-prefill resume wave length
SPEC = 4                           # verify length (pending + 3 proposals)


@pytest.fixture(scope="module")
def one_chip():
    # a CPU-only jax install has no TPU compiler to describe a chip to
    pytest.importorskip("libtpu")
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else under /tmp
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def compile_for_chip(one_chip, no_persistent_cache):
    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                for s, dt in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()
    return run


def _pool(dtype=jnp.bfloat16):
    return ((P, BS, KV, D), dtype)


POS = ((P, BS), jnp.int32)
TABLES = ((B, NB), jnp.int32)


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8])
def test_paged_decode_compiles(compile_for_chip, kv_dtype):
    quant = kv_dtype == jnp.int8
    scales = [((P, BS, KV), jnp.float32)] * 2 if quant else []

    def step(q, k, v, pos, tbl, pq, *sc):
        return ops.paged_decode_attention(
            q, k, v, pos, tbl, pq, interpret=False,
            k_scale_pages=sc[0] if quant else None,
            v_scale_pages=sc[1] if quant else None)

    hlo = compile_for_chip(step, ((B, H, D), jnp.bfloat16), _pool(kv_dtype),
                           _pool(kv_dtype), POS, TABLES, ((B,), jnp.int32),
                           *scales)
    assert "tpu_custom_call" in hlo


def test_paged_verify_compiles(compile_for_chip):
    def step(q, k, v, pos, tbl, pq):
        return ops.paged_verify_attention(q, k, v, pos, tbl, pq,
                                          interpret=False)

    hlo = compile_for_chip(step, ((B, SPEC, H, D), jnp.bfloat16), _pool(),
                           _pool(), POS, TABLES, ((B, SPEC), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_paged_prefix_compiles(compile_for_chip):
    """The chunk-resume wave: the paged prefix read in place plus the
    causal flash partials of the chunk itself."""
    def step(q, k, v, kp, vp, pos, tbl, positions):
        return ops.paged_prefill_attention(q, k, v, kp, vp, pos, tbl,
                                           positions, interpret=False)

    hlo = compile_for_chip(
        step, ((B, CHUNK, H, D), jnp.bfloat16),
        ((B, CHUNK, KV, D), jnp.bfloat16), ((B, CHUNK, KV, D), jnp.bfloat16),
        _pool(), _pool(), POS, TABLES, ((B, CHUNK), jnp.int32))
    assert hlo.count("tpu_custom_call") >= 2


def test_split_kv_decode_compiles(compile_for_chip):
    def step(q, k, v, valid):
        return ops.decode_attention(q, k, v, valid, interpret=False)

    hlo = compile_for_chip(step, ((B, H, D), jnp.bfloat16),
                           ((B, MAX_LEN, KV, D), jnp.bfloat16),
                           ((B, MAX_LEN, KV, D), jnp.bfloat16),
                           ((B, MAX_LEN), jnp.bool_))
    assert "tpu_custom_call" in hlo
