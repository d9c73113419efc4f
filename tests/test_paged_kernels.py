"""Page-fused Pallas kernels vs gather-then-attend oracles, the
kernel-vs-dense serving contract, and the int8 KV page precision harness.

Three layers of guarantees:

* **Kernel == oracle**: the page-fused decode and chunked-prefill kernels
  (block table in the index_map, no dense KV view) sweep against
  monolithic-softmax references across GQA ratios, windows, soft caps,
  dead table entries, scratch-page junk and int8 pages.
* **Kernel == dense engine**: the default (kernel) decode path and the
  ``decode_kernel=False`` gather-then-attend reference produce identical
  token streams through the real engines — plain, windowed, soft-capped
  and quantized stacks, and through the orchestrated shared-prefix /
  copy-on-write path.
* **Precision policy**: int8 KV pages round-trip within half an int8 step
  of the per-(entry, head) scale (hypothesis + seeded drivers), and
  teacher-forced greedy decode over a quantized cache agrees with the
  full-precision stack on >= 90% of steps (it is exact at tiny scale; the
  threshold leaves headroom for argmax near-ties).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TINY, TINY_ECFG
from repro.core.analytical import TPU_V5E
from repro.kernels import ops
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.ref import (flash_prefill_reference,
                               paged_decode_attention_reference,
                               paged_prefill_attention_reference,
                               paged_verify_attention_reference)
from repro.models import transformer as T
from repro.models.config import Family, ModelConfig
from repro.models.quant import (dequantize_kv_page, quantize_kv_page,
                                quantize_kv_pages)
from repro.serving.engine import DecodeEngine, EngineConfig, PrefillEngine
from repro.serving.request import Request

try:
    from hypothesis import given, settings
    from hypothesis import strategies as hst
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# Synthetic paged pools
# ---------------------------------------------------------------------------

def _paged_case(seed, b, h, kv, d, bs, nb_slot, quant=False):
    """Random pool + ragged per-row tables.  Dead table entries stay -1;
    the scratch page (and every unassigned page) is poisoned with live-
    looking positions so any unmasked read through a dead entry shows."""
    rng = np.random.default_rng(seed)
    n_phys = 1 + b * nb_slot
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    k_pages = jnp.asarray(rng.normal(size=(n_phys, bs, kv, d)), jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=(n_phys, bs, kv, d)), jnp.float32)
    pos_pages = np.asarray(rng.integers(0, bs * nb_slot,
                                        (n_phys, bs)), np.int32)  # poison
    tables = np.full((b, nb_slot), -1, np.int32)
    lengths = rng.integers(1, bs * nb_slot + 1, b)
    nxt = 1
    for row, n_tok in enumerate(lengths):
        n_used = -(-int(n_tok) // bs)
        for j in range(n_used):
            tables[row, j] = nxt
            page_pos = np.arange(j * bs, (j + 1) * bs)
            page_pos[page_pos >= n_tok] = -1     # blank tail of last page
            pos_pages[nxt] = page_pos
            nxt += 1
    pos_q = jnp.asarray(lengths - 1, jnp.int32)   # decoding the next token
    case = dict(q=q, k_pages=k_pages, v_pages=v_pages,
                pos_pages=jnp.asarray(pos_pages),
                block_tables=jnp.asarray(tables), pos_q=pos_q)
    if quant:
        kq, ks, vq, vs = quantize_kv_pages(k_pages, v_pages)
        case.update(k_pages=kq, v_pages=vq, k_scale_pages=ks,
                    v_scale_pages=vs)
    return case


DECODE_CASES = [
    # b, h, kv, d, bs, nb, window, soft_cap
    (2, 4, 2, 32, 8, 6, None, None),
    (3, 8, 8, 64, 16, 4, None, None),     # MHA-as-GQA
    (2, 4, 1, 32, 8, 8, None, None),      # MQA
    (2, 4, 2, 32, 8, 6, 12, None),        # sliding window
    (2, 8, 2, 64, 16, 4, None, 30.0),     # gemma-style soft cap
    (1, 4, 2, 32, 8, 6, 10, 20.0),        # window + cap together
]


@pytest.mark.parametrize("b,h,kv,d,bs,nb,win,cap", DECODE_CASES)
def test_paged_decode_vs_oracle(b, h, kv, d, bs, nb, win, cap):
    c = _paged_case(0, b, h, kv, d, bs, nb)
    out = ops.paged_decode_attention(c["q"], c["k_pages"], c["v_pages"],
                                     c["pos_pages"], c["block_tables"],
                                     c["pos_q"], window=win, soft_cap=cap,
                                     interpret=True)
    ref = paged_decode_attention_reference(
        c["q"], c["k_pages"], c["v_pages"], c["pos_pages"],
        c["block_tables"], c["pos_q"], window=win, soft_cap=cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("win,cap", [(None, None), (12, None), (None, 30.0)])
def test_paged_decode_quant_vs_oracle(win, cap):
    """int8 pools dequantize inside the kernel (scales folded into the
    score/value matmuls) and still match the dense dequant oracle."""
    c = _paged_case(1, 2, 4, 2, 32, 8, 6, quant=True)
    out = ops.paged_decode_attention(
        c["q"], c["k_pages"], c["v_pages"], c["pos_pages"],
        c["block_tables"], c["pos_q"], window=win, soft_cap=cap,
        k_scale_pages=c["k_scale_pages"], v_scale_pages=c["v_scale_pages"],
        interpret=True)
    ref = paged_decode_attention_reference(
        c["q"], c["k_pages"], c["v_pages"], c["pos_pages"],
        c["block_tables"], c["pos_q"], window=win, soft_cap=cap,
        k_scale_pages=c["k_scale_pages"], v_scale_pages=c["v_scale_pages"])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_decode_dead_entries_and_scratch_junk():
    """A row whose table is entirely dead (all -1, clamped to the poisoned
    scratch page by the index_map) attends over nothing real: with one
    valid self-token it reduces to that token's value row."""
    b, h, kv, d, bs, nb = 2, 4, 2, 32, 8, 4
    c = _paged_case(2, b, h, kv, d, bs, nb)
    tables = np.asarray(c["block_tables"]).copy()
    tables[1] = -1                      # row 1: no pages at all
    one = np.asarray(c["pos_pages"]).copy()
    out = ops.paged_decode_attention(c["q"], c["k_pages"], c["v_pages"],
                                     jnp.asarray(one), jnp.asarray(tables),
                                     c["pos_q"], interpret=True)
    ref = paged_decode_attention_reference(
        c["q"], c["k_pages"], c["v_pages"], jnp.asarray(one),
        jnp.asarray(tables), c["pos_q"])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # the all-masked row's partials must not poison the combine with NaNs
    assert bool(jnp.all(jnp.isfinite(out)))


# ---------------------------------------------------------------------------
# Multi-query verify kernel (speculative decoding's batched scorer)
# ---------------------------------------------------------------------------

def _verify_case(seed, b, h, kv, d, bs, nb_slot, s_len, quant=False):
    """Like ``_paged_case`` but with S speculative queries per row at the
    row's last S consecutive positions (lengths drawn >= S so every query
    has real keys beneath it)."""
    rng = np.random.default_rng(seed)
    c = _paged_case(seed, b, h, kv, d, bs, nb_slot, quant=quant)
    lengths = rng.integers(s_len, bs * nb_slot + 1, b)
    tables = np.full((b, nb_slot), -1, np.int32)
    pos_pages = np.asarray(rng.integers(0, bs * nb_slot,
                                        (1 + b * nb_slot, bs)), np.int32)
    nxt = 1
    for row, n_tok in enumerate(lengths):
        n_used = -(-int(n_tok) // bs)
        for j in range(n_used):
            tables[row, j] = nxt
            page_pos = np.arange(j * bs, (j + 1) * bs)
            page_pos[page_pos >= n_tok] = -1
            pos_pages[nxt] = page_pos
            nxt += 1
    c["q"] = jnp.asarray(rng.normal(size=(b, s_len, h, d)), jnp.float32)
    c["block_tables"] = jnp.asarray(tables)
    c["pos_pages"] = jnp.asarray(pos_pages)
    c["pos_q"] = jnp.asarray(lengths[:, None] - s_len
                             + np.arange(s_len)[None, :], jnp.int32)
    return c


VERIFY_CASES = [
    # b, h, kv, d, bs, nb, s_len, window, soft_cap, quant
    (2, 4, 2, 32, 8, 6, 3, None, None, False),
    (3, 8, 8, 64, 16, 4, 5, None, None, False),   # MHA-as-GQA
    (2, 4, 1, 32, 8, 8, 4, None, None, False),    # MQA
    (2, 4, 2, 32, 8, 6, 3, 12, None, False),      # sliding window
    (2, 8, 2, 64, 16, 4, 4, None, 30.0, False),   # soft cap
    (2, 4, 2, 32, 8, 6, 3, None, None, True),     # int8 pages
]


@pytest.mark.parametrize("b,h,kv,d,bs,nb,s,win,cap,quant", VERIFY_CASES)
def test_paged_verify_vs_oracle(b, h, kv, d, bs, nb, s, win, cap, quant):
    """Each of the S queries must equal an independent single-token decode
    at its own position — the exactness the accept-longest-prefix rule
    rests on."""
    c = _verify_case(10, b, h, kv, d, bs, nb, s, quant=quant)
    scales = ({"k_scale_pages": c["k_scale_pages"],
               "v_scale_pages": c["v_scale_pages"]} if quant else {})
    out = ops.paged_verify_attention(c["q"], c["k_pages"], c["v_pages"],
                                     c["pos_pages"], c["block_tables"],
                                     c["pos_q"], window=win, soft_cap=cap,
                                     interpret=True, **scales)
    ref = paged_verify_attention_reference(
        c["q"], c["k_pages"], c["v_pages"], c["pos_pages"],
        c["block_tables"], c["pos_q"], window=win, soft_cap=cap, **scales)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_verify_dead_entries_finite():
    """A row with an all-dead table (clamped to the poisoned scratch page)
    must stay finite through the per-query combines."""
    b, h, kv, d, bs, nb, s = 2, 4, 2, 32, 8, 4, 3
    c = _verify_case(11, b, h, kv, d, bs, nb, s)
    tables = np.asarray(c["block_tables"]).copy()
    tables[1] = -1
    out = ops.paged_verify_attention(c["q"], c["k_pages"], c["v_pages"],
                                     c["pos_pages"], jnp.asarray(tables),
                                     c["pos_q"], interpret=True)
    ref = paged_verify_attention_reference(
        c["q"], c["k_pages"], c["v_pages"], c["pos_pages"],
        jnp.asarray(tables), c["pos_q"])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert bool(jnp.all(jnp.isfinite(out)))


def test_paged_verify_width_one_matches_decode():
    """S=1 verify degenerates to the single-query decode kernel (same
    partials, same combine) — the fallback equivalence the engine's
    dispatch relies on."""
    b, h, kv, d, bs, nb = 2, 4, 2, 32, 8, 6
    c = _verify_case(12, b, h, kv, d, bs, nb, 1)
    out = ops.paged_verify_attention(c["q"], c["k_pages"], c["v_pages"],
                                     c["pos_pages"], c["block_tables"],
                                     c["pos_q"], interpret=True)
    one = ops.paged_decode_attention(c["q"][:, 0], c["k_pages"],
                                     c["v_pages"], c["pos_pages"],
                                     c["block_tables"], c["pos_q"][:, 0],
                                     interpret=True)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(one),
                               rtol=2e-6, atol=2e-6)


PREFILL_CASES = [
    # s (chunk len), prefix bs, nb, window, soft_cap
    (16, 8, 4, None, None),
    (24, 8, 6, None, None),      # non-pow2 chunk exercises the pad path
    (16, 8, 4, 12, None),
    (32, 16, 3, None, 30.0),
]


@pytest.mark.parametrize("s,bs,nb,win,cap", PREFILL_CASES)
def test_paged_prefill_vs_oracle(s, bs, nb, win, cap):
    """Resume-chunk queries attend over the paged prefix in-kernel plus
    the in-flight suffix — one exact split softmax, vs the monolithic
    gather-then-attend oracle."""
    b, h, kv, d = 2, 4, 2, 32
    rng = np.random.default_rng(3)
    c = _paged_case(3, b, h, kv, d, bs, nb)
    prefix_len = np.asarray(c["pos_q"]) + 1      # tokens already published
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, kv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, kv, d)), jnp.float32)
    positions = jnp.asarray(prefix_len[:, None] + np.arange(s)[None, :],
                            jnp.int32)
    out = ops.paged_prefill_attention(
        q, k, v, c["k_pages"], c["v_pages"], c["pos_pages"],
        c["block_tables"], positions, window=win, soft_cap=cap,
        block_q=16, block_k=16, interpret=True)
    ref = paged_prefill_attention_reference(
        q, k, v, c["k_pages"], c["v_pages"], c["pos_pages"],
        c["block_tables"], positions, window=win, soft_cap=cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_prefill_partials_reconstruct_output():
    """``return_partials`` is the suffix partition of the fused paged
    prefill: normalizing the partial triple recovers the plain kernel
    output exactly."""
    rng = np.random.default_rng(4)
    b, s, h, kv, d = 2, 32, 4, 2, 32
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, kv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, kv, d)), jnp.float32)
    o, l, m = flash_prefill(q, k, v, block_q=16, block_k=16,
                            return_partials=True, interpret=True)
    full = flash_prefill_reference(q, k, v)
    recon = o / l[..., None]
    np.testing.assert_allclose(np.asarray(recon), np.asarray(full),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# int8 KV pages: round-trip bound + decode agreement harness
# ---------------------------------------------------------------------------

def _assert_page_roundtrip(x: np.ndarray) -> None:
    """Round-trip error is bounded by half an int8 grid step of each
    (entry, head)'s own scale — the exactness-tolerance contract every
    BlockKind's pageable KV relies on."""
    q, s = quantize_kv_page(jnp.asarray(x, jnp.float32))
    back = np.asarray(dequantize_kv_page(q, s, jnp.float32))
    err = np.abs(back - x)
    bound = np.asarray(s)[..., None] * 0.51 + 1e-6
    assert np.all(err <= bound), float((err - bound).max())


# pool-leaf shapes as each pageable BlockKind lays them out: plain pools,
# scan-stacked group pools, MQA/GQA head counts
_PAGE_SHAPES = [(5, 8, 2, 16), (2, 5, 8, 2, 16), (9, 16, 1, 32),
                (3, 4, 8, 4, 8)]


@pytest.mark.parametrize("shape", _PAGE_SHAPES)
@pytest.mark.parametrize("scale", [1e-3, 1.0, 30.0])
def test_kv_page_roundtrip_seeded(shape, scale):
    rng = np.random.default_rng(hash((shape, scale)) % (2 ** 31))
    _assert_page_roundtrip(rng.normal(size=shape) * scale)


if HAVE_HYPOTHESIS:
    @settings(max_examples=30, deadline=None)
    @given(hst.integers(0, 2 ** 31 - 1),
           hst.sampled_from(_PAGE_SHAPES),
           hst.floats(1e-4, 1e4))
    def test_kv_page_roundtrip_hypothesis(seed, shape, scale):
        rng = np.random.default_rng(seed)
        _assert_page_roundtrip(rng.normal(size=shape) * scale)


_QUANT_CFGS = [
    pytest.param(ModelConfig(
        name="kq-gqa", family=Family.DENSE, n_layers=3, d_model=64,
        n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128), id="gqa"),
    pytest.param(ModelConfig(
        name="kq-swa", family=Family.DENSE, n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=128,
        sliding_window=16), id="sliding-window"),
    pytest.param(ModelConfig(
        name="kq-cap", family=Family.DENSE, n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=1, d_ff=128, vocab_size=128,
        logit_soft_cap=30.0), id="mqa-softcap"),
]


@pytest.mark.parametrize("cfg", _QUANT_CFGS)
def test_quantized_decode_greedy_agreement(cfg, model_zoo):
    """The precision policy: teacher-forced greedy decode over the int8
    cache agrees with the bf16/f32 stack on the prefill argmax row and on
    >= 90% of decode steps (same forced token stream feeds both, so a
    single near-tie flip cannot cascade)."""
    params = model_zoo(cfg)
    cfgq = cfg.with_kv_quant()
    rng = np.random.default_rng(7)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 20)), jnp.int32)
    c = T.init_cache(cfg, 2, 64)
    cq = T.init_cache(cfgq, 2, 64)
    lg, c, _ = T.prefill(cfg, params, toks, c)
    lgq, cq, _ = T.prefill(cfgq, params, toks, cq)
    assert bool(jnp.all(jnp.argmax(lg, -1) == jnp.argmax(lgq, -1)))
    forced = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 10)), jnp.int32)
    agree = total = 0
    for i in range(forced.shape[1]):
        o, c, _ = T.decode_step(cfg, params, forced[:, i:i + 1], c)
        oq, cq, _ = T.decode_step(cfgq, params, forced[:, i:i + 1], cq)
        agree += int(jnp.sum(jnp.argmax(o, -1) == jnp.argmax(oq, -1)))
        total += o.shape[0]
    assert agree / total >= 0.9, f"agreement {agree}/{total}"


# ---------------------------------------------------------------------------
# Engine contract: kernel decode == dense-gather reference, stream for
# stream, across BlockKind variants and the shared-prefix/COW path
# ---------------------------------------------------------------------------

_ENGINE_CFGS = [
    pytest.param(TINY, id="attention-gqa"),
    pytest.param(ModelConfig(
        name="ek-swa", family=Family.DENSE, n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
        sliding_window=16), id="sliding-window"),
    pytest.param(ModelConfig(
        name="ek-cap", family=Family.DENSE, n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=1, d_ff=128, vocab_size=128,
        logit_soft_cap=30.0), id="mqa-softcap"),
    pytest.param(TINY.with_kv_quant(), id="int8-pages"),
]


def _ab_streams(cfg, params, ecfg_base, prompts, max_new=8):
    streams = []
    for dk in (None, False):
        ecfg = dataclasses.replace(ecfg_base, decode_kernel=dk)
        pe = PrefillEngine(cfg, params, ecfg, None)
        de = DecodeEngine(cfg, params, ecfg, name=f"ab-{dk}")
        assert de.use_kernel == (dk is None and de.paged)
        reqs = []
        for rid, prompt in enumerate(prompts):
            r = Request(rid=rid, arrival=0.0, prompt=prompt.copy(),
                        max_new_tokens=max_new)
            st, lg = pe.run(r)
            de.insert(r, st, int(jnp.argmax(lg)))
            reqs.append(r)
        while de.active:
            de.step()
        streams.append([list(r.generated) for r in reqs])
    return streams


@pytest.mark.parametrize("cfg", _ENGINE_CFGS)
def test_decode_kernel_matches_dense_reference(cfg, model_zoo):
    """decode_kernel=None (page-fused kernel, the default) and
    decode_kernel=False (dense gather-then-attend A/B baseline) produce
    identical token streams on identical workloads."""
    params = model_zoo(cfg)
    ecfg = EngineConfig(max_len=64, max_batch=3, block_size=8)
    rng = np.random.default_rng(5)
    prompts = [np.asarray(rng.integers(0, cfg.vocab_size, 11 + 6 * i),
                          np.int32) for i in range(3)]
    kernel, dense = _ab_streams(cfg, params, ecfg, prompts)
    assert kernel == dense
    assert all(len(s) == 8 for s in kernel)


def test_decode_kernel_default_auto(tiny_params):
    """None = auto: kernel on for paged pools, off only on explicit
    opt-out or when the stack has no pageable KV."""
    de = DecodeEngine(TINY, tiny_params, TINY_ECFG)
    assert de.paged and de.use_kernel
    de_off = DecodeEngine(TINY, tiny_params,
                          dataclasses.replace(TINY_ECFG,
                                              decode_kernel=False))
    assert de_off.paged and not de_off.use_kernel
    from repro.models.config import BlockKind
    ssm = ModelConfig(name="ek-ssm", family=Family.SSM, n_layers=2,
                      d_model=32, n_heads=4, n_kv_heads=4, d_ff=0,
                      vocab_size=64, block_pattern=(BlockKind.MLSTM,))
    de_ssm = DecodeEngine(ssm, T.init(ssm, jax.random.PRNGKey(0)),
                          dataclasses.replace(TINY_ECFG, max_len=32))
    assert not de_ssm.paged and not de_ssm.use_kernel


def test_kernel_vs_dense_through_shared_prefix_orchestration(tiny_params):
    """The A/B holds through the full orchestrator with prefix sharing:
    zero-copy bound pages and copy-on-write forks feed the kernel the
    exact aliased tables the dense reference reads — token streams
    identical, sharing active in both arms."""
    from repro.serving.orchestrator import Orchestrator, OrchestratorConfig
    from repro.serving.workload import WorkloadConfig, generate
    outs = []
    for dk in (None, False):
        reqs = generate(WorkloadConfig(
            kind="synthetic", rps=500.0, n_requests=6,
            vocab_size=TINY.vocab_size, max_new_tokens=5, prefix_share=0.9,
            n_prefix_groups=1, seed=17, prompt_len_lo=16, prompt_len_hi=32))
        orch = Orchestrator(TINY, tiny_params, OrchestratorConfig(
            hw=TPU_V5E,
            n_prefill=1, n_decode=1, migration=False,
            engine=dataclasses.replace(TINY_ECFG, decode_kernel=dk)))
        s = orch.run(reqs)
        assert s["pages_bound"] > 0
        outs.append({r.rid: list(r.generated) for r in reqs})
    assert outs[0] == outs[1]
