"""Zero-copy prefix sharing: refcounted copy-on-write paged blocks.

Three layers of guarantees, matching the sharing design's trust chain:

* **BlockPool properties**: under arbitrary alloc / bind / release
  sequences the pool conserves — free list plus live pages accounts for
  every page, refcounts equal holder counts, a page frees exactly when
  its last holder lets go (free-at-zero).
* **Engine-integrated properties** (real jitted engines): random bind /
  append / fork / abort / extract / drain / reclaim op sequences keep
  the refcount invariants through the actual serving paths, with the
  Global KV Store holding pages of the live pool.
* **Exactness**: a shared-prefix decode is bit-identical to recomputing
  from token 0 — including a copy-on-write divergence mid-block, every
  BlockKind (paged attention stacks share; windowed / recurrent stacks
  fall back to the copy path), and a live ``move_span`` while a shared
  prefix is in flight.

The random-sequence machines run under hypothesis when it is installed
(wide exploration + shrinking) and under seeded numpy drivers always, so
the properties are exercised in every environment.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TINY, TINY_ECFG, assert_pools_restored
from repro.core.analytical import TPU_V5E
from repro.core.kvstore import GlobalKVStore, chain_hashes
from repro.core.layer_migration import even_spans
from repro.models import kvcache as KC
from repro.models.config import BlockKind, Family, ModelConfig
from repro.serving.engine import DecodeEngine, EngineConfig, PrefillEngine
from repro.serving.orchestrator import Orchestrator, OrchestratorConfig
from repro.serving.request import Request
from repro.serving.span import DecodePipeline
from repro.serving.workload import WorkloadConfig, generate

try:
    from hypothesis import given, settings
    from hypothesis import strategies as hst
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

BS = TINY_ECFG.block_size

_POOL_OP_NAMES = ("alloc", "bind", "release", "drop")
_ENGINE_OP_NAMES = ("insert", "insert12", "step", "abort", "extract",
                    "drain", "reclaim")


# ---------------------------------------------------------------------------
# BlockPool conservation under random op sequences (pure host)
# ---------------------------------------------------------------------------

def _run_pool_machine(ops, n_pages):
    """free list + Σ live pages (weighted by refcount = holder count)
    accounts for every page after ANY alloc/bind/release interleaving,
    and a page returns to the free list exactly at refcount zero."""
    pool = KC.BlockPool(n_pages)
    holders = [[] for _ in range(4)]     # model: who holds which pages
    for op, h, x in ops:
        if op == "alloc":
            n = x % 3 + 1
            if len(pool.free_list) >= n:
                holders[h] += pool.alloc(n)
        elif op == "bind":               # zero-copy bind: ref a live page
            live = [p for hs in holders for p in hs]
            if live:
                p = live[x % len(live)]
                pool.ref([p])
                holders[h].append(p)
        elif op == "release":
            if holders[h]:
                p = holders[h].pop(x % len(holders[h]))
                freed = pool.unref([p])
                still_held = any(p in hs for hs in holders)
                assert (p in freed) == (not still_held), \
                    "page freed while held / leaked at refcount zero"
        else:                            # drop: release a whole holder
            for p in holders[h]:
                pool.unref([p])
            holders[h] = []
        pool.check(holders=holders)
    for hs in holders:                   # teardown: everything comes back
        for p in hs:
            pool.unref([p])
    pool.check(holders=[])
    assert len(pool.free_list) == pool.n_pages - pool.n_reserved


if HAVE_HYPOTHESIS:
    _POOL_OPS = hst.lists(
        hst.tuples(hst.sampled_from(_POOL_OP_NAMES),
                   hst.integers(0, 3),       # holder id
                   hst.integers(0, 11)),     # op-specific selector
        max_size=40)

    @settings(max_examples=200, deadline=None)
    @given(_POOL_OPS, hst.integers(5, 16))
    def test_blockpool_conservation_random_ops(ops, n_pages):
        _run_pool_machine(ops, n_pages)


@pytest.mark.parametrize("seed", range(20))
def test_blockpool_conservation_seeded(seed):
    rng = np.random.default_rng(seed)
    ops = [(str(rng.choice(_POOL_OP_NAMES)), int(rng.integers(4)),
            int(rng.integers(12))) for _ in range(40)]
    _run_pool_machine(ops, int(rng.integers(5, 17)))


# ---------------------------------------------------------------------------
# Engine-integrated refcount invariants (real jitted serving paths)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def donor(tiny_params):
    """Prefilled wire states reused across examples: a 16-token prompt
    (2 full blocks — registrable) and its 12-token prefix (mid-block end
    — the COW trigger when fully bound)."""
    pe = PrefillEngine(TINY, tiny_params, TINY_ECFG, None)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, TINY.vocab_size, 16, dtype=np.int32)
    st16, lg16 = pe.run(Request(rid=990, arrival=0.0, prompt=prompt,
                                max_new_tokens=1))
    st12, lg12 = pe.run(Request(rid=991, arrival=0.0, prompt=prompt[:12],
                                max_new_tokens=1))
    return dict(prompt=prompt, keys=chain_hashes(prompt, BS),
                st16=st16, tok16=int(jnp.argmax(lg16)),
                st12=st12, tok12=int(jnp.argmax(lg12)))


def _run_engine_machine(donor, tiny_params, ops):
    """bind/append/fork/abort/extract/drain against a real DecodeEngine
    with the store holding pages of its pool: after every op the free
    list + slot rows + store holds account for every page with matching
    refcounts, and teardown restores the whole pool."""
    store = GlobalKVStore(block_size=BS)
    de = DecodeEngine(TINY, tiny_params, TINY_ECFG, name="dprop")
    de.attach_store(store)
    store.insert(donor["prompt"], ["b0", "b1"], nbytes_per_block=4096)
    keys = donor["keys"]
    rid = iter(range(100))

    def check():
        holders = [de.slot_pages(i) for i in range(TINY_ECFG.max_batch)]
        holders += [[p] for p in store.pool_pages(de.name).values()]
        de.pool.check(holders=holders)

    for op, x in ops:
        if op in ("insert", "insert12") and de.free_slot() is not None:
            pages = store.resident_prefix(keys, de.name)
            if op == "insert":
                n = min(len(pages), 2)
                st = KC.split_paged_state(donor["st16"], n, BS)
                r = Request(rid=next(rid), arrival=0.0,
                            prompt=donor["prompt"], max_new_tokens=40)
                slot = de.insert(r, st, donor["tok16"],
                                 shared_pages=pages[:n] or None)
                store.register_pages(keys, de.name,
                                     de.slot_pages(slot)[:len(keys)])
            elif len(pages) == 2:
                # full bind of a 12-token sibling: its next write lands
                # mid-way into a shared page -> the step COW-forks it
                st = KC.split_paged_state(donor["st12"], 2, BS)
                r = Request(rid=next(rid), arrival=0.0,
                            prompt=donor["prompt"][:12], max_new_tokens=40)
                de.insert(r, st, donor["tok12"], shared_pages=pages)
        elif op == "step" and de.active:
            de.step()
        elif op in ("abort", "extract"):
            slots = [i for i, s in enumerate(de.slots) if s is not None]
            if slots:
                slot = slots[x % len(slots)]
                if op == "abort":
                    de.release_slot(slot)
                else:
                    de.extract_slot(slot)
        elif op == "drain":
            de.drain()
        elif op == "reclaim":
            store.reclaim_pool(de.name, 1)
        check()

    de.drain()
    check()
    store.detach_pool(de.name)      # teardown: store lets go of its holds
    de.pool.check(holders=[])
    assert len(de._free) == TINY_ECFG.max_batch * de._nb_slot


if HAVE_HYPOTHESIS:
    _ENGINE_OPS = hst.lists(
        hst.tuples(hst.sampled_from(_ENGINE_OP_NAMES),
                   hst.integers(0, 5)),
        max_size=12)

    @settings(max_examples=10, deadline=None)
    @given(ops=_ENGINE_OPS)
    def test_engine_refcount_invariants_random_ops(donor, tiny_params, ops):
        _run_engine_machine(donor, tiny_params, ops)


@pytest.mark.parametrize("seed", range(6))
def test_engine_refcount_invariants_seeded(donor, tiny_params, seed):
    rng = np.random.default_rng(100 + seed)
    ops = [(str(rng.choice(_ENGINE_OP_NAMES)), int(rng.integers(6)))
           for _ in range(12)]
    _run_engine_machine(donor, tiny_params, ops)


# ---------------------------------------------------------------------------
# Exactness: shared-prefix decode == recompute-from-token-0
# ---------------------------------------------------------------------------

def test_shared_bind_bit_exact_and_zero_extra_pages(tiny_params,
                                                    greedy_reference):
    """Two requests with an identical 2-block prompt: the second binds the
    first's registered pages by reference — zero additional prefix pages
    in HBM (2x fewer than the copy path) and both token streams equal the
    monolithic recompute."""
    pe = PrefillEngine(TINY, tiny_params, TINY_ECFG, None)
    store = GlobalKVStore(block_size=BS)
    de = DecodeEngine(TINY, tiny_params, TINY_ECFG, name="dshare")
    de.attach_store(store)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, TINY.vocab_size, 16, dtype=np.int32)
    keys = chain_hashes(prompt, BS)
    store.insert(prompt, ["x"] * len(keys), nbytes_per_block=1024)

    r1 = Request(rid=0, arrival=0.0, prompt=prompt, max_new_tokens=6)
    st1, lg1 = pe.run(r1)
    s1 = de.insert(r1, st1, int(jnp.argmax(lg1)))
    store.register_pages(keys, de.name, de.slot_pages(s1)[:len(keys)])
    used_one = de.pool.used

    r2 = Request(rid=1, arrival=0.0, prompt=prompt.copy(),
                 max_new_tokens=6)
    st2, lg2 = pe.run(r2)
    pages = store.resident_prefix(keys, de.name)
    assert pages == de.slot_pages(s1)[:2]
    st2 = KC.split_paged_state(st2, len(pages), BS)
    de.insert(r2, st2, int(jnp.argmax(lg2)), shared_pages=pages)
    assert de.pages_shared == 2
    assert de.pool.used == used_one       # the bind allocated NO pages

    while de.active:
        de.step()
    ref = greedy_reference(TINY, tiny_params, prompt, 6)
    assert r1.generated == ref
    assert r2.generated == ref
    store.detach_pool(de.name)
    de.pool.check(holders=[])


def test_cow_divergence_mid_block_bit_exact(tiny_params, greedy_reference):
    """A 12-token request fully binds BOTH pages of an active 16-token
    donor (its prompt is a strict prefix): its first decode write lands
    mid-way into a shared page, forcing a copy-on-write fork.  The stale
    future-position entries in the bound page are masked by position, so
    the forked stream AND the donor both stay bit-identical to their
    monolithic recomputes."""
    pe = PrefillEngine(TINY, tiny_params, TINY_ECFG, None)
    de = DecodeEngine(TINY, tiny_params, TINY_ECFG, name="dcow")
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, TINY.vocab_size, 16, dtype=np.int32)

    r1 = Request(rid=0, arrival=0.0, prompt=prompt, max_new_tokens=6)
    st1, lg1 = pe.run(r1)
    s1 = de.insert(r1, st1, int(jnp.argmax(lg1)))
    pages = de.slot_pages(s1)[:2]

    r2 = Request(rid=1, arrival=0.0, prompt=prompt[:12], max_new_tokens=6)
    st2, lg2 = pe.run(r2)
    st2 = KC.split_paged_state(st2, 2, BS)    # head-split past both pages
    assert int(st2["n_blocks"]) == 0
    s2 = de.insert(r2, st2, int(jnp.argmax(lg2)), shared_pages=pages)

    de.step()
    assert de.cow_forks >= 1                   # the divergence fork fired
    assert de.slot_pages(s2)[0] == pages[0]    # untouched head still shared
    assert de.slot_pages(s2)[1] != pages[1]    # forked page is private
    while de.active:
        de.step()
    assert r1.generated == greedy_reference(TINY, tiny_params, prompt, 6)
    assert r2.generated == greedy_reference(TINY, tiny_params,
                                            prompt[:12], 6)
    de.pool.check(holders=[])
    assert len(de._free) == TINY_ECFG.max_batch * de._nb_slot


def test_move_span_with_shared_prefix_in_flight(tiny_params,
                                                greedy_reference):
    """Live §4.1 span move while two pipeline slots share prefix pages on
    every stage: the move gathers the shared content, re-adopts it
    unshared, and neither token stream is perturbed."""
    pe = PrefillEngine(TINY, tiny_params, TINY_ECFG, None)
    pipe = DecodePipeline(TINY, tiny_params, TINY_ECFG,
                          even_spans(TINY.n_layers, 2))
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, TINY.vocab_size, 16, dtype=np.int32)

    r1 = Request(rid=0, arrival=0.0, prompt=prompt, max_new_tokens=8)
    st1, lg1 = pe.run(r1)
    s1 = pipe.insert(r1, st1, int(jnp.argmax(lg1)))
    pages = pipe.slot_pages(s1)[:2]            # per-stage page tuples

    r2 = Request(rid=1, arrival=0.0, prompt=prompt.copy(),
                 max_new_tokens=8)
    st2, lg2 = pe.run(r2)
    st2 = KC.split_paged_state(st2, 2, BS)
    pipe.insert(r2, st2, int(jnp.argmax(lg2)), shared_pages=pages)
    for e in pipe.engines:
        assert e.pages_shared == 2

    for _ in range(3):
        pipe.step()
    res = pipe.move_span(0, 1, 1)              # live boundary-layer move
    assert res is not None and res["layers"] == 1
    while pipe.active:
        pipe.step()

    ref = greedy_reference(TINY, tiny_params, prompt, 8)
    assert r1.generated == ref
    assert r2.generated == ref
    for e in pipe.engines:                     # every stage pool restored
        e.pool.check(holders=[])
        assert len(e._free) == TINY_ECFG.max_batch * e._nb_slot


# -- every BlockKind through the orchestrated sharing path ------------------

_KIND_CFGS = [
    pytest.param(TINY, id="attention-paged-shared"),
    pytest.param(ModelConfig(
        name="swa4", family=Family.DENSE, n_layers=4, d_model=64,
        n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
        sliding_window=16), id="sliding-window-copy-path"),
    pytest.param(ModelConfig(
        name="hyb4", family=Family.HYBRID, n_layers=4, d_model=64,
        n_heads=4, n_kv_heads=1, d_ff=128, vocab_size=128, local_window=8,
        block_pattern=(BlockKind.RGLRU, BlockKind.LOCAL_ATTENTION)),
        id="rglru-local-attn-copy-path"),
    pytest.param(ModelConfig(
        name="xl4", family=Family.SSM, n_layers=4, d_model=64,
        n_heads=4, n_kv_heads=4, d_ff=0, vocab_size=128,
        block_pattern=(BlockKind.MLSTM, BlockKind.SLSTM)),
        id="mlstm-slstm-dense-path"),
]


@pytest.mark.parametrize("cfg", _KIND_CFGS)
def test_every_blockkind_shared_prefix_exact(cfg, model_zoo,
                                             greedy_reference):
    """Prefix-skewed workload through the full orchestrator with the
    sharing-enabled store: pure-attention paged stacks bind pages by
    reference; windowed / recurrent stacks auto-fall back to the copy /
    recompute path — and EVERY stream equals the from-token-0 rollout."""
    params = model_zoo(cfg)
    reqs = generate(WorkloadConfig(
        kind="synthetic", rps=500.0, n_requests=6, vocab_size=cfg.vocab_size,
        max_new_tokens=5, prefix_share=0.9, n_prefix_groups=1, seed=11,
        prompt_len_lo=16, prompt_len_hi=32))
    orch = Orchestrator(cfg, params, OrchestratorConfig(
        hw=TPU_V5E,
        n_prefill=1, n_decode=1, migration=False, engine=TINY_ECFG))
    summary = orch.run(reqs)
    for r in reqs:
        assert r.generated == greedy_reference(
            cfg, params, r.prompt, len(r.generated)), r.rid
        assert len(r.generated) == r.max_new_tokens
    if KC.prefix_cacheable(cfg):
        assert summary["prefix_sharing"]
        assert summary["pages_bound"] > 0
    else:
        assert not summary.get("prefix_sharing", False)
    assert_pools_restored(orch)


def test_sharing_off_is_token_identical(tiny_params):
    """The A/B arms agree: the same workload through prefix_sharing=True
    and =False produces identical token streams (sharing changes bytes
    moved and pages resident, never math)."""
    outs = []
    for sharing in (True, False):
        reqs = generate(WorkloadConfig(
            kind="synthetic", rps=500.0, n_requests=6,
            vocab_size=TINY.vocab_size, max_new_tokens=5, prefix_share=0.9,
            n_prefix_groups=1, seed=13, prompt_len_lo=16, prompt_len_hi=32))
        orch = Orchestrator(TINY, tiny_params, OrchestratorConfig(
            hw=TPU_V5E,
            n_prefill=1, n_decode=1, migration=False, engine=TINY_ECFG,
            prefix_sharing=sharing))
        s = orch.run(reqs)
        outs.append({r.rid: list(r.generated) for r in reqs})
        if sharing:
            assert s["pages_bound"] > 0
            assert s["bound_bytes_saved"] > 0
        else:
            assert s["pages_bound"] == 0
    assert outs[0] == outs[1]
