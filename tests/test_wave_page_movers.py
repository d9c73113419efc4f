"""A paged prefill wave moves its store pages in whole programs.

A store hit's page-resident prefix is copied from the decode pool into the
wave's pool in one program per row (``engine._pool_copy``), and a row's
newly completed blocks are cut into store payloads in one program
(``engine._page_payloads``).  These tests hold that path to the per-block
one it replaces — every block copied out of the pool as a payload, stacked
and scattered, and published one page at a time — bit for bit, and show
that the two programs compile once per pool shape whatever the hit length.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.analytical import TPU_V5E
from repro.core.kvstore import GlobalKVStore, chain_hashes
from repro.models import kvcache as KC
from repro.models.config import Family, ModelConfig
from repro.serving import engine as E
from repro.serving.engine import DecodeEngine, EngineConfig, PrefillEngine
from repro.serving.request import Request

CFG = ModelConfig(name="wave-pages", family=Family.DENSE, n_layers=2,
                  d_model=32, n_heads=2, n_kv_heads=2, d_ff=64,
                  vocab_size=64)
BS = 16
# 4 pages a row: the longest hit a row can hold is 3 blocks (one token is
# always computed), which is the full-table hit below
ECFG = EngineConfig(max_len=64, max_batch=4, block_size=BS, hw=TPU_V5E)
PREFIX = np.arange(3 * BS, dtype=np.int32) % 60 + 1


@pytest.fixture(scope="module")
def params(model_zoo):
    return model_zoo(CFG)


def _tokens(seed: int, n: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, 64, n, dtype=np.int32)


def _fleet(params, ecfg=ECFG, prefix=PREFIX, demote=()):
    """A store whose entries for ``prefix``'s blocks sit in a decode pool
    (prefilled, handed off and registered, as the orchestrator does; one
    decode step has written its idle rows' junk into scratch page 0);
    entries at ``demote`` are then demoted to the host tier."""
    store = GlobalKVStore(block_size=BS)
    de = DecodeEngine(CFG, params, ecfg, name="d0")
    de.attach_store(store)
    pe = PrefillEngine(CFG, params, ecfg, store)
    req = Request(rid=1000, arrival=0.0,
                  prompt=np.concatenate([prefix, _tokens(99, 5)]),
                  max_new_tokens=4)
    st, lg = pe.run(req)
    slot = de.insert(req, st, int(jnp.argmax(lg)))
    de.step()
    keys = chain_hashes(req.prompt, BS)
    n = len(prefix) // BS
    assert store.register_pages(keys[:n], de.name,
                                de.slot_pages(slot)[:n]) == n
    for j in demote:
        store._demote_resident(keys[j], store._entries[keys[j]])
    return store, de, pe


def _record_forwards(pe):
    """Keep a host copy of every wave forward's inputs (cache included:
    the wave pool, block tables and lengths)."""
    seen = []

    def rec(fn):
        def call(params, x, *, cache, frames, logits_at):
            seen.append(jax.tree.map(np.asarray, {
                "x": x, "cache": cache, "logits_at": logits_at}))
            return fn(params, x, cache=cache, frames=frames,
                      logits_at=logits_at)
        return call

    pe._prefill = rec(pe._prefill)
    pe._prefill_inc = rec(pe._prefill_inc)
    return seen


def _per_block(monkeypatch):
    """The per-block path: every hit block copied out of its pool as a
    payload (``fetch``), and every new block cut out one page at a time."""
    monkeypatch.setattr(GlobalKVStore, "fetch_pages", GlobalKVStore.fetch)
    monkeypatch.setattr(
        E, "_page_payloads",
        lambda pool, idx, *, block_size: tuple(
            KC.page_payload(pool, int(p), block_size) for p in idx))


def _serve(params, prompts, chunk, demote):
    store, de, pe = _fleet(params, demote=demote)
    pulls = []
    materialize = de.materialize
    de.materialize = lambda page: pulls.append(page) or materialize(page)
    seen = _record_forwards(pe)
    reqs = [Request(rid=i, arrival=0.0, prompt=p, max_new_tokens=2)
            for i, p in enumerate(prompts)]
    waves, out = [], {}
    for wave in pe.prefill_waves(reqs, chunk_tokens=chunk):
        waves.append((wave["rows"], wave["resumed"], wave["hit"]))
        for i, st, lg in wave["done"]:
            out[i] = jax.tree.map(np.asarray, (st, lg))
    return dict(store=store, pe=pe, seen=seen, waves=waves, out=out,
                pulls=pulls, cached=[r.cached_tokens for r in reqs])


def _assert_trees_equal(a, b):
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


FULL = np.concatenate([PREFIX, _tokens(1, 15)])          # 3-block hit
ONE = np.concatenate([PREFIX[:BS], _tokens(2, 16)])      # 1-block hit
LONG = [_tokens(3, 40), _tokens(4, 40)]                  # chunked misses

# (prompts, chunk tokens, prefix blocks demoted, waves as (rows, resumed,
# hit), store entries left holding a payload)
CASES = {
    # one row holding the longest hit its table allows (nothing new to
    # publish)
    "1-row-full-table": ([FULL], None, (), [(1, 0, True)], 0),
    # two rows; the full-table hit's middle block is on the host tier, so
    # its run mixes copied pages and a payload block; the 1-block hit
    # publishes its second block
    "2-rows-host-tier-block": ([FULL, ONE], None, (1,), [(2, 0, True)], 2),
    # chunk 1 of two long misses, then a 4-row wave resuming both beside
    # a full-table and a 1-block store hit, then their last chunks; each
    # long prompt publishes a block in its miss wave and one on resuming
    "4-rows-chunk-resume": (LONG + [FULL, ONE], BS, (),
                            [(2, 0, False), (4, 2, True), (2, 2, True)], 5),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_batched_store_pages_match_per_block(params, monkeypatch, case):
    prompts, chunk, demote, waves, n_payloads = CASES[case]
    new = _serve(params, prompts, chunk, demote)
    with monkeypatch.context() as m:
        _per_block(m)
        ref = _serve(params, prompts, chunk, demote)
    assert new["waves"] == ref["waves"] == waves
    assert new["cached"] == ref["cached"]
    assert 3 * BS in new["cached"]
    # the batched path copied no page out of the decode pool; the
    # per-block path copied one per page-resident hit block
    assert new["pulls"] == [] and ref["pulls"]
    # every wave's forward saw the same pool pages, tables, lengths, inputs
    assert len(new["seen"]) == len(ref["seen"])
    for a, b in zip(new["seen"], ref["seen"]):
        _assert_trees_equal(a, b)
    # the same request states and logits came out
    assert sorted(new["out"]) == sorted(ref["out"]) \
        == list(range(len(prompts)))
    for i in new["out"]:
        _assert_trees_equal(new["out"][i], ref["out"][i])
    # the store holds the same entries, bytes and payloads, and billed the
    # fetches alike
    s_new, s_ref = new["store"], ref["store"]
    assert list(s_new._entries) == list(s_ref._entries)
    for k, e in s_new._entries.items():
        f = s_ref._entries[k]
        assert (e.nbytes, e.tier, e.pool, e.page, e.n_tokens) \
            == (f.nbytes, f.tier, f.pool, f.page, f.n_tokens)
        assert (e.payload is None) == (f.payload is None)
        if e.payload is not None:
            _assert_trees_equal(e.payload, f.payload)
    # each block held as a payload is that page of its request's final
    # state (the hit prefix's other entries stay page-resident)
    payloads = 0
    for i, p in enumerate(prompts):
        for j, k in enumerate(chain_hashes(p, BS)):
            e = s_new._entries.get(k)
            if e is not None and e.payload is not None:
                payloads += 1
                for g_e, g_s in zip(e.payload["groups"],
                                    new["out"][i][0]["groups"]):
                    for leaf in ("k", "v", "pos"):
                        np.testing.assert_array_equal(
                            np.asarray(g_e[leaf]), g_s[leaf][:, j])
    assert payloads == n_payloads
    assert [s_new.used_bytes(t) for t in range(3)] \
        == [s_ref.used_bytes(t) for t in range(3)]
    assert dataclasses.asdict(s_new.stats) == dataclasses.asdict(s_ref.stats)
    assert s_new.stats.bytes_fetched > 0
    assert new["pe"].fetch_latency_s == ref["pe"].fetch_latency_s


def test_wave_page_programs_do_not_grow_with_hit_length(params):
    """Hits of every length a row can hold (1 to 7 blocks of an 8-page
    table), each publishing the rest of a 127-token prompt (6 to 0 new
    blocks), at 1, 2 and 4 rows: the page copy and the publishing program
    compile for the first hit length of each row count and never again.
    The forward is stubbed (the cache passes through): its own shapes are
    ``test_prefill_compile_count_bounded``'s."""
    ecfg = dataclasses.replace(ECFG, max_len=128)
    nb_slot = ecfg.max_len // BS
    prefix = np.arange((nb_slot - 1) * BS, dtype=np.int32) % 60 + 1
    store, de, pe = _fleet(params, ecfg, prefix)
    pe._prefill_inc = lambda params, x, *, cache, frames, logits_at: (
        jnp.zeros((x.shape[0], CFG.vocab_size)), cache, None)
    progs = (E._pool_copy, E._page_payloads)
    rid = 0
    for rows in (1, 2, 4):
        after_first = None
        for h in range(1, nb_slot):
            reqs = []
            for r in range(rows):
                rid += 1
                reqs.append(Request(
                    rid=rid, arrival=0.0, max_new_tokens=1,
                    prompt=np.concatenate([prefix[:h * BS],
                                           _tokens(rid, 127 - h * BS)])))
            waves = [(w["rows"], w["hit"]) for w in pe.prefill_waves(reqs)]
            assert waves == [(rows, True)]
            assert all(r.cached_tokens == h * BS for r in reqs)
            if after_first is None:
                after_first = [p._cache_size() for p in progs]
        assert [p._cache_size() for p in progs] == after_first, rows
