"""Global KV Cache Store: prefix matching, tiers, eviction, pipeline."""
import numpy as np
import pytest

from repro.core.kvstore import GlobalKVStore, TierSpec, chain_hashes
from repro.core.pipeline import PipelineModel, paper_example


def test_chain_hash_prefix_property():
    a = chain_hashes([1, 2, 3, 4, 5, 6, 7, 8], 4)
    b = chain_hashes([1, 2, 3, 4, 9, 9, 9, 9], 4)
    assert a[0] == b[0] and a[1] != b[1]


def test_match_longest_prefix():
    st = GlobalKVStore(block_size=4)
    toks = list(range(16))
    keys = chain_hashes(toks, 4)
    st.insert(toks, ["p0", "p1", "p2", "p3"], nbytes_per_block=100)
    n, matched = st.match(toks)
    assert n == 16 and matched == keys
    n, matched = st.match(toks[:8] + [99] * 8)
    assert n == 8
    n, matched = st.match([99] + toks)
    assert n == 0


def test_fetch_promotes_and_counts_latency():
    st = GlobalKVStore(block_size=4, tiers=[
        TierSpec("hbm", 250, 100.0), TierSpec("host", 10_000, 1.0)])
    st.insert(list(range(8)), ["a", "b"], nbytes_per_block=100)
    # third block overflows hbm -> first entry demoted to host
    st.insert(list(range(12)), ["a", "b", "c"], nbytes_per_block=100)
    tiers = [e.tier for e in st._entries.values()]
    assert 1 in tiers
    _, keys = st.match(list(range(12)))
    payloads, lat = st.fetch(keys)
    assert payloads == ["a", "b", "c"]
    assert lat > 0
    assert all(e.tier == 0 or e.nbytes == 100 for e in st._entries.values())


def test_overlapped_fetch_rejects_malformed_request_state():
    """A request-state payload (it carries "groups") that the per-layer
    schedule cannot read is an error, not an opaque block billed
    serially; opaque payloads still bill serially."""
    st = GlobalKVStore(block_size=4)
    toks = list(range(8))
    layer = {"k": np.zeros((2, 4, 1, 8), np.float32)}
    st.insert(toks, [{"groups": (layer,)}, "opaque"], nbytes_per_block=100)
    _, keys = st.match(toks)
    with pytest.raises(KeyError, match="rem"):
        st.fetch(keys[:1], t_layer_compute=1e-3)
    payloads, lat = st.fetch(keys[1:], t_layer_compute=1e-3)
    assert payloads == ["opaque"] and lat > 0


def test_eviction_cascade_drops_from_last_tier():
    st = GlobalKVStore(block_size=4, tiers=[
        TierSpec("hbm", 200, 100.0), TierSpec("host", 200, 1.0)])
    for i in range(6):
        st.insert([i * 10 + j for j in range(4)], [f"p{i}"],
                  nbytes_per_block=100)
    assert st.stats.evictions > 0
    assert st.used_bytes() <= 400


def test_hit_rate_accounting():
    st = GlobalKVStore(block_size=4)
    toks = list(range(8))
    st.match(toks)                 # miss
    st.insert(toks, ["a", "b"], nbytes_per_block=10)
    st.match(toks)                 # hit
    assert 0.0 < st.stats.hit_rate < 1.0


# -- layer-wise pipeline (Eq. 12–17) ----------------------------------------

def test_paper_example_numbers():
    """§4.2 worked example: T_F,layer ≈ 4.22 ms, T_KV ≈ 0.082 ms."""
    pm = paper_example()
    assert pm.t_fwd_layer == pytest.approx(4.22e-3, rel=0.01)
    assert pm.t_kv_layer == pytest.approx(0.082e-3, rel=0.03)
    assert pm.fully_hidden()
    # overlap hides essentially all transfer: residual << serial overhead
    assert pm.residual_stall() < 3 * pm.t_kv_layer
    assert pm.serial_time() > pm.overlapped_time()


def test_pipeline_not_hidden_when_bandwidth_starved():
    pm = PipelineModel(n_layers=32, t_fwd_layer=1e-3, t_kv_layer=5e-3)
    assert not pm.fully_hidden()
    assert pm.residual_stall() > 0


def test_timeline_channels_do_not_overlap_within_channel():
    pm = paper_example()
    ev = pm.timeline()
    for chan in ("HtoD", "GPU", "DtoH"):
        spans = sorted((s, e) for c, _, s, e in ev if c == chan)
        for (s1, e1), (s2, e2) in zip(spans, spans[1:]):
            assert s2 >= e1 - 1e-12


# -- tentative probes must not perturb LRU order (regression) ---------------

def test_tentative_match_does_not_touch_lru():
    """``match(record_stats=False)`` is a *tentative* probe (batch
    planning runs one per candidate per step) — it must not bump the
    matched entries' recency, or planning probes would pin hot-looking
    prefixes and starve the real LRU order."""
    st = GlobalKVStore(block_size=4, tiers=[TierSpec("hbm", 200, 100.0)])
    old, new = list(range(4)), list(range(10, 14))
    st.insert(old, ["old"], nbytes_per_block=100)
    st.insert(new, ["new"], nbytes_per_block=100)
    for _ in range(5):                      # tentative probes on the LRU key
        st.match(old, record_stats=False)
    # a third insert overflows the single 2-block tier: the probed-but-
    # untouched ``old`` entry must still be the eviction victim
    st.insert(list(range(20, 24)), ["k3"], nbytes_per_block=100)
    assert st.match(old, record_stats=False)[0] == 0
    assert st.match(new, record_stats=False)[0] == 4


def test_match_touch_flag_overrides_record_stats():
    st = GlobalKVStore(block_size=4, tiers=[TierSpec("hbm", 200, 100.0)])
    old, new = list(range(4)), list(range(10, 14))
    st.insert(old, ["old"], nbytes_per_block=100)
    st.insert(new, ["new"], nbytes_per_block=100)
    st.match(old, record_stats=False, touch=True)   # explicit recency bump
    st.insert(list(range(20, 24)), ["k3"], nbytes_per_block=100)
    assert st.match(old, record_stats=False)[0] == 4    # survived
    assert st.match(new, record_stats=False)[0] == 0    # evicted instead


# -- zero-copy page residency ------------------------------------------------

class _FakePool:
    """Minimal pool contract (ref/unref/materialize) over a real
    ``BlockPool`` so the store-side residency logic is testable without
    an engine."""

    def __init__(self, n_pages=8):
        from repro.models.kvcache import BlockPool
        self.pool = BlockPool(n_pages)
        self.materialized = []

    def ref_pages(self, pages):
        self.pool.ref(pages)

    def unref_pages(self, pages):
        return self.pool.unref(list(pages))

    def materialize(self, page):
        self.materialized.append(int(page))
        return {"payload-of-page": int(page)}


def _resident_store():
    st = GlobalKVStore(block_size=4, tiers=[
        TierSpec("hbm", 1000, 100.0), TierSpec("host", 10_000, 1.0)])
    toks = list(range(12))
    keys = chain_hashes(toks, 4)
    st.insert(toks, [f"p{i}" for i in range(3)], nbytes_per_block=100)
    fp = _FakePool()
    st.attach_pool("d0", fp)
    slot = fp.pool.alloc(3)                 # the decode slot's own pages
    assert st.register_pages(keys, "d0", slot) == 3
    return st, fp, keys, slot, toks


def test_register_pages_converts_and_frees_tier_bytes():
    st, fp, keys, slot, toks = _resident_store()
    assert st.used_bytes(0) == 0            # payload copies dropped
    assert all(int(fp.pool.refcount[p]) == 2 for p in slot)  # slot + store
    assert st.stats.registered_blocks == 3
    assert st.pool_pages("d0") == dict(zip(keys, slot))
    # double registration is a no-op (first wins)
    assert st.register_pages(keys, "d0", slot) == 0
    # the bind lookup hands back the physical pages, longest-prefix style
    assert st.resident_prefix(keys, "d0") == slot
    assert st.resident_prefix(keys, "other") == []
    assert st.stats.bound_blocks == 3
    # match still resolves and fetch materializes out of the live pool
    n, mk = st.match(toks)
    assert n == 12
    payloads, _ = st.fetch(mk)
    assert [p["payload-of-page"] for p in payloads] == slot


def test_reclaim_pool_counts_only_freed_pages():
    st, fp, keys, slot, _ = _resident_store()
    # every page still held by the slot: demoting the store's holds frees
    # nothing, so reclaim must scan past them and report 0
    assert st.reclaim_pool("d0", 1) == 0
    assert st.stats.demotions == 3
    assert all(int(fp.pool.refcount[p]) == 1 for p in slot)
    assert all(e.pool is None and e.tier == 1 for e in st._entries.values())
    assert st.demote_latency_s > 0
    # demoted entries still serve hits (payload form, backing tier)
    assert st.match(list(range(12)), record_stats=False)[0] == 12


def test_reclaim_pool_frees_lru_first_after_release():
    st, fp, keys, slot, _ = _resident_store()
    fp.pool.unref(slot)                     # slot released; store-only holds
    st.resident_prefix(keys[:1], "d0")      # touch key0 -> key1 is now LRU?
    freed = st.reclaim_pool("d0", 1)
    assert freed == 1
    assert len(fp.pool.free_list) == fp.pool.n_pages - fp.pool.n_reserved - 2
    assert st.reclaim_pool("d0", 8) == 2    # rest demote + free
    fp.pool.check()


# -- capacity invariants: no tier ever over-fills ---------------------------

def _assert_within_capacity(st):
    for i, spec in enumerate(st.tiers):
        assert st.used_bytes(i) <= spec.capacity_bytes, \
            f"tier {i} ({spec.name}) over-filled"


def test_make_room_demotes_residents_before_overfilling():
    """A tier 0 holding only pool-resident entries has no payload victims.
    An insert that cannot fit must shed the page holds (demote residents
    to the backing tier) before giving up — and then drop the block
    rather than silently exceeding the byte budget (the historical
    over-fill bug)."""
    st = GlobalKVStore(block_size=4, tiers=[
        TierSpec("hbm", 250, 100.0), TierSpec("host", 10_000, 1.0)])
    toks = list(range(8))
    keys = chain_hashes(toks, 4)
    st.insert(toks, ["a", "b"], nbytes_per_block=100)
    fp = _FakePool()
    st.attach_pool("d0", fp)
    slot = fp.pool.alloc(2)
    assert st.register_pages(keys, "d0", slot) == 2
    assert st.used_bytes(0) == 0            # page-resident, no tier bytes
    fp.pool.unref(slot)                     # store holds only
    # a 300 B block exceeds hbm capacity: no payload victims exist, so
    # _make_room demotes both residents (page holds released), then
    # reports no-room and the block is dropped — never over-filled
    st.insert(list(range(20, 24)), ["x"], nbytes_per_block=300)
    _assert_within_capacity(st)
    assert st.stats.demotions == 2          # residents were shed, not ignored
    assert st.match(list(range(20, 24)), record_stats=False)[0] == 0
    # the demoted residents survive in payload form on the host tier
    assert all(e.pool is None and e.tier == 1 for e in st._entries.values())
    assert st.match(toks, record_stats=False)[0] == 8
    fp.pool.check(holders=[])               # every page hold released


def test_insert_never_exceeds_capacity_under_churn():
    """Randomized churn over tiny tiers: the per-tier byte ledger must
    never exceed capacity after any insert, and inserts too large even
    for an empty tier are dropped, not jammed in."""
    rng = np.random.default_rng(0)
    st = GlobalKVStore(block_size=4, tiers=[
        TierSpec("hbm", 300, 100.0), TierSpec("host", 500, 1.0)])
    for it in range(60):
        n_blocks = int(rng.integers(1, 5))
        toks = [int(t) for t in
                rng.integers(0, 50, size=(n_blocks * 4,))]
        st.insert(toks, [f"v{it}-{j}" for j in range(n_blocks)],
                  nbytes_per_block=int(rng.integers(50, 200)))
        _assert_within_capacity(st)
    assert st.stats.evictions > 0           # churn really overflowed


def test_oversized_insert_dropped_not_overfilled():
    st = GlobalKVStore(block_size=4, tiers=[TierSpec("hbm", 100, 100.0)])
    st.insert(list(range(4)), ["big"], nbytes_per_block=1000)
    _assert_within_capacity(st)
    assert st.match(list(range(4)), record_stats=False)[0] == 0


def test_swap_billing_counts_bytes_and_latency():
    st = GlobalKVStore(block_size=4, tiers=[
        TierSpec("hbm", 1000, 100.0), TierSpec("host", 10_000, 1.0)])
    t_out = st.swap_out(1_000_000)
    t_in = st.swap_in(1_000_000)
    assert t_out == pytest.approx(1_000_000 / 1e9)  # host-tier bw (1 GB/s)
    assert t_in == t_out
    assert st.stats.swaps_out == 1 and st.stats.swaps_in == 1
    assert st.stats.bytes_swapped == 1_000_000
    assert st.swap_latency_s == pytest.approx(t_out + t_in)


def test_detach_pool_demotes_everything():
    st, fp, keys, slot, _ = _resident_store()
    fp.pool.unref(slot)
    assert st.detach_pool("d0") == 3
    fp.pool.check(holders=[])               # every hold released
    assert st.pool_pages("d0") == {}
    assert all(e.pool is None for e in st._entries.values())
    assert st.detach_pool("d0") == 0        # idempotent
    # entries survive as normal payload blocks on the backing tier
    assert st.match(list(range(12)), record_stats=False)[0] == 12
