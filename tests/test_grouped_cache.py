"""A cache allocated by layer kind: (c) the allocator of a pool of several
groups of layers, one of them a window's ring; (f) what reads one pool of
keys and values works on such a pool by group or refuses it by name; the
gauges and ``stats()`` by group."""

import numpy as np
import pytest

import jax.numpy as jnp

from hetu_tpu.layers import CacheSpec, GroupedCacheSpec
from hetu_tpu.layers.cache import ring_order, ring_slots
from hetu_tpu.serve import ServingEngine, kv_cache
from hetu_tpu.serve.kv_cache import (DoubleFree, GroupedKVCachePool,
                                     KVCachePool, OutOfPages,
                                     UnsupportedCacheLayout, make_pool)

PAGE, WINDOW, RING = 4, 8, 3


def spec(window_layers=4, full_layers=1, heads=2, dim=16):
    return GroupedCacheSpec((
        CacheSpec.kv(window_layers, heads, dim, name="window",
                     window=WINDOW, query_heads=2 * heads),
        CacheSpec.kv(full_layers, heads, dim, name="full",
                     query_heads=2 * heads)))


def pool(slots=3, max_seq_len=64, **kw):
    return make_pool(spec(), num_slots=slots, page_size=PAGE,
                     max_seq_len=max_seq_len, **kw)


# ------------------------------------------------------- (c) the allocator

@pytest.mark.parametrize("tokens", [1, 4, 8, 9, 12, 13, 24, 40, 64])
def test_a_window_groups_sequence_never_exceeds_its_ring(tokens):
    p = pool()
    p.alloc(7, 1)
    for n in range(1, tokens + 1):
        p.ensure(7, n)
        held = p.table(7).tables
        assert len(held["window"].pages) == min(-(-n // PAGE), RING)
        assert len(held["full"].pages) == -(-n // PAGE)
    stats = p.stats()
    assert stats["groups"]["window"]["pages_overwritten"] == max(
        0, -(-tokens // PAGE) - RING)
    assert stats["groups"]["full"]["pages_overwritten"] == 0
    p.free(7)
    assert p.free_pages == p.num_pages - 1 and p.stats()["sequences"] == 0


def test_a_prompt_of_several_windows_takes_one_ring():
    p = pool()
    pt = p.alloc(1, 40)
    assert len(pt.tables["window"].pages) == RING
    assert len(pt.tables["full"].pages) == 10
    assert len(pt.pages) == 13 and p.pages_needed(40) == 13
    assert p.needed_by_group(40) == [3, 10]
    pt.length = 40
    assert [t.length for t in pt.tables.values()] == [40, 40]


def test_the_default_pool_holds_every_slots_whole_allocation():
    """The cell's arithmetic at its own counts (tiny heads, so that nothing
    large is built): 64 x 17 and 64 x 108 pages and a scratch page each."""
    p = make_pool(GroupedCacheSpec((
        CacheSpec.kv(7, 1, 8, jnp.bfloat16, name="window", window=2048,
                     query_heads=8),
        CacheSpec.kv(2, 1, 8, jnp.bfloat16, name="full", query_heads=8))),
        num_slots=64, page_size=128, max_seq_len=13824)
    assert {n: g.num_pages for n, g in p.groups.items()} == {
        "window": 1089, "full": 6913}
    assert {n: g.pages_per_seq for n, g in p.groups.items()} == {
        "window": 17, "full": 108}
    # at the published heads: 2,048 bytes a token a layer
    real = CacheSpec.kv(7, 4, 128, jnp.bfloat16, window=2048)
    assert real.bytes_per_token == 2048
    assert 1089 * 128 * real.token_bytes == 1_998_323_712       # 2.00 GB
    assert 6913 * 128 * 2 * 2048 == 3_624_402_944               # 3.62 GB
    assert p.nbytes == (1089 * 7 + 6913 * 2) * 128 * 2 * 8 * 2
    assert p.num_pages - 1 == 64 * (17 + 108) == p.free_pages


def test_stats_balance_by_group_and_pages_return():
    p = pool()
    p.alloc(1, 5, owner="a")
    p.alloc(2, 30, owner="b")
    s = p.stats()
    assert s["sequences"] == 2 and s["allocs"] == 2 and s["frees"] == 0
    assert s["groups"]["window"]["pages_private"] == 2 + RING
    assert s["groups"]["full"]["pages_private"] == 2 + 8
    assert s["pages_total"] == p.num_pages - 1 == 3 * (RING + 16)
    assert s["pages_free"] + s["pages_private"] == s["pages_total"]
    assert s["pages_by_tenant"] == {"a": 4, "b": RING + 8}
    assert s["pages_by_class"]["scratch"] == 2
    assert sum(s["pages_by_class"].values()) == sum(
        g.num_pages for g in p.groups.values())
    assert p.utilization()["groups"]["window"]["pages_used"] == 2 + RING
    p.free(1)
    with pytest.raises(DoubleFree):
        p.free(1)
    p.free(2)
    assert p.stats()["pages_free"] == p.num_pages - 1
    assert p.cache_stats()["pool_bytes"] == p.nbytes == sum(
        g.nbytes for g in p.groups.values())


@pytest.mark.parametrize("short", ["window", "full"])
def test_admission_refuses_when_either_group_is_short(short):
    pages = {"window": 1 + 2 * RING, "full": 1 + 32}
    pages[short] = 1 + (RING if short == "window" else 10)
    p = pool(num_pages=pages)
    p.alloc(1, 40)                      # all of the short group
    assert not p.can_admit(4)
    free = p.free_by_group()
    with pytest.raises(OutOfPages, match=short):
        p.alloc(2, 4)
    assert p.free_by_group() == free and p.live_sequences == 1
    assert 2 not in p.groups["window"]._tables       # nothing was taken
    p.free(1)
    assert p.can_admit(4)


def test_growth_that_one_group_cannot_cover_takes_nothing():
    p = pool(num_pages={"window": 1 + RING, "full": 1 + 2})
    p.alloc(1, 8)
    free = p.free_by_group()
    with pytest.raises(OutOfPages, match="full"):
        p.ensure(1, 9)
    assert p.free_by_group() == free


def test_a_grouped_spec_wants_its_page_counts_by_name():
    with pytest.raises(ValueError, match="mapping"):
        pool(num_pages=100)
    with pytest.raises(ValueError, match="distinct names"):
        GroupedCacheSpec((CacheSpec.kv(1, 1, 8), CacheSpec.kv(1, 1, 8)))
    with pytest.raises(ValueError, match="multiple of page_size"):
        KVCachePool(spec=CacheSpec.kv(1, 1, 8, window=6), num_pages=4,
                    page_size=4, max_seq_len=16)


def test_a_ring_in_the_order_of_its_positions():
    tables = jnp.asarray([[5, 6, 7], [1, 2, 0]], jnp.int32)
    ordered, first = ring_order(tables, jnp.asarray([23, 6]), PAGE)
    # 23 tokens: logical pages 3, 4, 5 live in slots 0, 1, 2; 6 tokens:
    # pages 0 and 1, the third entry the scratch padding
    assert np.asarray(ordered).tolist() == [[5, 6, 7], [1, 2, 0]]
    assert np.asarray(first).tolist() == [12, 0]
    ordered, first = ring_order(tables, jnp.asarray([17, 13]), PAGE)
    assert np.asarray(ordered).tolist() == [[7, 5, 6], [2, 0, 1]]
    assert np.asarray(first).tolist() == [8, 4]
    assert np.asarray(ring_slots(jnp.asarray([4]), 3)).tolist() == [[1, 2, 0]]


def test_one_group_with_a_window_is_a_pool_of_its_own():
    p = KVCachePool(spec=CacheSpec.kv(2, 2, 8, window=WINDOW), num_pages=7,
                    page_size=PAGE, max_seq_len=32)
    p.alloc(1, 20)
    assert len(p.table(1).pages) == RING and p.pages_per_seq == RING
    assert p.gather_indices([1, None]).shape == (2, RING)
    assert p.cache_stats()["window"] == WINDOW


# ----------------- (f) what reads one pool of keys and values, by name

def _tiny_engine(**kw):
    from test_afmoe import ENGINE, SEED, TINY
    from benchmark.adapters import afmoe as adapter
    return ServingEngine(adapter.build_model(TINY, SEED), **{**ENGINE, **kw})


@pytest.mark.parametrize("call,what", [
    (lambda p: p.k, "pool.k"), (lambda p: p.v, "pool.v"),
    (lambda p: p.export_pages(1), "export_pages"),
    (lambda p: p.import_pages(None), "import_pages"),
    (lambda p: p.copy_on_write(1, 0), "copy_on_write"),
    (lambda p: p.retain(1), "retain"),
    (lambda p: p.alloc(2, 8, shared_pages=[1]), "shared_pages"),
    (lambda p: p.require_kv("a feature"), "a feature")],
    ids=["k", "v", "export", "import", "cow", "retain", "shared", "named"])
def test_a_grouped_pool_refuses_by_name(call, what):
    p = pool()
    p.alloc(1, 8)
    with pytest.raises(UnsupportedCacheLayout, match=what):
        call(p)
    assert p.stats()["sequences"] == 1           # and nothing was changed


@pytest.mark.parametrize("kind", ["window", "head-major"])
def test_a_ring_or_a_head_major_pool_refuses_by_name(kind):
    sp = (CacheSpec.kv(1, 2, 8, window=WINDOW) if kind == "window"
          else CacheSpec.kv(1, 2, 8, query_heads=4))
    p = KVCachePool(spec=sp, num_pages=9, page_size=PAGE, max_seq_len=32)
    p.alloc(1, 8)
    assert not sp.plain_kv and sp.holds_kv
    calls = [lambda: p.export_pages(1), lambda: p.k]
    if kind == "window":    # a ring's slots are no prefix's pages
        calls += [lambda: p.copy_on_write(1, 0),
                  lambda: p.alloc(2, 8, shared_pages=[1])]
    for call in calls:
        with pytest.raises(UnsupportedCacheLayout):
            call()
    from hetu_tpu.serve.fleet.prefix import PrefixSharer
    with pytest.raises(UnsupportedCacheLayout, match="prefix sharing"):
        PrefixSharer(p)


@pytest.mark.parametrize("kw,what", [
    ({"paged_decode": False}, "gather path"),
    ({"role": "prefill"}, "page migration"),
    ({"role": "decode"}, "page migration"),
    ({"prefix_sharing": True}, "prefix sharing"),
    ({"draft_model": object()}, "speculative decoding")],
    ids=["gather", "prefill-role", "decode-role", "prefix", "draft"])
def test_the_engine_refuses_each_fleet_feature_by_name(kw, what):
    with pytest.raises(UnsupportedCacheLayout, match=what):
        _tiny_engine(**kw)


def test_defrag_moves_each_groups_pages_and_changes_no_stream():
    from test_afmoe import prompts

    def streams(**kw):
        eng = _tiny_engine(**kw)
        hs = [eng.submit(p, 10) for p in prompts((5, 23, 40, 9, 30))]
        eng.run_until_idle()
        return [h.tokens for h in hs]

    assert streams(defrag_every=1) == streams()
    p = pool()
    p.alloc(1, 8), p.alloc(2, 30), p.free(1)
    assert p.defrag() == RING + 8
    assert p.table(2).tables["window"].pages == [1, 2, 3]
    p.stats()                                        # invariants hold


def test_a_hung_engine_evacuates_without_a_record():
    """The failover monitor re-homes by re-prefill what it cannot export."""
    from test_afmoe import prompts
    eng = _tiny_engine()
    h = eng.submit(prompts((30,))[0], 6)
    eng.step()
    eng.hang(5)
    ((req, record, handle, tl),) = eng.evacuate()
    assert record is None and handle is h
    assert eng.pool.stats()["sequences"] == 0
    other = _tiny_engine()
    assert other.accept_failover(req, handle, tl) is None
    other.run_until_idle()
    assert h.status == "completed" and len(h.tokens) == 6


def test_the_memory_ledger_and_the_donation_audit_read_the_groups():
    from hetu_tpu.exec.profiler import audit_serving_donation
    from hetu_tpu.obs import memledger
    eng = _tiny_engine()
    for g in eng.pool.groups.values():
        assert memledger._pool_page_bytes(g) * g.num_pages == g.nbytes
    with memledger.use(memledger.MemoryLedger()) as ledger:
        h = eng.submit(np.arange(30), 4)
        eng.run_until_idle()
        snap = ledger.snapshot()
    assert h.status == "completed"
    assert snap["components"]["kv_pool"] == eng.pool.nbytes
    assert len(snap["kv_pools"]) == 2            # each group a pool
    report = audit_serving_donation(eng)
    assert report["pool_bytes"] == eng.pool.nbytes
    for name, prog in report["programs"].items():
        assert prog["unusable"] == [], name
        assert prog["aliased_bytes"] >= eng.pool.nbytes, name


def test_gauges_and_stats_by_group():
    from hetu_tpu.obs import get_registry
    eng = _tiny_engine()
    h = eng.submit(np.arange(40), 12)
    eng.step()
    eng.step()
    text = get_registry().render_prometheus()
    assert 'hetu_serve_cache_pages{group="window",state="held"} 3' in text
    assert 'hetu_serve_cache_pages{group="full",state="held"} 11' in text
    assert 'hetu_serve_cache_pages{group="full",state="free"} 37' in text
    eng.run_until_idle()
    assert h.status == "completed"
    text = get_registry().render_prometheus()
    assert 'hetu_serve_cache_pages{group="window",state="held"} 0' in text
    assert ('hetu_serve_cache_pages_overwritten_total{group="window"}'
            in text)
    stats = eng.stats()
    assert stats["pool"]["groups"]["full"]["pages_used"] == 0
    assert stats["cache"]["groups"]["window"]["window"] == 8
    assert eng.pool.stats()["groups"]["window"]["pages_overwritten"] == 3
    by_model = stats["metrics"]["hetu_serve_cache_token_bytes"]
    assert by_model == (4 + 1) * 2 * 2 * 16 * 4


def test_paged_decode_steps_by_group():
    """The window group's rings of 3 entries hold 2 steps a row, the full
    group's tables of 16 entries 8: a request of 40 to 51 tokens walks
    both of its ring's steps (the window's edge in its first entry, at 4
    positions into it at most) and 5 to 7 of its table's, the idle slots
    one each, so every full layer skips and the window layers skip for
    the idle slots alone."""
    eng = _tiny_engine()
    h = eng.submit(np.arange(40), 12)
    eng.run_until_idle()
    assert h.status == "completed"
    steps = eng.stats()["paged_decode"]["steps"]
    n = sum(eng.stats()["lookahead"]["steps"].values())
    slots, layers = eng.batcher.num_slots, {"window": 4, "full": 1}
    held = {"window": 2, "full": 8}
    for g in ("window", "full"):
        assert (steps[g]["walked"] + steps[g]["skipped"]
                == n * slots * held[g] * layers[g])
    assert steps["window"]["walked"] == n * (2 + slots - 1) * 4
    assert n * (5 + slots - 1) <= steps["full"]["walked"] <= n * (
        7 + slots - 1)
