"""(b) The paged kernel and flash with grouped KV heads and a window against
plain attention in ``numpy``, at 1, 2 and many pages, the window's edge
inside a page and on a page's edge, in the head-major pages of grouped
heads and the token-major pages of equal heads; with equal heads and no
window both give what they gave; ``layers.GroupedQueryAttention`` without
a cache."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_tpu.layers import GroupedQueryAttention
from hetu_tpu.layers.cache import ring_order
from hetu_tpu.ops.pallas.flash import flash_attention_bhsd
from hetu_tpu.ops.pallas.paged_decode import paged_decode_attention

pytestmark = pytest.mark.pallas

def _plain(q, k, v, window):
    """q [b, h, sq, d] at the LAST sq positions of k, v [b, kh, sk, d]."""
    group = q.shape[1] // k.shape[1]
    k, v = np.repeat(k, group, axis=1), np.repeat(v, group, axis=1)
    sq, sk = q.shape[2], k.shape[2]
    s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    at = (np.arange(sq)[:, None] + sk - sq) - np.arange(sk)[None, :]
    seen = at >= 0
    if window is not None:
        seen &= at < window
    s = np.where(seen, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("bhqk,bhkd->bhqd", p / p.sum(-1, keepdims=True), v)


@pytest.mark.parametrize("KH", [2, 4], ids=["grouped", "equal-heads"])
@pytest.mark.parametrize("lens,window", [
    ([1, 4, 3], None), ([5, 8, 7], None), ([40, 17, 33, 1], None),
    ([3, 4, 1], 6), ([8, 7, 5], 6), ([40, 17, 33, 9, 6, 7], 6),
    ([40, 24, 12], 8), ([23, 40], 1)],
    ids=["1page", "2pages", "many", "1page-w", "2pages-w", "many-w",
         "edge-on-a-page-edge", "window-of-one"])
def test_paged_kernel_with_grouped_heads_and_a_window(lens, window, KH):
    """One, two and many pages; the window's edge inside a page and on a
    page's edge; 4 query heads over 2 KV heads in head-major pages, and
    over 4 in the token-major pages that equal heads keep."""
    rng = np.random.default_rng(len(lens))
    H, D, page = 4, 8, 4
    head_major = KH < H
    n_pages = -(-max(lens) // page)
    P = 1 + len(lens) * n_pages
    tables = np.zeros((len(lens), n_pages), np.int32)
    shape = (P, KH, page, D) if head_major else (P, page, KH, D)
    k_pool = rng.standard_normal(shape).astype(np.float32)
    v_pool = rng.standard_normal(shape).astype(np.float32)
    k_pool[0] = v_pool[0] = np.nan           # the scratch page is poison
    q = rng.standard_normal((len(lens), H, D)).astype(np.float32)
    want = []
    for b, n in enumerate(lens):
        tables[b, :-(-n // page)] = 1 + b * n_pages + np.arange(-(-n // page))
        kk, vv = k_pool[tables[b]], v_pool[tables[b]]
        if head_major:
            kk, vv = kk.swapaxes(1, 2), vv.swapaxes(1, 2)
        kk = kk.reshape(-1, KH, D)[:n].swapaxes(0, 1)[None]
        vv = vv.reshape(-1, KH, D)[:n].swapaxes(0, 1)[None]
        want.append(_plain(q[b][None, :, None], kk, vv, window)[0, :, 0])
    got = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(tables), jnp.asarray(lens, jnp.int32), window=window,
        kv_heads=KH if head_major else None, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.stack(want), atol=2e-6)


def test_a_pool_of_another_layout_than_its_heads_say_is_refused():
    q = jnp.zeros((2, 4, 8))
    args = (jnp.zeros((2, 2), jnp.int32), jnp.ones((2,), jnp.int32))
    token_major = jnp.zeros((3, 4, 2, 8))       # (pages, page, KH, D)
    with pytest.raises(ValueError, match="wanted \\(2, 2, 8\\)"):
        paged_decode_attention(q, token_major, token_major, *args,
                               kv_heads=2, interpret=True)
    with pytest.raises(ValueError, match="wanted \\(4, 4, 8\\)"):
        paged_decode_attention(q, token_major, token_major, *args,
                               interpret=True)


def test_paged_kernel_over_a_ring_in_the_order_of_its_positions():
    """A ring of 3 slots holding a sequence of 23 tokens: logical pages 3,
    4 and 5 in slots 0, 1, 2, handed over oldest first with the position
    the first one holds."""
    rng = np.random.default_rng(5)
    KH, D, page, ring, n, window = 2, 8, 4, 3, 23, 8
    pages = rng.standard_normal((6, KH, page, D)).astype(np.float32)
    pool = np.full((1 + ring, KH, page, D), np.nan, np.float32)
    table = np.asarray([[1, 2, 3]], np.int32)
    for p in range(3, 6):                       # what is still resident
        pool[table[0, p % ring]] = pages[p]
    q = rng.standard_normal((1, 4, D)).astype(np.float32)
    ordered, first = ring_order(jnp.asarray(table), jnp.asarray([n]), page)
    assert list(np.asarray(ordered[0])) == [1, 2, 3] and int(first[0]) == 12
    got = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(pool), jnp.asarray(pool), ordered,
        jnp.asarray([n], jnp.int32), window=window, first_position=first,
        kv_heads=KH, interpret=True)
    seq = pages.swapaxes(1, 2).reshape(-1, KH, D)[:n].swapaxes(0, 1)[None]
    want = _plain(q[:, :, None], seq, seq, window)[:, :, 0]
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-6)


@pytest.mark.parametrize("KH", [2, 4], ids=["grouped", "equal-heads"])
def test_rings_with_first_position_and_poison_before_the_window(KH):
    """Rings of 3 slots (window 8, pages of 4) handed over oldest first,
    one row each: the window's edge inside the ring's first page (23, 17,
    29 tokens), on a page's edge (24: the first step's first slot holds
    nothing seen), before the first entry (5: the window holds the whole
    sequence), an empty slot (0).  NaN in the scratch page, in the tails
    past each row's length and in the positions of each ring before the
    window leaves every output bit for bit as it was."""
    rng = np.random.default_rng(KH)
    H, D, page, ring, window = 4, 8, 4, 3, 8
    lens = np.asarray([23, 24, 5, 0, 17, 29], np.int32)
    head_major = KH < H
    B = len(lens)
    seqs = rng.standard_normal((B, 2, 32, KH, D)).astype(np.float32)
    shape = (1 + B * ring, KH, page, D) if head_major else (
        1 + B * ring, page, KH, D)
    k_pool, v_pool = (rng.standard_normal(shape).astype(np.float32)
                      for _ in range(2))
    table = 1 + np.arange(B * ring, dtype=np.int32).reshape(B, ring)
    for b, n in enumerate(lens):
        for p in range(max(-(-int(n) // page) - ring, 0), -(-int(n) // page)):
            for pool, x in ((k_pool, seqs[b, 0]), (v_pool, seqs[b, 1])):
                blk = x[p * page:(p + 1) * page]
                pool[table[b, p % ring]] = (blk.swapaxes(0, 1) if head_major
                                            else blk)
    q = rng.standard_normal((B, H, D)).astype(np.float32)
    ordered, first = ring_order(jnp.asarray(table), jnp.asarray(lens), page)

    def run(k, v):
        return np.asarray(paged_decode_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), ordered,
            jnp.asarray(lens), window=window, first_position=first,
            kv_heads=KH if head_major else None, interpret=True))
    got = run(k_pool, v_pool)
    for b, n in enumerate(lens):
        if n == 0:
            assert np.isnan(got[b]).all()
            continue
        kk, vv = (seqs[b, i, :n].swapaxes(0, 1)[None] for i in (0, 1))
        want = _plain(q[b][None, :, None], kk, vv, window)[0, :, 0]
        np.testing.assert_allclose(got[b], want, atol=2e-6)
    bad_k, bad_v = k_pool.copy(), v_pool.copy()
    for pool in (bad_k, bad_v):
        pool[0] = np.nan
        for b, n in enumerate(lens):
            at = np.arange(int(first[b]), int(first[b]) + ring * page)
            gone = (at < n - window) | (at >= n)
            for p in np.unique(at[gone] // page):
                slot = table[b, p % ring]
                rows = at[gone][at[gone] // page == p] % page
                if head_major:
                    pool[slot][:, rows] = np.nan
                else:
                    pool[slot][rows] = np.nan
    np.testing.assert_array_equal(run(bad_k, bad_v), got)


def test_equal_heads_and_no_window_keep_the_paged_program_they_had():
    """The program of the models that were there, to the letter: the new
    forms carry a name of their own and the old one none."""
    q = jnp.zeros((2, 4, 8))
    pool = jnp.zeros((3, 5, 4, 4, 8))
    args = (jnp.zeros((2, 2), jnp.int32), jnp.ones((2,), jnp.int32))
    old = str(jax.make_jaxpr(lambda *a: paged_decode_attention(
        *a, layer=1, interpret=True))(q, pool, pool, *args))
    new = str(jax.make_jaxpr(lambda *a: paged_decode_attention(
        *a, layer=1, window=6, interpret=True))(q, pool, pool, *args))
    assert "gqa_paged_decode" not in old and "gqa_paged_decode" in new


def test_equal_heads_and_no_window_keep_the_flash_program_they_had():
    """One forward kernel with static branches: with equal heads and no
    window it is the call named ``flash_fwd`` with its ``lse`` output, which
    the backward reads; grouped heads or a window make the forward-only
    call under a name of its own, with no ``lse``."""
    x = jnp.zeros((1, 4, 64, 16))
    kv = jnp.zeros((1, 2, 64, 16))

    def traced(k, **kw):
        return str(jax.make_jaxpr(lambda q, k: flash_attention_bhsd(
            q, k, k, causal=True, block_q=16, block_k=16, interpret=True,
            **kw))(x, k))
    old, grouped, window = traced(x), traced(kv), traced(x, window=24)
    assert "flash_fwd" in old and "f32[1,4,64,1]" in old
    for new, name in ((grouped, "flash_grouped"), (window, "flash_window")):
        assert name in new and name not in old
        assert "flash_fwd" not in new and "f32[1,4,64,1]" not in new


@pytest.mark.parametrize("s,window,kh", [
    (16, None, 2), (16, 6, 2), (40, 8, 2), (40, 8, 4), (40, 1, 1),
    (37, 12, 2), (64, 40, 2)],
    ids=["grouped", "window", "long", "equal-heads", "one", "ragged",
         "wide"])
def test_flash_with_grouped_heads_and_a_window(s, window, kh):
    rng = np.random.default_rng(s)
    q = rng.standard_normal((2, 4, s, 16)).astype(np.float32)
    k = rng.standard_normal((2, kh, s, 16)).astype(np.float32)
    v = rng.standard_normal((2, kh, s, 16)).astype(np.float32)
    got = flash_attention_bhsd(jnp.asarray(q), jnp.asarray(k),
                               jnp.asarray(v), causal=True, window=window,
                               block_q=8, block_k=8, interpret=True)
    np.testing.assert_allclose(np.asarray(got), _plain(q, k, v, window),
                               atol=2e-6)


def test_flash_with_equal_heads_and_no_window_is_todays_bitwise():
    """Equal heads and no window take the kernel they took; and a window
    that holds the whole sequence gives that kernel's numbers bitwise."""
    rng = np.random.default_rng(2)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 2, 32, 16)), jnp.float32)
               for _ in range(3))
    kw = dict(causal=True, block_q=8, block_k=8, interpret=True)
    today = flash_attention_bhsd(q, k, v, **kw)
    text = str(jax.make_jaxpr(lambda *a: flash_attention_bhsd(*a, **kw))(
        q, k, v))
    assert "flash_fwd" in text and "flash_window" not in text
    np.testing.assert_array_equal(
        np.asarray(flash_attention_bhsd(q, k, v, window=32, **kw)),
        np.asarray(today))
    with pytest.raises(ValueError, match="causal"):
        flash_attention_bhsd(q, k, v, window=4, interpret=True)
    with pytest.raises(ValueError, match="heads"):
        flash_attention_bhsd(q, k[:, :1], v, causal=True, interpret=True)


def test_the_layer_without_a_cache_is_plain_grouped_attention():
    """The whole sequence at once through the materialised core: norms,
    gate and projections around plain attention over K and V repeated."""
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, 12, 32)),
                    jnp.float32)
    layer = GroupedQueryAttention(32, 4, 2, 8, window=5, rope_theta=100.0)
    q, k, v, g = layer._heads(x, jnp.arange(12), "bsd,dhe->bhse")
    o = _plain(np.asarray(q), np.asarray(k), np.asarray(v), 5)
    o = o * np.asarray(jax.nn.sigmoid(g))
    want = np.einsum("bhse,hed->bsd", o, np.asarray(layer.wo).reshape(
        4, 8, 32))
    np.testing.assert_allclose(np.asarray(layer(x)), want, atol=1e-5)
    with pytest.raises(ValueError, match="KV heads"):
        GroupedQueryAttention(32, 4, 3, 8)
