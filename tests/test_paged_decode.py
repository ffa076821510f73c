"""Paged-decode Pallas kernel: interpret-mode parity vs the XLA decode
path on ragged seq_lengths (ulp-tight), scratch-page poisoning immunity,
layered-pool indexing, head-block tiling invariance, and the engine-level
no-materialization acceptance (zero ``gather_views`` traces in the paged
decode program, counted at the seam).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_tpu.core import set_random_seed
from hetu_tpu.layers.attention import (MultiHeadAttention, PagedDecode,
                                       decode_attention)
from hetu_tpu.models.gpt import GPT, GPTConfig
from hetu_tpu.ops.pallas.paged_decode import (_work_list,
                                              paged_decode_attention,
                                              walked_steps)
from hetu_tpu.serve import ServingEngine
from hetu_tpu.serve.kv_cache import gather_view_count

pytestmark = pytest.mark.pallas


def _paged_setup(lens, *, H=2, D=8, page=4, n_pages=None, P=None, seed=0):
    """Pools + page tables for ragged ``lens``; pages handed out low-first
    from 1 (page 0 reserved scratch), mirroring KVCachePool placement."""
    rng = np.random.default_rng(seed)
    B = len(lens)
    n_pages = n_pages or max(-(-int(n) // page) for n in lens)
    P = P or 1 + sum(-(-int(n) // page) for n in lens)
    tables = np.zeros((B, n_pages), np.int32)
    nxt = 1
    for i, n in enumerate(lens):
        for j in range(-(-int(n) // page)):
            tables[i, j] = nxt
            nxt += 1
    k_pool = rng.standard_normal((P, page, H, D)).astype(np.float32)
    v_pool = rng.standard_normal((P, page, H, D)).astype(np.float32)
    q = rng.standard_normal((B, H, D)).astype(np.float32)
    return q, k_pool, v_pool, tables, np.asarray(lens, np.int32)


def _reference(q, k_pool, v_pool, tables, lens):
    """The XLA path the kernel replaces: gather the contiguous caches,
    run ``decode_attention`` (cache_index = len - 1 for one new token)."""
    B, n_pages = tables.shape
    page = k_pool.shape[1]
    k_cache = k_pool[tables].reshape(B, n_pages * page, *k_pool.shape[2:])
    v_cache = v_pool[tables].reshape(B, n_pages * page, *v_pool.shape[2:])
    out = decode_attention(jnp.asarray(q)[:, None], jnp.asarray(k_cache),
                           jnp.asarray(v_cache), jnp.asarray(lens - 1))
    return np.asarray(out)[:, 0]


@pytest.mark.parametrize("lens", [[5, 16, 1], [4, 4], [13, 2, 7, 9],
                                  list(range(1, 21)), [3, 1]],
                         ids=["3rows", "2rows", "4rows", "every-length",
                              "one-page-table"])
def test_paged_matches_decode_attention_ragged(lens):
    """Parity vs the gather + decode_attention path is ulp-tight on
    ragged batches (fp32 online softmax vs fp32 full softmax); the last
    fourth case ends a row at every position of a five-page table, so
    every split of a step's page slots into whole, partial and dead
    occurs; the tables of the second and the last have one page, so the
    step's second slot holds nothing of its own."""
    q, k_pool, v_pool, tables, lens = _paged_setup(lens)
    out = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(tables), jnp.asarray(lens), interpret=True)
    ref = _reference(q, k_pool, v_pool, tables, lens)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-6, atol=2e-7)


def test_scratch_page_poisoning_bitwise_immune():
    """Fill the reserved scratch page 0 with NaN: every output must be
    BITWISE unchanged — padded page-table entries and positions at/past
    seq_lengths are never read into the math (a single leaked NaN would
    infect the whole row through the softmax)."""
    q, k_pool, v_pool, tables, lens = _paged_setup([5, 16, 1])
    clean = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(tables), jnp.asarray(lens), interpret=True)
    k_poison, v_poison = k_pool.copy(), v_pool.copy()
    k_poison[0] = np.nan
    v_poison[0] = np.nan
    poisoned = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(k_poison), jnp.asarray(v_poison),
        jnp.asarray(tables), jnp.asarray(lens), interpret=True)
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(poisoned))


def test_tail_of_last_page_masked():
    """Garbage (NaN) in the allocated-but-unwritten tail of a row's LAST
    page must not contribute either — the in-page position mask, not just
    the whole-page skip, carries the seq_lengths contract."""
    q, k_pool, v_pool, tables, lens = _paged_setup([5, 9])
    clean = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(tables), jnp.asarray(lens), interpret=True)
    page = k_pool.shape[1]
    k_poison, v_poison = k_pool.copy(), v_pool.copy()
    for i, n in enumerate(lens):
        last_pg = tables[i, (int(n) - 1) // page]
        k_poison[last_pg, int(n) % page or page:] = np.nan
        v_poison[last_pg, int(n) % page or page:] = np.nan
    poisoned = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(k_poison), jnp.asarray(v_poison),
        jnp.asarray(tables), jnp.asarray(lens), interpret=True)
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(poisoned))


def test_layered_pool_and_head_block_invariance():
    """The stacked (layers, pages, ...) form with a static layer index
    reads exactly its layer, bit for bit; head_block tilings agree to
    float32 rounding (the autotune knob cannot change results)."""
    q, k_pool, v_pool, tables, lens = _paged_setup([5, 16, 1], H=4)
    base = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(tables), jnp.asarray(lens), interpret=True)
    k5 = np.stack([k_pool * 3, k_pool])
    v5 = np.stack([v_pool * 3, v_pool])
    layered = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(k5), jnp.asarray(v5),
        jnp.asarray(tables), jnp.asarray(lens), layer=1, interpret=True)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(layered))
    with pytest.raises(ValueError, match="layer"):
        paged_decode_attention(
            jnp.asarray(q), jnp.asarray(k5), jnp.asarray(v5),
            jnp.asarray(tables), jnp.asarray(lens), interpret=True)
    # a head block is the width of the page's flattened products, so a
    # tiling changes the order of the float32 sums and nothing else
    for hb in (1, 2):
        tiled = paged_decode_attention(
            jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(tables), jnp.asarray(lens), head_block=hb,
            interpret=True)
        np.testing.assert_allclose(np.asarray(tiled), np.asarray(base),
                                   rtol=2e-6, atol=2e-7)
    with pytest.raises(ValueError, match="head_block"):
        paged_decode_attention(
            jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(tables), jnp.asarray(lens), head_block=3,
            interpret=True)


# the serving cells' shape: 16 heads of 128 on pages of 64, the stacked
# five-dimensional pool read at a static layer.  Row 0 fills its table,
# row 1 holds one token, row 2 ends on a page edge, row 3 mid-page.
_CELL = dict(H=16, D=128, page=64, n_pages=4)
_CELL_LENS = [256, 1, 128, 77]


def _cell_setup(dtype, seed=5):
    q, k_pool, v_pool, tables, lens = _paged_setup(_CELL_LENS, seed=seed,
                                                   **_CELL)
    rng = np.random.default_rng(seed + 1)
    # layer 0 is another layer's content: reading it would show
    k5 = np.stack([rng.standard_normal(k_pool.shape, np.float32), k_pool])
    v5 = np.stack([rng.standard_normal(v_pool.shape, np.float32), v_pool])
    return tuple(jnp.asarray(x, dtype) for x in (q, k5, v5)) + (tables, lens)


def _masked_softmax_reference(q, k5, v5, tables, lens, layer):
    """Float32 masked-softmax attention over the gathered pages of one
    layer, from the same (rounded) inputs, in float64 on the host."""
    q, k, v = (np.asarray(x.astype(jnp.float32), np.float64)
               for x in (q, k5[layer], v5[layer]))
    B, n_pages = tables.shape
    k = k[tables].reshape(B, -1, *k.shape[2:])
    v = v[tables].reshape(B, -1, *v.shape[2:])
    s = np.einsum("bhd,bkhd->bhk", q, k) / np.sqrt(q.shape[-1])
    s = np.where(np.arange(s.shape[-1])[None, None] < lens[:, None, None],
                 s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("bhk,bkhd->bhd", p / p.sum(-1, keepdims=True), v)


@pytest.mark.parametrize("dtype,rtol,atol", [
    (jnp.float32, 2e-5, 2e-6),
    # half a bf16 ulp of the output, and the weights' rounding ahead of P.V
    (jnp.bfloat16, 4e-3, 4e-3),
], ids=["float32", "bfloat16"])
def test_cell_shape_matches_masked_softmax(dtype, rtol, atol):
    q, k5, v5, tables, lens = _cell_setup(dtype)
    out = paged_decode_attention(q, k5, v5, jnp.asarray(tables),
                                 jnp.asarray(lens), layer=1, interpret=True)
    assert out.dtype == dtype and out.shape == q.shape
    ref = _masked_softmax_reference(q, k5, v5, tables, lens, 1)
    np.testing.assert_allclose(np.asarray(out.astype(jnp.float32)), ref,
                               rtol=rtol, atol=atol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("head", [0, 7, 15])
def test_own_head_isolation_bitwise(dtype, head):
    """Both products run over the page flattened to (page * heads, D), so
    every head's query meets every head's keys and the own-head mask is
    all that keeps them apart.  Changing K and V of every head but one,
    to large finite values in live rows and to NaN in dead ones (the
    scratch page and the last pages' tails, all heads), must leave that
    head's output bit for bit as it was."""
    q, k5, v5, tables, lens = _cell_setup(dtype)
    run = lambda k, v: np.asarray(paged_decode_attention(
        q, k, v, jnp.asarray(tables), jnp.asarray(lens), layer=1,
        interpret=True).astype(jnp.float32))
    clean = run(k5, v5)
    others = np.arange(_CELL["H"]) != head
    k_bad, v_bad = (np.array(x.astype(jnp.float32)) for x in (k5, v5))
    rng = np.random.default_rng(head)
    for x in (k_bad, v_bad):
        x[1][:, :, others] = 1e30 * rng.choice(
            [-1.0, 1.0], x[1][:, :, others].shape)
        x[1, 0] = np.nan                                # the scratch page
        for i, n in enumerate(lens):
            if int(n) % _CELL["page"]:
                last = tables[i, (int(n) - 1) // _CELL["page"]]
                x[1, last, int(n) % _CELL["page"]:] = np.nan
    bad = run(jnp.asarray(k_bad, dtype), jnp.asarray(v_bad, dtype))
    np.testing.assert_array_equal(clean[:, head], bad[:, head])
    assert np.isfinite(clean).all()


# ragged mixes of the walk: empty rows (0 tokens), one-token rows, rows
# that end on a page edge and mid-page, and rows that fill their table, at
# 4 heads of 8 on pages of 4 over tables of 5 entries (3 steps, the last
# with one slot past the table)
_MIXES = {
    "mixed": [0, 1, 8, 9, 20, 13, 0, 4],
    "all-empty-but-one": [0, 0, 0, 7, 0, 0],
    "all-empty-but-one-full": [0, 20, 0],
    "one-token-rows": [1, 1, 1, 2],
    "page-edges": [4, 8, 12, 16, 20],
    "full-tables": [20, 20, 20],
}


@pytest.mark.parametrize("head_block", [None, 2, 1],
                         ids=["all-heads", "hb2", "hb1"])
@pytest.mark.parametrize("mix", list(_MIXES))
def test_walk_over_ragged_rows_matches_masked_softmax(mix, head_block):
    """Each row walks only the steps that hold its pages and an empty row
    one masked step; every output is float64 masked-softmax attention over
    the row's own tokens (an empty row's NaN, 0 / 0, as it always was)."""
    lens = _MIXES[mix]
    q, k_pool, v_pool, tables, lens = _paged_setup(
        lens, H=4, page=4, n_pages=5, seed=len(lens))
    with np.errstate(invalid="ignore"):
        ref = _masked_softmax_reference(
            jnp.asarray(q), jnp.asarray(k_pool)[None],
            jnp.asarray(v_pool)[None], tables, lens, 0)
    k_pool[0] = v_pool[0] = np.nan               # the scratch page is poison
    out = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(tables), jnp.asarray(lens), head_block=head_block,
        interpret=True)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-6, atol=2e-7)
    assert np.isnan(np.asarray(out)[lens == 0]).all()
    assert np.isfinite(np.asarray(out)[lens > 0]).all()


@pytest.mark.parametrize("window", [None, 6], ids=["whole", "window-6"])
def test_work_list_counted_by_hand(window):
    """Pages of 4, tables of 5 entries (3 steps of two slots), rows of 0,
    1, 8, 9 and 20 tokens: without a window 1 (the empty row's masked
    step) + 1 + 1 + 2 + 3 = 8 of 15 steps; with a window of 6 the last row
    sees tokens 14 to 19, entries 3 and 4, step 1 alone and step 2: 7.
    A slot that holds nothing its row sees keeps the page the slot held
    in the item before (page 0 before any), so it copies nothing."""
    lens = np.asarray([0, 1, 8, 9, 20], np.int32)
    tables = np.asarray([[0, 0, 0, 0, 0], [11, 0, 0, 0, 0],
                         [21, 22, 0, 0, 0], [31, 32, 33, 0, 0],
                         [41, 42, 43, 44, 45]], np.int32)
    rows, steps, pages, n = (np.asarray(x) for x in _work_list(
        jnp.asarray(tables), jnp.asarray(lens), 4, window))
    if window is None:
        want_rows, want_steps = [0, 1, 2, 3, 3, 4, 4, 4], [0, 0, 0, 0, 1,
                                                           0, 1, 2]
        want_pages = [[0, 0], [11, 0], [21, 22], [31, 32], [33, 32],
                      [41, 42], [43, 44], [45, 44]]
    else:
        want_rows, want_steps = [0, 1, 2, 3, 3, 4, 4], [0, 0, 0, 0, 1, 1, 2]
        want_pages = [[0, 0], [11, 0], [21, 22], [31, 32], [33, 32],
                      [33, 44], [45, 44]]
    assert int(n) == len(want_rows)
    assert walked_steps(lens, 5, 4, window) == (int(n), 15)
    assert list(rows[:n]) == want_rows and list(steps[:n]) == want_steps
    assert pages.reshape(-1, 2)[:n].tolist() == want_pages
    # one entry past the longest walk, and every item in bounds
    assert len(rows) == 16 and rows.max() < len(lens)


def test_rows_sharing_their_leading_pages_beyond_the_pool():
    """Prefix sharing: rows name the same leading pages, so the live pages
    of a call (13 here) exceed the pool's 6; each row still reads its own
    table, and the walk is bounded by the tables, never by the pool."""
    rng = np.random.default_rng(9)
    H, D, page = 2, 8, 4
    k_pool, v_pool = (rng.standard_normal((6, page, H, D)).astype(np.float32)
                      for _ in range(2))
    tables = np.asarray([[1, 2, 3, 4], [1, 2, 3, 5], [1, 2, 0, 0],
                         [1, 2, 3, 4]], np.int32)
    lens = np.asarray([16, 14, 6, 13], np.int32)
    q = rng.standard_normal((4, H, D)).astype(np.float32)
    out = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(tables), jnp.asarray(lens), interpret=True)
    ref = _masked_softmax_reference(
        jnp.asarray(q), jnp.asarray(k_pool)[None], jnp.asarray(v_pool)[None],
        tables, lens, 0)
    assert sum(-(-int(n) // page) for n in lens) > k_pool.shape[0]
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-6, atol=2e-7)


def test_mha_paged_step_matches_cached_step():
    """One MultiHeadAttention paged decode step == the contiguous-cache
    ``_call_cached`` step: same output, and the scattered K/V rows land
    exactly where the gathered view would have written them."""
    set_random_seed(3)
    H, D, page, n_pages = 2, 8, 4, 3
    mha = MultiHeadAttention(H * D, H)
    rng = np.random.default_rng(1)
    lens = np.asarray([5, 9], np.int32)  # history BEFORE the new token
    B = len(lens)
    q, k_pool, v_pool, tables, _ = _paged_setup(
        list(lens + 1), H=H, D=D, page=page, n_pages=n_pages, seed=1)
    x = jnp.asarray(rng.standard_normal((B, 1, H * D)), jnp.float32)

    # contiguous reference caches mirroring the pool's current content
    max_len = n_pages * page
    k_cache = jnp.asarray(k_pool[tables].reshape(B, max_len, H, D))
    v_cache = jnp.asarray(v_pool[tables].reshape(B, max_len, H, D))
    y_ref, (k_ref, v_ref) = mha(x, kv_cache=(k_cache, v_cache),
                                cache_index=jnp.asarray(lens))
    y_paged, (k_new, v_new) = mha(
        x, kv_cache=(jnp.asarray(k_pool), jnp.asarray(v_pool)),
        cache_index=jnp.asarray(lens),
        paged=PagedDecode(jnp.asarray(tables)))
    np.testing.assert_allclose(np.asarray(y_paged), np.asarray(y_ref),
                               rtol=2e-6, atol=2e-7)
    # the scatter wrote each row's new K/V at (page, slot) == position len
    k_new, v_new = np.asarray(k_new), np.asarray(v_new)
    for i, n in enumerate(lens):
        pg, slot = tables[i, int(n) // page], int(n) % page
        np.testing.assert_array_equal(
            k_new[pg, slot], np.asarray(k_ref)[i, int(n)])
        np.testing.assert_array_equal(
            v_new[pg, slot], np.asarray(v_ref)[i, int(n)])


def tiny_gpt(seed=0, **kw):
    set_random_seed(seed)
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2, num_heads=2,
                    max_seq_len=64, **kw)
    return GPT(cfg)


def test_gpt_paged_decode_matches_gather_decode():
    """A full GPT paged decode step (stacked pools threaded through every
    block) produces the same next-token logits as the gather-view decode
    path, on a ragged batch."""
    m = tiny_gpt()
    cfg = m.config
    H, D = cfg.num_heads, cfg.hidden_size // cfg.num_heads
    page, n_pages = 8, 4
    lens = np.asarray([5, 9, 2], np.int32)
    B = len(lens)
    rng = np.random.default_rng(2)
    P = 1 + B * n_pages
    tables = np.zeros((B, n_pages), np.int32)
    nxt = 1
    for i, n in enumerate(lens):
        for j in range(-(-(int(n) + 1) // page)):
            tables[i, j] = nxt
            nxt += 1
    k_pool = rng.standard_normal(
        (cfg.num_layers, P, page, H, D)).astype(np.float32)
    v_pool = rng.standard_normal(
        (cfg.num_layers, P, page, H, D)).astype(np.float32)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 1)), jnp.int32)

    max_len = n_pages * page
    kv = [(jnp.asarray(k_pool[li][tables].reshape(B, max_len, H, D)),
           jnp.asarray(v_pool[li][tables].reshape(B, max_len, H, D)))
          for li in range(cfg.num_layers)]
    logits_ref, _ = m(toks, kv_cache=kv, cache_index=jnp.asarray(lens))
    logits_paged, (k2, v2) = m(
        toks, kv_cache=(jnp.asarray(k_pool), jnp.asarray(v_pool)),
        cache_index=jnp.asarray(lens), paged_tables=jnp.asarray(tables))
    np.testing.assert_allclose(np.asarray(logits_paged),
                               np.asarray(logits_ref),
                               rtol=2e-5, atol=2e-6)
    assert k2.shape == k_pool.shape and v2.shape == v_pool.shape


@pytest.mark.serve
def test_engine_paged_decode_zero_gather_materialization():
    """Acceptance: the paged engine's decode program traces ZERO
    ``gather_views`` calls (the counting seam in serve/kv_cache.py) —
    only the per-bucket prefill program gathers — and its token streams
    are bitwise-identical to the gather engine's on the same requests."""
    m = tiny_gpt()

    def run(paged):
        eng = ServingEngine(m, num_slots=2, page_size=8, max_seq_len=64,
                            prompt_buckets=(8,), sampling="top_k", top_k=3,
                            temperature=1.5, seed=0, paged_decode=paged)
        before = gather_view_count()
        hs = [eng.submit([i + 1, i + 2, i + 3], 6) for i in range(4)]
        eng.run_until_idle()
        assert all(h.status == "completed" for h in hs)
        return [tuple(h.tokens) for h in hs], gather_view_count() - before

    paged_streams, paged_traces = run(True)
    gather_streams, gather_traces = run(False)
    # paged: exactly the one prefill bucket program gathered; gather
    # baseline additionally traces its decode program's gather
    assert paged_traces == 1
    assert gather_traces == 2
    assert paged_streams == gather_streams
    # and directly: tracing the paged decode impl touches the seam 0 times
    eng = ServingEngine(m, num_slots=2, page_size=8, max_seq_len=64,
                        prompt_buckets=(8,), seed=0, paged_decode=True)
    before = gather_view_count()
    jax.eval_shape(
        eng._paged_decode_impl, m, eng.pool.k, eng.pool.v,
        jnp.zeros((2, 8), jnp.int32), jnp.zeros((2,), jnp.int32),
        jnp.zeros((2, 1), jnp.int32), jnp.zeros((2,), jnp.int32),
        jnp.zeros((2,), jnp.int32))
    assert gather_view_count() == before


@pytest.mark.serve
def test_engine_counts_the_steps_its_kernel_walks():
    """The engine counts, from the lengths it hands the kernel, the grid
    steps walked and skipped by group of layers: walked plus skipped is
    every step the tables hold (2 slots x 4 steps of two pages x 2 layers
    a decode step), the same in ``stats()`` and on ``/metrics``."""
    from hetu_tpu.obs import get_registry
    m = tiny_gpt()
    eng = ServingEngine(m, num_slots=2, page_size=8, max_seq_len=64,
                        prompt_buckets=(8,), seed=0)
    counter = get_registry().counter(
        "hetu_serve_paged_decode_steps_total", "", ("group", "kind"))
    before = {k: counter.labels(group="all", kind=k).value
              for k in ("walked", "skipped")}
    h = eng.submit([1, 2, 3], 30)
    eng.run_until_idle()
    assert h.status == "completed"
    steps = eng.stats()["paged_decode"]["steps"]
    decode_steps = sum(eng.stats()["lookahead"]["steps"].values())
    assert set(steps) == {"all"}
    walked, skipped = steps["all"]["walked"], steps["all"]["skipped"]
    assert walked + skipped == decode_steps * 2 * 4 * 2
    # the request's 3 to 32 tokens hold one to two steps, the idle slot
    # walks its one: 2 to 3 a layer of 8
    assert decode_steps * 2 * 2 <= walked <= decode_steps * 3 * 2
    assert {k: counter.labels(group="all", kind=k).value - before[k]
            for k in before} == steps["all"]
