"""Paged decode attention (Pallas/Mosaic): attend IN PLACE over the pool.

The serving decode step used to route attention through XLA gather/scatter:
every step materialized a contiguous ``(L, batch, max_len, H, D)`` view of
the paged KV pool (``layers/cache.gather_views``) before attending — the
dominant per-token HBM traffic at long context, since the whole history is
re-copied to attend over one new token.  This kernel is the PagedAttention
insight (vLLM, SOSP'23) composed with flash-style online softmax
(FlashAttention, NeurIPS'22): the grid walks each sequence's page table and
DMAs K/V pages **directly from the pool** at their physical indices, so no
contiguous view ever exists.

Schedule:
- a step is ``_PAGES_PER_STEP`` consecutive pages of a row's table (one
  operand pair of the pool a page slot), and the grid walks only the steps
  that hold a page the row sees: ``(head_blocks, items)``, items innermost,
  where the items are every row's steps from the first that reaches into
  its window (step 0 without one) to the one that holds position
  ``length - 1``, row after row.  The wrapper builds that work list with
  ``jnp`` from the lengths, the window and ``first_position``
  (``_work_list``); its length is a traced scalar and the grid's bound
  (Mosaic lowers a dynamic grid bound), so no step of the grid holds
  nothing, and the pipeline's look-ahead always fetches a row's first
  pages during the row before's last live step.  A row that sees nothing
  (an empty slot) takes one masked item.  The list is bounded by the
  tables (``batch x steps``), never by the pool: rows that share pages
  (prefix sharing) each walk them.  Measured on a TPU v5e inside whole
  decode steps: 285 us a call over 48 rows of 32 to 1,152 cached tokens
  (pages of 64, tables of 20) where the walk over every step of every
  table took 356, and 65 us where it took 143 with 35 of the 48 rows
  idle.
- the item's row, its step and the PHYSICAL page of each slot ride as
  scalar-prefetch operands with the lengths, so each slot's BlockSpec index
  map picks the page (the gather happens in the DMA descriptor, not in
  HBM) and q's and the output's pick the row: a row's items revisit one
  resident output block, which the row's last item writes.  A slot that
  holds nothing its row sees (past the last page, or before the window)
  stays on the page that slot held in the item before, and a block whose
  index does not change is not copied again: it moves no bytes.
- both products of a page run on the MXU as plain 2-D matmuls over the
  page flattened to ``(page * heads, head_dim)``, which in the pool's own
  layout is the same bytes in the same order (``_flat_page``): ``q (hb, D) .
  K_flat^T`` scores every head's query against every head's keys, an
  own-head (block-diagonal) mask keeps column ``t * hb + h`` for row ``h``
  alone, and the masked weights, exactly 0.0, kill every cross-head term
  of ``P . V_flat``.  That is ``hb`` times the multiply-adds one query a
  head needs, on a unit that is otherwise idle; the vector unit touches
  the ``hb`` rows of scores and not the page.  ``P`` goes into the second
  product in the pool's dtype (as the flash kernels' does); the statistics
  and the accumulation are float32.
- a step is one chain of latencies (product, cross-lane max, exp, product)
  whatever it holds, some 0.7 us on a v5e beside the 0.7 us of a page's
  DMA and a step's fixed cost: the pages of a step share one chain (one
  max, one normalizer update, one accumulator update), which is what lets
  a live page cost its DMA and no more.
- VMEM scratch carries the running max ``m``, normalizer ``l`` and fp32
  output accumulator across a row's items (the flash forward recurrence),
  from its first to its last, which flushes the output.
- masking: position ``i`` of the row is live iff ``< seq_lengths[b]``.
  The steps at/past the length (including the scratch-page-0 padding of
  short page tables) are not walked, and an empty row's one item is
  skipped under ``pl.when``, so a poisoned scratch page (NaN) cannot
  perturb any output (tested).  In a step the scores are masked after
  their product and V's dead rows zeroed before theirs (0 x NaN), so the
  unwritten tail of a row's last page, and a slot that holds nothing of
  the row, cannot either.  What the own-head
  mask isolates is finite values: an inf or NaN in a LIVE V row of one
  head reaches every head of its block (0 x inf in ``P . V_flat``), where
  a product a head kept it to its own.  A pool that holds one is already
  serving garbage for that head.
- one new token per sequence (the decode shape): q is ``(batch, heads,
  head_dim)``.  Prefill keeps the bucketed gather path — it runs once per
  request; decode runs once per generated token.

**Grouped heads** (fewer KV heads than query heads): ``q`` has ``H`` heads,
the pool ``KH``, and query head ``h`` reads KV head ``h // (H / KH)``.  A
block holds whole groups, the page is still one flat matrix and the
own-head mask becomes an own-GROUP mask: a KV head's page is read once for
all of its query heads, and the multiply-adds beyond what the queries need
fall from the head block's size to the KV heads in it.  Such a pool
(``kv_heads=``) is laid out head-major, a page ``(KH, page_size, D)``: the
trailing ``(page_size, D)`` are whole tiles of the device where ``(KH, D)``
with few KV heads is a fraction of one.  Measured on the v5e at 32 query
heads over 4 KV heads of 128, pages of 128, 64 rows of which 16 hold
10,752 tokens and 48 hold 1,152 (my chip run, PR 32): 1,364 us a call
head-major against 1,712 us token-major ``(page_size, KH, D)`` over whole
tables of 108 entries, 423 against 581 us over a window's ring of 17; the
token-major form of grouped heads was taken out again.

**A window**: only positions ``length - window .. length - 1`` are seen.
Steps wholly before the window are not walked, like those past the
length, and the page that holds the window's edge is masked at its head as
the last page is at its tail.
``first_position`` says what position a row's first table entry holds
where the table does not start at 0: a window group's ring in logical order
(``layers.cache.ring_order``), whose 17 entries are all the kernel walks
however long the sequence.

The pool may be passed per layer ``(pages, page_size, H, D)`` or as the
whole stacked ``(layers, pages, page_size, H, D)`` array with a static
``layer`` — the stacked form lets the serving engine thread ONE array pair
through all blocks with no per-layer slicing copies.

``head_block`` (heads loaded per grid step — VMEM footprint vs grid
parallelism, and the factor by which the page's products exceed what one
query a head needs) consults the autotune DB (``autotune_paged_decode``) and
defaults to all heads.  On the CPU the kernel runs in interpreter mode
(tests), so the same code path is exercised everywhere.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hetu_tpu.core.runtime import pallas_interpret
from hetu_tpu.ops.pallas.flash import _compiler_params, _sds

__all__ = ["paged_decode_attention", "walked_steps"]

_NEG_INF = -1e30  # finite: -inf - -inf = nan would poison alpha/exp paths


# Pages a grid step attends over, sharing one chain of latencies (module
# docstring); ``_kernel``'s signature names the two slots.  More only add
# to what every step pays for its operands' index maps (measured at 3 and
# 4: no better).
_PAGES_PER_STEP = 2


def _kernel(rows_ref, steps_ref, pages_ref, sl_ref, q_ref, k0_ref, v0_ref,
            k1_ref, v1_ref, o_ref, m_sc, l_sc, acc, *, scale, page, layered,
            group=1, window=None):
    del pages_ref                           # the index maps' alone
    i = pl.program_id(1)
    b = rows_ref[i]

    # the items of a row are consecutive: its first starts the recurrence
    # and its last flushes the output (``rows`` holds one entry past the
    # longest walk, so the look at the next item is always in bounds)
    @pl.when(jnp.logical_or(i == 0, rows_ref[jnp.maximum(i - 1, 0)] != b))
    def _():
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc[:] = jnp.zeros_like(acc)

    seq_len = sl_ref[b]
    start = steps_ref[i] * _PAGES_PER_STEP * page
    if window is not None:
        seen_from = jnp.maximum(seq_len - window, 0)

    # every item holds a page its row sees but the one item of a row that
    # sees nothing, whose slots are never read into the math
    @pl.when(seq_len > 0)
    def _():
        # column t * hb + h' of S = q (hb, D) . K_flat^T is head h's query
        # against head h''s key at token t; only h' == h is wanted, and
        # the weights of the rest, exactly 0.0, kill every cross-head
        # term of P . V_flat (the module docstring has the reckoning).
        # With grouped heads the page holds kh = hb / group KV heads,
        # head-major: the column is h' * page + t, and h' is wanted where
        # h' == h // group
        hb, D = q_ref.shape[1:]
        kh = hb // group
        q = q_ref[0]
        row = jax.lax.broadcasted_iota(jnp.int32, (hb, page * kh), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (hb, page * kh), 1)
        own = (col // page == row // group if group > 1
               else jax.lax.rem(col, kh) == row)               # own head
        v_row = jax.lax.broadcasted_iota(jnp.int32, (page * kh, D), 0)
        per_token = kh           # flat rows a token takes before the next
        if group > 1:
            col, v_row, per_token = col % page, v_row % page, 1
        scores, values = [], []
        for slot, (k_ref, v_ref) in enumerate(((k0_ref, v0_ref),
                                               (k1_ref, v1_ref))):
            # this slot's rows before the row's length (all of them in a
            # whole page, none in a slot past the last page): token
            # col // hb is live iff col < rows.  A masked position's
            # weight is exactly 0.0, but IEEE 0*NaN = NaN: V's dead rows
            # are zeroed too, so garbage in the unwritten tail of a row's
            # LAST page can never reach the PV product (the K side is
            # covered by the where below).  The masks ride in slots the
            # step leaves empty; a second, unmasked body for whole steps
            # measured no faster and doubled what every program traces.
            rows = (seq_len - start - slot * page) * per_token
            k, v = _flat_page(k_ref, layered), _flat_page(v_ref, layered)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale    # (hb, page*kh)
            seen = col < rows
            if window is not None:
                # the slot's rows before the window, masked as those past
                # the length are (K's scores and V's rows alike)
                gone = (seen_from - start - slot * page) * per_token
                seen = jnp.logical_and(seen, col >= gone)
            scores.append(jnp.where(jnp.logical_and(own, seen),
                                    s, _NEG_INF))
            v_seen = v_row < rows
            if window is not None:
                v_seen = jnp.logical_and(v_seen, v_row >= gone)
            values.append(jnp.where(v_seen, v, jnp.zeros_like(v)))
        m_prev = m_sc[:, :1]                                   # (hb, 1)
        m_new = jnp.maximum(m_prev, jnp.max(
            jnp.maximum(*scores), axis=1, keepdims=True))
        weights = [jnp.exp(s - m_new) for s in scores]         # (hb, page*hb)
        alpha = jnp.exp(m_prev - m_new)
        l_sc[:, :1] = alpha * l_sc[:, :1] + jnp.sum(
            weights[0] + weights[1], axis=1, keepdims=True)
        m_sc[:, :1] = m_new
        acc[:] = acc[:] * alpha + sum(
            jax.lax.dot_general(
                pw.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)            # (hb, D)
            for pw, v in zip(weights, values))

    @pl.when(jnp.logical_or(i == pl.num_programs(1) - 1,
                            rows_ref[i + 1] != b))
    def _():
        o_ref[0] = (acc[:] / l_sc[:, :1]).astype(o_ref.dtype)


def _flat_page(ref, layered):
    """The page block ``(page, hb, D)`` as the matrix ``(page * hb, D)``:
    row ``t * hb + h`` is head ``h`` at token ``t``, the block's own order
    (head-major, ``(hb, page, D)`` and row ``h * page + t``).
    (A 16-bit block's registers pair two heads a sublane and Mosaic repacks
    them for the matrix; a step waits on its DMA meanwhile.  Flattening
    through the 32-bit words, which repacks nothing, measured within 1% on
    the chip and was not kept.)"""
    x = ref[0, 0] if layered else ref[0]
    return x.reshape(-1, x.shape[-1])


def _step_span(lengths, page, window):
    """First and last grid step of each row that hold a page it sees, and
    its first and last seen table entries (``lengths`` in the table's own
    positions; the last entry is -1 for a row that sees nothing, whose
    span is step 0 alone).  ``jax.numpy`` and ``numpy`` arrays alike."""
    slots = _PAGES_PER_STEP
    last = (lengths + page - 1) // page - 1
    first = 0 * lengths if window is None else (
        (lengths - window).clip(0) // page)
    return first // slots, last.clip(0) // slots, first, last


def _work_list(tables, lengths, page, window):
    """The grid's items, row after row: every step of a row from the first
    to the last that holds a page the row sees (one, masked, for a row that
    sees nothing).  Returns the item's row (one entry more, a copy of the
    last), its step, the physical page of each of its slots (flat, slot
    fastest) and the number of items; the arrays are as long as the most
    a call can walk, every step of every row."""
    B, n_pages = tables.shape
    slots = _PAGES_PER_STEP
    lo, hi, first, last = _step_span(lengths, page, window)
    count = hi - lo + 1
    ends = jnp.cumsum(count)
    item = jnp.arange(B * -(-n_pages // slots), dtype=jnp.int32)
    row = jnp.minimum(jnp.searchsorted(ends, item, side="right",
                                       method="compare_all"), B - 1)
    step = jnp.minimum(lo[row] + item - (ends - count)[row], hi[row])
    entry = step[:, None] * slots + jnp.arange(slots, dtype=jnp.int32)
    seen = (entry >= first[row][:, None]) & (entry <= last[row][:, None])
    at = tables.reshape(-1)[row[:, None] * n_pages
                            + jnp.minimum(entry, n_pages - 1)]
    # a slot that holds nothing its row sees stays on the page it held in
    # the item before (page 0 before any), and a block whose index does
    # not change is not copied again: it moves no bytes
    src = jax.lax.cummax(jnp.where(seen, item[:, None], -1), axis=0)
    pages = jnp.where(src >= 0, jnp.take_along_axis(
        at, jnp.maximum(src, 0), axis=0), 0)
    return (jnp.append(row, row[-1]).astype(jnp.int32), step,
            pages.reshape(-1), ends[-1])


def walked_steps(lengths, entries: int, page: int,
                 window: int | None = None) -> tuple:
    """Grid steps one head block of a call walks, and the steps its tables
    hold, for rows of ``lengths`` tokens over tables of ``entries`` pages
    that hold each row's last pages (a whole table, or a window's ring in
    the order of its positions).  ``numpy``, for the host's counters."""
    lengths = np.asarray(lengths)
    lengths = lengths - np.maximum(-(-lengths // page) - entries, 0) * page
    lo, hi, _, _ = _step_span(lengths, page, window)
    rows = len(lengths)
    return int((hi - lo).sum()) + rows, rows * -(-entries // _PAGES_PER_STEP)


def _head_block(H: int, D: int, page: int,
                head_block: int | None) -> int:
    """Heads per grid step: explicit arg > autotune DB > all heads."""
    if head_block is None:
        from hetu_tpu.ops.pallas.autotune import tuned_entry
        hit = tuned_entry("paged_decode", f"h{H}|d{D}|p{page}")
        if hit and H % int(hit["head_block"]) == 0:
            head_block = int(hit["head_block"])
    hb = head_block or H
    if H % hb:
        raise ValueError(f"head_block {hb} must divide num_heads {H}")
    return hb


def paged_decode_attention(q, k_pool, v_pool, page_tables, seq_lengths, *,
                           layer: int | None = None,
                           scale: float | None = None,
                           head_block: int | None = None,
                           interpret: bool | None = None,
                           window: int | None = None,
                           first_position=None,
                           kv_heads: int | None = None):
    """Flash-style decode attention of one new query per sequence over its
    paged KV history, read in place from the pool.

    q: ``(batch, heads, head_dim)`` — the new token's queries.
    k_pool/v_pool: ``(pages, page_size, heads, head_dim)`` or the stacked
    ``(layers, pages, ...)`` form with a static ``layer``.
    page_tables: ``(batch, pages_per_seq)`` int32 physical page indices,
    short tables padded with the scratch page (``kv_cache.SCRATCH_PAGE``).
    seq_lengths: ``(batch,)`` int32 — valid tokens per row INCLUDING the
    new token (whose K/V must already be written into the pool).
    ``kv_heads``: the pool holds fewer heads than ``q`` (a divisor of
    them: grouped heads), and its pages are then head-major, ``(kv_heads,
    page_size, head_dim)`` (module docstring).  ``window``: only the last
    ``window`` of the row's ``seq_lengths`` positions are seen.  ``first_position`` ``(batch,)``:
    the position each row's first table entry holds (a multiple of the page
    size; nought where not given).
    Returns ``(batch, heads, head_dim)``; numerically the valid-prefix
    softmax attention (``layers.attention.decode_attention`` restricted to
    one query), with fp32 statistics and accumulation.
    """
    if interpret is None:
        interpret = pallas_interpret()
    layered = k_pool.ndim == 5
    if layered and layer is None:
        raise ValueError("a stacked (layers, pages, ...) pool needs the "
                         "static layer index")
    B, H, D = q.shape
    KH = H if kv_heads is None else int(kv_heads)
    if H % KH:
        raise ValueError(f"{H} query heads over {KH} KV heads")
    group = H // KH
    head_major = group > 1
    page = k_pool.shape[-2 if head_major else -3]
    if k_pool.shape[-3 if head_major else -2] != KH:
        raise ValueError(
            f"pages {k_pool.shape[-3:]} of a pool of {KH} KV heads for {H} "
            f"query heads: wanted "
            f"{(KH, page, D) if head_major else (page, KH, D)}")
    n_pages = page_tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    hb = _head_block(H, D, page, head_block)
    if hb % group:
        raise ValueError(f"head_block {hb} must hold whole groups of "
                         f"{group} query heads")
    # the forms that models before grouped heads and windows compile keep
    # the program (and the device events' name) they had
    plain = group == 1 and window is None

    lengths = seq_lengths.astype(jnp.int32)
    if first_position is not None:
        # everything below is in the table's own positions
        lengths = lengths - first_position.astype(jnp.int32)
    lengths = jnp.minimum(lengths, n_pages * page)
    rows, steps, pages, n_items = _work_list(
        page_tables.astype(jnp.int32), lengths, page, window)

    lead = (layer,) if layered else ()
    kh = hb // group
    page_block = (kh, page, D) if head_major else (page, kh, D)
    slots = _PAGES_PER_STEP

    def kv_spec(s):
        def index(h, i, rows, steps, pages, sl):
            at = pages[i * slots + s]
            return lead + ((at, h, 0, 0) if head_major else (at, 0, h, 0))
        return pl.BlockSpec((1,) * len(lead) + (1,) + page_block, index)
    q_spec = pl.BlockSpec((1, hb, D), lambda h, i, rows, *_: (rows[i], h, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(H // hb, n_items),
        in_specs=[q_spec, kv_spec(0), kv_spec(0), kv_spec(1), kv_spec(1)],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((hb, 128), jnp.float32),
            pltpu.VMEM((hb, 128), jnp.float32),
            pltpu.VMEM((hb, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, page=page, layered=layered,
                          group=group, window=window),
        grid_spec=grid_spec,
        out_shape=_sds(q.shape, q.dtype, q),
        compiler_params=_compiler_params(1),
        interpret=interpret,
        **({} if plain else {"name": "gqa_paged_decode"}),
    )(rows, steps, pages, lengths, q, k_pool, v_pool, k_pool, v_pool)
