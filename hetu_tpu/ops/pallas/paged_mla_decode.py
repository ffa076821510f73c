"""Paged decode attention over a latent cache (Pallas/Mosaic).

Multi-head latent attention caches one latent a token, shared by all heads:
``[c, k_pe]``, ``value_width`` values of compressed keys and values and
then the rotated position part.  In the absorbed form a head's query is as
wide as the latent (its ``nope`` part taken through ``W_uk`` into the
latent space), every head scores against the same cached row, and the
row's first ``value_width`` values are also what the weights sum: one page
serves both products and is read once.

``paged_decode.py`` is the kernel this follows (the page table and the
lengths as scalar prefetch, the physical page picked in the block's index
map, flash statistics in VMEM scratch, dead steps skipped and a slot past
the row's last page left on the block it held, so that nothing is copied
for it); what differs is the layout and the products.  The pool holds a page
TOKEN-MINOR, ``(width, page)``: a latent of 576 values is no multiple of
the 128 lanes, and a pool whose minor dimension it were would either be
padded to 640 or, as the v5e compiler chose for it, be laid out token-minor
behind the program's back and copied whole into row-major order for every
call of the kernel and back after it (seen in the compiled decode step:
two copies of 3.4 GB a tick).  Stated token-minor, the page is the scores'
right-hand side as it lies, ``q (heads, width) . page``, and the values'
transposed, ``P (heads, page) . page[:value_width]^T``; the heads are the
rows of the query, so there is nothing to mask out between heads.  A page
of 128 tokens is 147 KB, a fifth of a
microsecond of DMA and far less than a grid step's own latency, so a step
takes ``pages_per_step`` pages (an operand of the pool each), which share
one max, one normaliser and one accumulator update.

Masking as in ``paged_decode.py``: a position at or past the row's length
scores ``-1e30`` after its product and its value row is zeroed before its
product, so neither the scratch page nor the unwritten tail of a row's last
page (NaN included) reaches an output.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hetu_tpu.core.runtime import pallas_interpret
from hetu_tpu.ops.pallas.flash import _compiler_params, _sds

__all__ = ["paged_mla_decode"]

_NEG_INF = -1e30
PAGES_PER_STEP = 8


def _kernel(pt_ref, sl_ref, q_ref, *refs, scale, page, slots, value_width,
            layered):
    del pt_ref                              # the index maps' alone
    page_refs, (o_ref, m_sc, l_sc, acc) = refs[:slots], refs[slots:]
    b, p = pl.program_id(0), pl.program_id(1)

    @pl.when(p == 0)
    def _():
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc[:] = jnp.zeros_like(acc)

    seq_len = sl_ref[b]
    start = p * slots * page

    @pl.when(start < seq_len)
    def _():
        q = q_ref[0]                                           # (H, W)
        heads = q.shape[0]
        col = jax.lax.broadcasted_iota(jnp.int32, (heads, page), 1)
        v_col = jax.lax.broadcasted_iota(jnp.int32, (value_width, page), 1)
        scores, values = [], []
        for slot, ref in enumerate(page_refs):
            rows = seq_len - start - slot * page     # live rows of the slot
            kv = ref[0, 0] if layered else ref[0]              # (W, page)
            s = jax.lax.dot_general(
                q, kv, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale    # (H, page)
            scores.append(jnp.where(col < rows, s, _NEG_INF))
            v = kv[:value_width]                               # (vw, page)
            values.append(jnp.where(v_col < rows, v, jnp.zeros_like(v)))
        m_prev = m_sc[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(
            functools.reduce(jnp.maximum, scores), axis=1, keepdims=True))
        weights = [jnp.exp(s - m_new) for s in scores]
        alpha = jnp.exp(m_prev - m_new)
        l_sc[:, :1] = alpha * l_sc[:, :1] + jnp.sum(
            sum(weights), axis=1, keepdims=True)
        m_sc[:, :1] = m_new
        acc[:] = acc[:] * alpha + sum(
            jax.lax.dot_general(
                w.astype(v.dtype), v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)            # (H, vw)
            for w, v in zip(weights, values))

    @pl.when(p == pl.num_programs(1) - 1)
    def _():
        o_ref[0] = (acc[:] / l_sc[:, :1]).astype(o_ref.dtype)


def paged_mla_decode(q, pool, page_tables, seq_lengths, *, value_width: int,
                     scale: float, layer: int | None = None,
                     pages_per_step: int | None = None,
                     interpret: bool | None = None):
    """One absorbed query a head a row over the row's paged latents, read
    in place from the pool.

    q: ``(batch, heads, width)``; pool: ``(pages, width, page_size)`` or
    the stacked ``(layers, pages, width, page_size)`` with a static
    ``layer``; page_tables ``(batch, pages_per_seq)`` int32, short tables
    padded with the scratch page; seq_lengths ``(batch,)``: valid tokens a
    row INCLUDING the new one, whose latent is already in the pool.
    Returns ``(batch, heads, value_width)``: ``sum_s softmax_s(scale * q .
    latent_s) latent_s[:value_width]``, float32 statistics and
    accumulation."""
    if interpret is None:
        interpret = pallas_interpret()
    layered = pool.ndim == 4
    if layered and layer is None:
        raise ValueError("a stacked (layers, pages, ...) pool needs the "
                         "static layer index")
    B, H, W = q.shape
    page = pool.shape[-1]
    n_pages = page_tables.shape[1]
    slots = min(pages_per_step or PAGES_PER_STEP, n_pages)
    steps = -(-n_pages // slots)

    # slot s of step p holds entry p * slots + s of the row's table; past
    # the row's last page a slot stays on the last page it did hold, and a
    # slot that holds none takes page 0 (paged_decode.py has the reasons)
    lengths = jnp.minimum(seq_lengths.astype(jnp.int32), n_pages * page)
    last = jnp.maximum(lengths - 1, 0)[:, None] // page        # (B, 1)
    entry = jnp.arange(steps * slots, dtype=jnp.int32)[None]
    held = jnp.minimum(entry, last - (last - entry) % slots)
    tables = jnp.where(
        held >= 0,
        jnp.take_along_axis(page_tables.astype(jnp.int32),
                            jnp.maximum(held, 0), axis=1), 0)

    lead = (layer,) if layered else ()

    def page_spec(s):
        return pl.BlockSpec(
            (1,) * len(lead) + (1, W, page),
            lambda b, p, pt, sl: lead + (pt[b, p * slots + s], 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, steps),
        in_specs=[pl.BlockSpec((1, H, W), lambda b, p, pt, sl: (b, 0, 0))]
        + [page_spec(s) for s in range(slots)],
        out_specs=pl.BlockSpec((1, H, value_width),
                               lambda b, p, pt, sl: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, value_width), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, page=page, slots=slots,
                          value_width=value_width, layered=layered),
        grid_spec=grid_spec,
        out_shape=_sds((B, H, value_width), q.dtype, q),
        compiler_params=_compiler_params(1),
        name="paged_mla_decode",
        interpret=interpret,
    )(tables, lengths, q, *([pool] * slots))
