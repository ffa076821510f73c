"""Paged decode attention (Pallas/Mosaic): attend IN PLACE over the pool.

The serving decode step used to route attention through XLA gather/scatter:
every step materialized a contiguous ``(L, batch, max_len, H, D)`` view of
the paged KV pool (``serve/kv_cache.gather_views``) before attending — the
dominant per-token HBM traffic at long context, since the whole history is
re-copied to attend over one new token.  This kernel is the PagedAttention
insight (vLLM, SOSP'23) composed with flash-style online softmax
(FlashAttention, NeurIPS'22): the grid walks each sequence's page table and
DMAs K/V pages **directly from the pool** at their physical indices, so no
contiguous view ever exists.

Schedule:
- grid ``(batch, head_blocks, pages_per_seq)``, pages innermost.  The page
  table and per-row sequence lengths ride as scalar-prefetch operands, so
  each step's BlockSpec index map picks the PHYSICAL page
  (``tables[b, p]``) — the gather happens in the DMA descriptor, not in
  HBM.
- VMEM scratch carries the running max ``m``, normalizer ``l`` and fp32
  output accumulator across pages (the flash forward recurrence); the
  output flushes on the last page step.
- masking: position ``p*page_size + i`` is live iff ``< seq_lengths[b]``.
  Pages entirely at/past the length (including the scratch-page-0 padding
  of short page tables) are skipped under ``pl.when`` — their contents are
  never read into the math, so a poisoned scratch page (NaN) cannot
  perturb any output (tested).
- one new token per sequence (the decode shape): q is ``(batch, heads,
  head_dim)``.  Prefill keeps the bucketed gather path — it runs once per
  request; decode runs once per generated token.

The pool may be passed per layer ``(pages, page_size, H, D)`` or as the
whole stacked ``(layers, pages, page_size, H, D)`` array with a static
``layer`` — the stacked form lets the serving engine thread ONE array pair
through all blocks with no per-layer slicing copies.

``head_block`` (heads loaded per grid step — VMEM footprint vs grid
parallelism) consults the autotune DB (``autotune_paged_decode``) and
defaults to all heads.  On the CPU the kernel runs in interpreter mode
(tests), so the same code path is exercised everywhere.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hetu_tpu.core.runtime import pallas_interpret
from hetu_tpu.ops.pallas.flash import _compiler_params, _sds

__all__ = ["paged_decode_attention"]

_NEG_INF = -1e30  # finite: -inf - -inf = nan would poison alpha/exp paths


def _kernel(pt_ref, sl_ref, q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc, *,
            scale, page, layered):
    b, p = pl.program_id(0), pl.program_id(2)
    n_pages = pl.num_programs(2)

    @pl.when(p == 0)
    def _():
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc[:] = jnp.zeros_like(acc)

    seq_len = sl_ref[b]
    start = p * page
    # a page whose first position is at/past the row's length contributes
    # nothing — this covers both the tail of the last real page's
    # successor AND the scratch-page-0 padding of short page tables, so
    # garbage (even NaN) in those pages never reaches the math
    live = start < seq_len

    @pl.when(live)
    def _():
        # One query per head makes both products matrix-VECTOR work, so
        # they run on the VPU in the pool's own (page, heads, D) layout:
        # Mosaic's matmul wants the batch (head) dimension leading on both
        # operands, and a per-page transpose of K and V to get it there
        # would cost more than the products themselves.
        q = q_ref[0].astype(jnp.float32)                       # (hb, D)
        k = (k_ref[0, 0] if layered else k_ref[0]).astype(jnp.float32)
        v = (v_ref[0, 0] if layered else v_ref[0]).astype(jnp.float32)
        # scores (page, hb, 1): per-head q . k over D
        s = jnp.sum(q[None] * k, axis=-1, keepdims=True) * scale
        valid = start + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0) < seq_len
        s = jnp.where(valid, s, _NEG_INF)
        # a masked position's weight underflows to exactly 0.0, but IEEE
        # 0*NaN = NaN: zero the dead V rows too, so garbage in the
        # unwritten tail of a row's LAST page can never reach the PV
        # product (the K side is covered by the where above)
        v = jnp.where(valid, v, 0.0)
        m_prev = m_sc[:, :1]                                   # (hb, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
        pw = jnp.exp(s - m_new[None])                          # (page, hb, 1)
        alpha = jnp.exp(m_prev - m_new)
        l_sc[:, :1] = alpha * l_sc[:, :1] + jnp.sum(pw, axis=0)
        m_sc[:, :1] = m_new
        acc[:] = acc[:] * alpha + jnp.sum(pw * v, axis=0)      # (hb, D)

    @pl.when(p == n_pages - 1)
    def _():
        o_ref[0] = (acc[:] / l_sc[:, :1]).astype(o_ref.dtype)


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
                           interpret: bool | None = None):
    """Flash-style decode attention of one new query per sequence over its
    paged KV history, read in place from the pool.

    q: ``(batch, heads, head_dim)`` — the new token's queries.
    k_pool/v_pool: ``(pages, page_size, heads, head_dim)`` or the stacked
    ``(layers, pages, ...)`` form with a static ``layer``.
    page_tables: ``(batch, pages_per_seq)`` int32 physical page indices,
    short tables padded with the scratch page (``kv_cache.SCRATCH_PAGE``).
    seq_lengths: ``(batch,)`` int32 — valid tokens per row INCLUDING the
    new token (whose K/V must already be written into the pool).
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
    page = k_pool.shape[-3]
    n_pages = page_tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    hb = _head_block(H, D, page, head_block)

    if layered:
        kv_spec = pl.BlockSpec(
            (1, 1, page, hb, D),
            lambda b, h, p, pt, sl: (layer, pt[b, p], 0, h, 0))
    else:
        kv_spec = pl.BlockSpec(
            (1, page, hb, D), lambda b, h, p, pt, sl: (pt[b, p], 0, h, 0))
    q_spec = pl.BlockSpec((1, hb, D), lambda b, h, p, pt, sl: (b, h, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H // hb, n_pages),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((hb, 128), jnp.float32),
            pltpu.VMEM((hb, 128), jnp.float32),
            pltpu.VMEM((hb, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, page=page, layered=layered),
        grid_spec=grid_spec,
        out_shape=_sds(q.shape, q.dtype, q),
        compiler_params=_compiler_params(2),
        interpret=interpret,
    )(page_tables.astype(jnp.int32), seq_lengths.astype(jnp.int32),
      q, k_pool, v_pool)
