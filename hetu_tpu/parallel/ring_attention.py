"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference has NO long-context machinery (SURVEY §5.7: repo-wide grep
finds no ring/ulysses/blockwise anywhere; attention is a materialized QK^T —
reference python/hetu/layers/attention.py).  These are new first-class
capabilities the TPU rebuild adds, following the public ring-attention
formulation (Liu et al., blockwise attention over a device ring) and
DeepSpeed-Ulysses' head↔sequence all-to-all exchange.

Design:
- ``ring_flash_attention`` (default core): K/V chunks circulate the ring
  via ``lax.ppermute``; every (q-chunk, kv-chunk) visit runs the Pallas
  flash kernels, with a ring-level custom vjp that circulates fp32 dK/dV
  accumulators a second time in the backward (see the section comment
  below).  Measured on a v5e at B4 S2048 H16 D64 causal: fwd+bwd 3.6 ms
  vs 17.2 ms for the blockwise-scan core.
- ``ring_attention`` (``impl="blockwise"``): the XLA blockwise-scan core —
  any chunk size or dtype, no 128-alignment requirement; per-step blocks
  are rematerialized in the backward (``jax.checkpoint``) so activation
  memory stays O(local_seq²·heads / ring), not O(seq²).
- ``ulysses_attention``: all_to_all seq-shard → head-shard, run a local
  attention core at full sequence length, all_to_all back.  The local
  core defaults to the Pallas flash kernel.

Both are exposed as ``attn_fn`` factories pluggable into
``layers.MultiHeadAttention`` so one model definition serves sp too.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from hetu_tpu.core.runtime import pallas_interpret

__all__ = [
    "ring_attention", "ring_flash_attention", "ulysses_attention",
    "ring_attn_fn", "ulysses_attn_fn",
]

_NEG = jnp.float32(-1e30)


# --------------------------------------------------------------------------
# ring attention over the Pallas flash kernel
# --------------------------------------------------------------------------
#
# The flash kernel's standalone custom_vjp drops the lse cotangent, which is
# nonzero when blocks combine across the ring — so the ring CANNOT simply
# differentiate through per-block flash calls.  Instead the ring owns its own
# custom_vjp and the lse cotangent never exists:
#
# - forward: K/V chunks circulate (ppermute); each visit runs the flash
#   FORWARD kernel on the (q-chunk, kv-chunk) pair and folds (out_t, lse_t)
#   into an online logsumexp combine.  The GLOBAL lse per q row is saved.
# - backward: with the global lse, exp(QK^T*scale - lse) IS the true global
#   softmax probability of any block, so each block's (dq, dk, dv) is exactly
#   the fused flash backward kernel fed the global (lse, delta).  K/V chunks
#   circulate a second time carrying fp32 dK/dV accumulators with them; after
#   a full cycle each chunk arrives home with contributions from every rank,
#   and delta = rowsum(dO*O) is computed once per rank, amortized over the
#   whole ring.
#
# Chunk relations under causal masking: the diagonal visit (src == r) runs
# the causal kernel, past chunks (src < r) run unmasked, future chunks are
# skipped (their lse contribution is -inf).


def _ring_spec(axis):
    S = lax.axis_size(axis)
    return S, lax.axis_index(axis), [(i, (i + 1) % S) for i in range(S)]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def ring_flash_attention(q, k, v, axis: str = "sp", causal: bool = False,
                         scale: Optional[float] = None,
                         interpret: Optional[bool] = None,
                         block_q: Optional[int] = None,
                         block_k: Optional[int] = None):
    """Ring attention with the Pallas flash kernel as the block core.

    Must run inside a shard_map manual over ``axis``; q, k, v:
    ``[b, s_local, h, d]`` (rank r holds positions
    ``[r*s_local, (r+1)*s_local)``); s_local must divide into 128-aligned
    kernel blocks on TPU.
    """
    out, _ = _ring_flash_fwd(q, k, v, axis, causal, scale, interpret,
                             block_q, block_k)
    return out


def _ring_flash_fwd(q, k, v, axis, causal, scale, interpret,
                    block_q=None, block_k=None):
    from hetu_tpu.ops.pallas.flash import flash_block_fwd

    S, r, ring = _ring_spec(axis)
    b, sq, h, d = q.shape
    sc = scale if scale is not None else 1.0 / (d ** 0.5)
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))  # (b,h,s,d)

    def run_block(kb, vb, block_causal):
        return flash_block_fwd(qt, kb, vb, scale=sc, causal=block_causal,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)

    def step(carry, t):
        kb, vb, m, s, o = carry
        src = (r - t) % S
        if causal:
            case = jnp.where(src == r, 0, jnp.where(src < r, 1, 2))
            out_t, lse_t = lax.switch(
                case,
                [lambda kb, vb: run_block(kb, vb, True),
                 lambda kb, vb: run_block(kb, vb, False),
                 # zeros_like/full_like inherit the carry's varying axes
                 lambda kb, vb: (jnp.zeros_like(o).astype(qt.dtype),
                                 jnp.full_like(m, _NEG))],
                kb, vb)
        else:
            out_t, lse_t = run_block(kb, vb, False)
        m_new = jnp.maximum(m, lse_t)
        c_old = jnp.where(m <= _NEG, 0.0, jnp.exp(m - m_new))
        c_t = jnp.where(lse_t <= _NEG, 0.0, jnp.exp(lse_t - m_new))
        s = s * c_old + c_t
        o = o * c_old + out_t.astype(jnp.float32) * c_t
        kb = lax.ppermute(kb, axis, ring)
        vb = lax.ppermute(vb, axis, ring)
        return (kb, vb, m_new, s, o), None

    # inits derive from qt so they inherit its varying axes (works with
    # or without shard_map's check_vma)
    m0 = jnp.full_like(qt[..., :1], _NEG, dtype=jnp.float32)
    s0 = jnp.zeros_like(m0)
    o0 = jnp.zeros_like(qt, dtype=jnp.float32)
    (kf, vf, m, s, o), _ = lax.scan(step, (kt, vt, m0, s0, o0),
                                    jnp.arange(S))
    s = jnp.maximum(s, 1e-30)
    out = (o / s).astype(q.dtype)          # (b,h,s,d)
    lse = m + jnp.log(s)                    # global logsumexp (b,h,s,1)
    return jnp.swapaxes(out, 1, 2), (q, k, v, out, lse)


def _ring_flash_bwd(axis, causal, scale, interpret, block_q, block_k,
                    res, g):
    from hetu_tpu.ops.pallas.flash import flash_block_bwd

    q, k, v, out_hsd, lse = res            # out_hsd: (b,h,s,d) bf16/f32
    S, r, ring = _ring_spec(axis)
    b, sq, h, d = q.shape
    sc = scale if scale is not None else 1.0 / (d ** 0.5)
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    dot = jnp.swapaxes(g, 1, 2)            # (b,h,s,d)
    delta = jnp.sum(dot.astype(jnp.float32) * out_hsd.astype(jnp.float32),
                    axis=-1, keepdims=True)

    def run_block(kb, vb, block_causal):
        return flash_block_bwd(qt, kb, vb, dot.astype(qt.dtype), lse, delta,
                               scale=sc, causal=block_causal,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)

    def step(carry, t):
        kb, vb, dkb, dvb, dq = carry
        src = (r - t) % S
        if causal:
            case = jnp.where(src == r, 0, jnp.where(src < r, 1, 2))
            dq_t, dk_t, dv_t = lax.switch(
                case,
                [lambda kb, vb: run_block(kb, vb, True),
                 lambda kb, vb: run_block(kb, vb, False),
                 lambda kb, vb: (jnp.zeros_like(dq), jnp.zeros_like(dkb),
                                 jnp.zeros_like(dvb))],
                kb, vb)
        else:
            dq_t, dk_t, dv_t = run_block(kb, vb, False)
        dq = dq + dq_t
        dkb = dkb + dk_t
        dvb = dvb + dv_t
        kb, vb, dkb, dvb = (lax.ppermute(x, axis, ring)
                            for x in (kb, vb, dkb, dvb))
        return (kb, vb, dkb, dvb, dq), None

    z_kv = jnp.zeros_like(kt, dtype=jnp.float32)
    dq0 = jnp.zeros_like(qt, dtype=jnp.float32)
    (kf, vf, dk, dv, dq), _ = lax.scan(
        step, (kt, vt, z_kv, jnp.zeros_like(z_kv), dq0), jnp.arange(S))
    return (jnp.swapaxes(dq, 1, 2).astype(q.dtype),
            jnp.swapaxes(dk, 1, 2).astype(k.dtype),
            jnp.swapaxes(dv, 1, 2).astype(v.dtype))


ring_flash_attention.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_attention(q, k, v, *, axis: str = "sp", causal: bool = False,
                   scale: Optional[float] = None, remat: bool = True):
    """Blockwise ring attention over the ``axis`` mesh ring.

    Must run inside a shard_map manual over ``axis``.  q,k,v:
    ``[b, s_local, h, d]`` — the rank's contiguous sequence chunk (rank r
    holds positions ``[r*s_local, (r+1)*s_local)``).
    """
    S = lax.axis_size(axis)
    r = lax.axis_index(axis)
    b, sq, h, d = q.shape
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    ring = [(i, (i + 1) % S) for i in range(S)]

    # matmuls stay in the INPUT dtype with fp32 accumulation: bf16 feeds
    # the MXU directly (pre-casting q/k/v to fp32 halves matmul throughput
    # and doubles the HBM traffic of the ring's hot loop); softmax
    # statistics and the combine stay fp32 regardless.
    q_pos = r * sq + jnp.arange(sq)

    def block(qb, kb, vb, src):
        """One K/V block folded into the online softmax: returns the block's
        (logits-exp, rowmax, V-weighted partial) in fp32."""
        logits = jnp.einsum("bqhd,bkhd->bhqk", qb, kb,
                            preferred_element_type=jnp.float32) * scale
        if causal:
            k_pos = src * sq + jnp.arange(sq)
            cm = q_pos[:, None] >= k_pos[None, :]
            logits = jnp.where(cm[None, None], logits, _NEG)
        m = jnp.max(logits, axis=-1)                       # [b,h,q]
        p = jnp.exp(logits - m[..., None])
        # fully-masked rows: zero them instead of exp(-1e30-(-1e30))=1
        p = jnp.where((m == _NEG)[..., None], 0.0, p)
        l = jnp.sum(p, axis=-1)                            # [b,h,q]
        o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(vb.dtype), vb,
                       preferred_element_type=jnp.float32)
        return m, l, o

    if remat:
        block = jax.checkpoint(block)

    def step(carry, t):
        kb, vb, m, l, o = carry
        src = (r - t) % S  # whose block we hold at step t
        bm, bl, bo = block(q, kb, vb, src)
        m_new = jnp.maximum(m, bm)
        c_old = jnp.where(m == _NEG, 0.0, jnp.exp(m - m_new))
        c_new = jnp.where(bm == _NEG, 0.0, jnp.exp(bm - m_new))
        l = l * c_old + bl * c_new
        o = o * c_old.transpose(0, 2, 1)[..., None] \
            + bo * c_new.transpose(0, 2, 1)[..., None]
        kb = lax.ppermute(kb, axis, ring)
        vb = lax.ppermute(vb, axis, ring)
        return (kb, vb, m_new, l, o), None

    # inits derive from q so they inherit its varying manual axes (the
    # wrapper is manual over every mesh axis, not just the ring axis)
    bhq = jnp.swapaxes(q[..., 0], 1, 2).astype(jnp.float32) * 0
    m0 = bhq + _NEG
    l0 = bhq
    o0 = jnp.zeros_like(q, dtype=jnp.float32)
    carry0 = (k, v, m0, l0, o0)
    (kf, vf, m, l, o), _ = lax.scan(step, carry0, jnp.arange(S))
    l = jnp.maximum(l, 1e-30)
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ulysses_attention(q, k, v, *, axis: str = "sp", causal: bool = False,
                      mask=None, inner_fn: Optional[Callable] = None):
    """DeepSpeed-Ulysses: a2a seq→heads, full-length local attention, a2a
    back.  Must run inside a shard_map manual over ``axis``; heads must be
    divisible by the axis size.  ``inner_fn(q,k,v,mask,causal)`` is the
    local attention core (default: dense fp32-softmax; plug the Pallas
    flash kernel here)."""
    from hetu_tpu.layers.attention import dot_product_attention
    inner = inner_fn or dot_product_attention

    sp = lax.axis_size(axis)
    h = q.shape[2]
    if h % sp:
        raise ValueError(f"{h} heads not divisible over sp={sp}")
    # [b, s/sp, h, d] -> [b, s, h/sp, d]
    swap = lambda t: lax.all_to_all(t, axis, split_axis=2, concat_axis=1,
                                    tiled=True)
    unswap = lambda t: lax.all_to_all(t, axis, split_axis=1, concat_axis=2,
                                      tiled=True)
    out = inner(swap(q), swap(k), swap(v), mask, causal=causal)
    return unswap(out)


def _sp_sharded(fn_inner, mesh: Mesh, axis: str, check_vma: bool = True,
                head_axis: Optional[str] = None):
    """Wrap an inside-shard_map attention core into a drop-in ``attn_fn`` for
    MultiHeadAttention: qkv arrive seq-sharded over ``axis`` (GSPMD side),
    manual only over ``axis``.  ``check_vma=False`` is needed when the core
    runs Pallas kernels in interpreter mode (CPU tests): the interpreter's
    internal grid slicing mixes varying and unvarying values, which the
    vma checker rejects.

    ``head_axis`` composes SP × TP: with Megatron column-parallel qkv
    (``qkv_three_heads`` → tp) the activations reaching attention are
    already head-sharded over tp, and every attention core here is
    per-head independent — so the composition is an in_specs entry, not a
    new algorithm: each tp rank rings (or all-to-alls) only its own head
    slice over ``axis``.  Without the entry, shard_map does NOT error on
    the mismatch — it RESHARDS, silently all-gathering the tp-sharded
    heads on entry and re-scattering on exit every layer (a quiet perf
    cliff, which is why the default stays None only for meshes with no tp
    axis in play)."""

    # Manualize EVERY mesh axis: leaving axes "auto" makes XLA try to
    # partition the region automatically, which Mosaic kernels refuse
    # ("Mosaic kernels cannot be automatically partitioned") even for
    # size-1 axes.  Batch rides the dp axis when the mesh has one.
    if head_axis is not None and head_axis not in mesh.axis_names:
        raise ValueError(f"head_axis {head_axis!r} not in mesh axes "
                         f"{mesh.axis_names}")
    batch_axis = "dp" if "dp" in mesh.axis_names else None
    spec = P(batch_axis, axis, head_axis)

    def attn_fn(q, k, v, mask=None, *, causal: bool = False):
        if mask is not None:
            raise NotImplementedError(
                "sequence-parallel attention supports causal/full, not "
                "padding masks yet"
            )

        def inner(q, k, v):
            return fn_inner(q, k, v, causal=causal)

        return jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=spec,
            out_specs=spec,
            check_vma=check_vma,
        )(q, k, v)

    attn_fn.spec = spec  # introspectable by tests / dryrun assertions
    return attn_fn


def ring_attn_fn(mesh: Mesh, axis: str = "sp", *, remat: bool = True,
                 impl: str = "flash", interpret: Optional[bool] = None,
                 block_q: Optional[int] = None,
                 block_k: Optional[int] = None,
                 head_axis: Optional[str] = None):
    """attn_fn running ring attention over ``axis``; plug into
    ``MultiHeadAttention(attn_fn=...)``.

    ``impl="flash"`` (default) runs the Pallas flash kernel per block with
    the ring-level custom vjp; ``impl="blockwise"`` keeps the XLA
    blockwise-scan core (any chunk size/dtype, no 128-alignment needs).
    ``head_axis="tp"`` composes with Megatron tensor parallelism: heads
    stay tp-sharded through the ring (see ``_sp_sharded``).
    """
    if impl == "flash":
        interp = interpret if interpret is not None else pallas_interpret()
        core = lambda q, k, v, causal: ring_flash_attention(  # noqa: E731
            q, k, v, axis, causal, None, interp, block_q, block_k)
        return _sp_sharded(core, mesh, axis, check_vma=not interp,
                           head_axis=head_axis)
    if impl == "blockwise":
        core = lambda q, k, v, causal: ring_attention(  # noqa: E731
            q, k, v, axis=axis, causal=causal, remat=remat)
        return _sp_sharded(core, mesh, axis, head_axis=head_axis)
    raise ValueError(f"unknown ring impl {impl!r}")


def ulysses_attn_fn(mesh: Mesh, axis: str = "sp", *,
                    inner_fn: Optional[Callable] = None,
                    head_axis: Optional[str] = None):
    """attn_fn running Ulysses head/seq all-to-all attention over ``axis``.

    The local core defaults to the Pallas flash kernel (each rank holds the
    full sequence for its head slice after the all-to-all, exactly the
    kernel's sweet spot); pass ``inner_fn=dot_product_attention`` for the
    dense fp32-softmax core.  With ``head_axis="tp"`` the all-to-all
    redistributes only the rank's tp-local head slice, so local heads
    (num_heads / tp) must be divisible by the ``axis`` size.
    """
    if inner_fn is None:
        from hetu_tpu.ops.pallas import flash_attn_fn
        inner_fn = flash_attn_fn()
    # interpreted Pallas cores (CPU tests) trip shard_map's vma checker
    # regardless of who supplied the core
    interp = pallas_interpret()
    return _sp_sharded(
        lambda q, k, v, causal: ulysses_attention(
            q, k, v, axis=axis, causal=causal, inner_fn=inner_fn
        ),
        mesh, axis, check_vma=not interp, head_axis=head_axis,
    )
