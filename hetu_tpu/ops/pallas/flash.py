"""Flash attention for TPU (Pallas/Mosaic).

Replaces the reference's materialized QK^T softmax attention
(python/hetu/layers/attention.py:5) with a fused online-softmax kernel so the
(seq, seq) score matrix never touches HBM — the MFU-critical kernel for the
BERT/GPT baselines (BASELINE.md north star).

Design (FlashAttention-2 schedule on the MXU):
- forward: grid (batch, heads, q_blocks, kv_blocks), kv innermost; VMEM
  scratch carries the running max ``m``, normalizer ``l`` and fp32 output
  accumulator across kv blocks; output and logsumexp are flushed on the last
  kv step.
- backward (fused, the common path): one pass with grid (batch, heads,
  kv_blocks, q_blocks): dK/dV accumulate in VMEM scratch across the inner q
  loop, while each (j, i) step writes its dQ contribution ``dS @ K`` to a
  per-kv-block partial summed outside the kernel (a no-op when one kv block
  covers the sequence).  Probabilities are recomputed ONCE per block pair —
  half the recompute/exp work of the classic two-kernel split, which
  measured ~0.9 ms per kernel at BERT-large seq-512 shape on a v5e.
  ``delta = rowsum(dO * O)`` is computed in-kernel from the O block (the
  separate XLA reduction was another ~0.4 ms/layer of badly-laid-out
  traffic).
- backward (long-sequence fallback, kv blocks > _MAX_DQ_PARTIALS): the
  fp32 dQ partials would cost nk x |Q| memory, so the classic two-kernel
  split runs instead — a q-innermost pass for dK/dV and a kv-innermost
  pass accumulating dQ in VMEM.  Sequences that long normally run under
  ring attention (parallel/ring_attention.py), which chunks kv per device,
  so this path is rare.
- fp32 statistics and accumulation regardless of input dtype (bf16 inputs
  feed the MXU directly; probabilities are cast back to the value dtype for
  the PV matmul, matching the reference's softmax-in-compute-dtype behavior).
- causal masking skips fully-masked kv blocks; ragged seq lengths are handled
  by padding to block multiples and masking padded kv columns (padded q rows
  produce garbage that is sliced off, and contribute zero to gradients
  because their dO is zero).

- grouped heads and a window (forward only, as prefill uses it: static
  branches of ``_fwd_kernel`` and ``_fwd_call``, which with equal heads and
  no window trace the program they always did): K and V may have fewer
  heads than Q, a divisor of them, and query head ``h`` reads KV head ``h //
  group`` through the block's index map, so nothing is repeated in HBM.  With ``window`` query ``t`` sees
  key ``s`` iff ``s <= t`` and ``t - s < window``; the kv dimension of the
  grid is only as long as the blocks a q block can see (the window's and the
  diagonal's), counted from the q block's first live kv block, so blocks
  wholly outside the window are no grid steps at all and those above the
  diagonal keep the last live block's index and move no bytes.  No backward
  is written for this form: training with a window is left.

On the CPU the kernels run in interpreter mode (tests), so the same code path
is exercised everywhere (``core.runtime.pallas_interpret``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hetu_tpu.core.runtime import pallas_interpret

__all__ = ["flash_attention", "flash_attn_fn",
           "flash_block_fwd", "flash_block_bwd"]

_NEG_INF = -1e30  # finite: -inf - -inf = nan would poison alpha/exp paths
_MAX_DQ_PARTIALS = 8  # fused bwd keeps nk fp32 dQ partials; beyond, two-pass


def _sds(shape, dtype, like):
    """ShapeDtypeStruct carrying the varying-axes (vma) signature of
    ``like`` — required when the kernel runs inside a shard_map manual
    region (ring chunks, the Ulysses local core) under check_vma."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _compiler_params(n_parallel: int, arbitrary: int = 1,
                     vmem_limit_bytes: int | None = None):
    """Dimension semantics: ``n_parallel`` parallel dims followed by
    ``arbitrary`` sequential ones (0 for grids whose dims are all
    independent — Mosaic megacore partitioning can only split dims
    declared parallel).  ``vmem_limit_bytes`` raises the kernel's scoped
    VMEM above the compiler's 16 MiB default."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * n_parallel
        + ("arbitrary",) * arbitrary,
        vmem_limit_bytes=vmem_limit_bytes)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _block_mask(block_q, block_k, kv_len, causal, i, j):
    """(block_q, block_k) bool mask: kv padding columns off; with causal,
    cols above the diagonal (absolute positions via block indices i, j)
    off."""
    col = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = col < kv_len
    if causal:
        row = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        mask = jnp.logical_and(mask, col <= row)
    return mask


def _first_kv_block(i, block_q, block_k, window):
    """The first kv block that q block ``i`` sees any of."""
    return jnp.maximum(i * block_q - (window - 1), 0) // block_k


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest, scale, causal, block_q,
                block_k, kv_len, padded, window=None, kv_blocks=None):
    """``rest``: ``lse_ref`` where the call has that output (the forms that
    are differentiated), then the scratch ``acc, m_sc, l_sc``.  With
    ``window`` the grid's last dimension counts steps from the q block's
    first live kv block, of ``kv_blocks`` in all (module docstring)."""
    lse_ref = rest[0] if len(rest) == 4 else None
    acc, m_sc, l_sc = rest[-3:]
    i, step = pl.program_id(2), pl.program_id(3)
    n_steps = pl.num_programs(3)
    j, nk = step, n_steps
    if window is not None:
        j, nk = _first_kv_block(i, block_q, block_k, window) + step, kv_blocks

    @pl.when(step == 0)
    def _():
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc[:] = jnp.zeros_like(acc)

    # causal: kv block strictly above the diagonal band contributes nothing
    live = (j * block_k <= i * block_q + block_q - 1) if causal else True
    if window is not None:      # nor does a step past the last kv block
        live = jnp.logical_and(live, j < nk)

    def accumulate(s):
        m_prev = m_sc[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_sc[:, :1] = alpha * l_sc[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        m_sc[:, :1] = m_new
        acc[:] = acc[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0, :, :], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    def scores():
        return jax.lax.dot_general(
            q_ref[0, 0, :, :], k_ref[0, 0, :, :], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    # mask work (two iotas + where over (block_q, block_k)) is on the hot
    # path; only diagonal-crossing causal blocks and the final padded kv
    # block need it — interior blocks take the maskless fast path.
    # block contains a masked (col > row) element iff its max col exceeds
    # its MIN row
    crosses = (jnp.logical_and(live, j * block_k + block_k - 1
                               > i * block_q)
               if causal else False)
    needs_pad = (j == nk - 1) if padded else False
    masked = jnp.logical_or(crosses, needs_pad)
    if window is not None:
        # or if its first key is out of the last query's window
        masked = jnp.logical_or(
            masked, i * block_q + block_q - 1 - j * block_k >= window)

    @pl.when(jnp.logical_and(live, jnp.logical_not(masked)))
    def _():
        accumulate(scores())

    @pl.when(jnp.logical_and(live, masked))
    def _():
        mask = _block_mask(block_q, block_k, kv_len, causal, i, j)
        if window is not None:
            row = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            col = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = jnp.logical_and(mask, row - col < window)
        accumulate(jnp.where(mask, scores(), _NEG_INF))

    @pl.when(step == n_steps - 1)
    def _():
        l = l_sc[:, :1]
        o_ref[0, 0, :, :] = (acc[:] / l).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[0, 0, :, :] = m_sc[:, :1] + jnp.log(l)


def _q_spec(block_q, D):
    return pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0))


def _kv_spec(block_k, D):
    return pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h, j, 0))


def _fwd_one_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                    scale, causal, block_q, block_k, kv_len, padded):
    # single kv block covers the sequence: plain one-pass softmax, no
    # scratch round trips, no online-combine machinery — measured 3.5x
    # the general kernel's forward at BERT-large seq-512 shape (the
    # scratch init/flush + pl.when plumbing cost ~0.67 of its 0.93 ms)
    i = pl.program_id(2)
    s = jax.lax.dot_general(
        q_ref[0, 0, :, :], k_ref[0, 0, :, :], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if causal or padded:
        s = jnp.where(_block_mask(block_q, block_k, kv_len, causal, i, 0),
                      s, _NEG_INF)
    m = jnp.max(s, axis=1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=1, keepdims=True)
    o = jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0, 0, :, :], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    o_ref[0, 0, :, :] = (o / l).astype(o_ref.dtype)
    lse_ref[0, 0, :, :] = m + jnp.log(l)


def _fwd_call(q, k, v, scale, causal, block_q, block_k, kv_len, interpret,
              window=None):
    """``(out, lse)``.  K and V with fewer heads than Q, or a ``window``,
    make the form that prefill uses: ``lse`` is ``None`` (nothing is
    differentiated through it), a kv block is fetched through ``h // group``
    and, past the diagonal, not at all, and the call has a name of its
    own."""
    B, H, Sq, D = q.shape
    Dv = v.shape[-1]   # v and the output may be narrower than q and k
    Sk = k.shape[2]
    nq, nk = Sq // block_q, Sk // block_k
    group = H // k.shape[1]
    plain = group == 1 and window is None
    if nk == 1 and plain:
        out, lse = pl.pallas_call(
            functools.partial(
                _fwd_one_kernel, scale=scale, causal=causal,
                block_q=block_q, block_k=block_k, kv_len=kv_len,
                padded=(Sk != kv_len)),
            grid=(B, H, nq),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, D),
                             lambda b, h, i: (b, h, i, 0)),
                pl.BlockSpec((1, 1, block_k, D),
                             lambda b, h, i: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, block_k, Dv),
                             lambda b, h, i: (b, h, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block_q, Dv),
                             lambda b, h, i: (b, h, i, 0)),
                pl.BlockSpec((1, 1, block_q, 1),
                             lambda b, h, i: (b, h, i, 0)),
            ],
            out_shape=[
                _sds((B, H, Sq, Dv), q.dtype, q),
                _sds((B, H, Sq, 1), jnp.float32, q),
            ],
            compiler_params=_compiler_params(3, arbitrary=0),
            name="flash_fwd_one",
            interpret=interpret,
        )(q, k, v)
        return out, lse
    steps = nk
    if window is not None:
        # kv blocks that hold any of block_q + window - 1 consecutive
        # positions, wherever those begin: blocks wholly outside the
        # window are no grid steps at all
        steps = min(nk, (block_q + window - 2) // block_k + 2)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, kv_len=kv_len, padded=(Sk != kv_len),
        **({} if window is None else {"window": window, "kv_blocks": nk}))

    def kv_index(b, h, i, step):
        j, last = step, nk - 1
        if window is not None:
            j = _first_kv_block(i, block_q, block_k, window) + step
        if causal:      # a block past the diagonal: the last live one's
            last = jnp.minimum(last, (i * block_q + block_q - 1) // block_k)
        return (b, h // group, jnp.minimum(j, last), 0)

    def kv_spec(width):
        return _kv_spec(block_k, width) if plain else pl.BlockSpec(
            (1, 1, block_k, width), kv_index)

    out_specs = [_q_spec(block_q, Dv), pl.BlockSpec(
        (1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0))]
    out_shape = [_sds((B, H, Sq, Dv), q.dtype, q),
                 _sds((B, H, Sq, 1), jnp.float32, q)]
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, steps),
        in_specs=[_q_spec(block_q, D), kv_spec(D), kv_spec(Dv)],
        out_specs=out_specs if plain else out_specs[:1],
        out_shape=out_shape if plain else out_shape[:1],
        scratch_shapes=[
            pltpu.VMEM((block_q, Dv), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=_compiler_params(3),
        name=("flash_fwd" if plain else
              "flash_grouped" if window is None else "flash_window"),
        interpret=interpret,
    )(q, k, v)
    return out if plain else (out[0], None)


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------

def _recompute_p(q_ref, k_ref, lse_ref, *, scale, causal, block_q, block_k,
                 kv_len, i, j):
    """exp(QK^T*scale - lse) with padding/causal masking; (block_q, block_k)."""
    q = q_ref[0, 0, :, :]
    k = k_ref[0, 0, :, :]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    s = jnp.where(_block_mask(block_q, block_k, kv_len, causal, i, j),
                  s, _NEG_INF)
    return jnp.exp(s - lse_ref[0, 0, :, :])


def _delta(do_ref, o_ref):
    return jnp.sum(do_ref[0, 0, :, :].astype(jnp.float32)
                   * o_ref[0, 0, :, :].astype(jnp.float32),
                   axis=1, keepdims=True)


def _block_grads(p, q_ref, k_ref, v_ref, do_ref, d, scale):
    """(dv, dk, dq) fp32 contributions of one block pair given the
    probabilities ``p`` and per-row ``d = rowsum(dO*O)`` — the shared
    gradient math of every backward kernel."""
    do = do_ref[0, 0, :, :]
    v = v_ref[0, 0, :, :]
    q = q_ref[0, 0, :, :]
    k = k_ref[0, 0, :, :]
    dv = jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = (p * (dp - d) * scale).astype(q.dtype)
    dk = jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dq = jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return dv, dk, dq


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, od_ref, lse_ref,
                      dk_ref, dv_ref, dq_ref, dk_acc, dv_acc, *,
                      scale, causal, block_q, block_k, kv_len,
                      delta_in=False):
    # grid (B, H, nk, nq) — q innermost.  dK/dV accumulate in scratch for
    # kv block j; the dQ contribution of (j, i) is one matmul, written to
    # its own partial slot and reduced over j outside the kernel.
    j, i = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(i == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    live = (j * block_k <= i * block_q + block_q - 1) if causal else True

    @pl.when(live)
    def _():
        p = _recompute_p(q_ref, k_ref, lse_ref, scale=scale, causal=causal,
                         block_q=block_q, block_k=block_k, kv_len=kv_len,
                         i=i, j=j)
        d = od_ref[0, 0, :, :] if delta_in else _delta(do_ref, od_ref)
        dv, dk, dq = _block_grads(p, q_ref, k_ref, v_ref, do_ref, d, scale)
        dv_acc[:] += dv
        dk_acc[:] += dk
        dq_ref[0, 0, 0, :, :] = dq

    if causal:  # dead (j, i) pairs still own a dQ partial slot: zero it
        @pl.when(jnp.logical_not(live))
        def _():
            dq_ref[0, 0, 0, :, :] = jnp.zeros_like(dq_ref[0, 0, 0, :, :])

    @pl.when(i == nq - 1)
    def _():
        dk_ref[0, 0, :, :] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_one_kernel(q_ref, k_ref, v_ref, do_ref, od_ref, lse_ref,
                    dk_ref, dv_ref, dq_ref, *,
                    scale, causal, block_q, block_k, kv_len,
                    delta_in=False):
    # one (q, kv) block pair covers the whole sequence: every gradient is
    # a single contribution — no scratch accumulators, no partial slots
    # (the same machinery-vs-math win as _fwd_one_kernel)
    p = _recompute_p(q_ref, k_ref, lse_ref, scale=scale, causal=causal,
                     block_q=block_q, block_k=block_k, kv_len=kv_len,
                     i=0, j=0)
    d = od_ref[0, 0, :, :] if delta_in else _delta(do_ref, od_ref)
    dv, dk, dq = _block_grads(p, q_ref, k_ref, v_ref, do_ref, d, scale)
    dv_ref[0, 0, :, :] = dv.astype(dv_ref.dtype)
    dk_ref[0, 0, :, :] = dk.astype(dk_ref.dtype)
    dq_ref[0, 0, :, :] = dq.astype(dq_ref.dtype)


def _bwd_one_call(q, k, v, do, od, lse, *, scale, causal, block_q, block_k,
                  kv_len, interpret, delta_in, out_dtypes):
    """Single-block-pair backward dispatch; ``od`` is O (delta_in=False)
    or the precomputed delta (delta_in=True)."""
    B, H, Sq, D = q.shape
    Dv = v.shape[-1]   # v and the output may be narrower than q and k
    spec_q = pl.BlockSpec((1, 1, block_q, D), lambda b, h: (b, h, 0, 0))
    spec_kv = pl.BlockSpec((1, 1, block_k, D), lambda b, h: (b, h, 0, 0))
    spec_do = pl.BlockSpec((1, 1, block_q, Dv), lambda b, h: (b, h, 0, 0))
    spec_v = pl.BlockSpec((1, 1, block_k, Dv), lambda b, h: (b, h, 0, 0))
    spec_od = (pl.BlockSpec((1, 1, block_q, 1), lambda b, h: (b, h, 0, 0))
               if delta_in else spec_do)
    spec_lse = pl.BlockSpec((1, 1, block_q, 1), lambda b, h: (b, h, 0, 0))
    dk_t, dv_t, dq_t = out_dtypes
    return pl.pallas_call(
        functools.partial(_bwd_one_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, kv_len=kv_len,
                          delta_in=delta_in),
        grid=(B, H),
        in_specs=[spec_q, spec_kv, spec_v, spec_do, spec_od, spec_lse],
        out_specs=[spec_kv, spec_v, spec_q],
        out_shape=[
            _sds(k.shape, dk_t, k),
            _sds(v.shape, dv_t, v),
            _sds(q.shape, dq_t, q),
        ],
        compiler_params=_compiler_params(2, arbitrary=0),
        name="flash_bwd_one",
        interpret=interpret,
    )(q, k, v, do, od, lse)


def _bwd_kv_kernel(q_ref, k_ref, v_ref, do_ref, delta_ref, lse_ref,
                   dk_ref, dv_ref, dk_acc, dv_acc, *,
                   scale, causal, block_q, block_k, kv_len):
    # long-seq fallback: dK/dV only (q innermost).  delta arrives
    # precomputed (one XLA reduction) — recomputing it in-kernel would
    # re-read the O block once per inner step, and this path is chosen
    # exactly when the inner trip count nk is large.
    j, i = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(i == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    live = (j * block_k <= i * block_q + block_q - 1) if causal else True

    @pl.when(live)
    def _():
        p = _recompute_p(q_ref, k_ref, lse_ref, scale=scale, causal=causal,
                         block_q=block_q, block_k=block_k, kv_len=kv_len,
                         i=i, j=j)
        do = do_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        q = q_ref[0, 0, :, :]
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0, :, :]) * scale
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _():
        dk_ref[0, 0, :, :] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, delta_ref, lse_ref,
                   dq_ref, dq_acc, *, scale, causal, block_q, block_k,
                   kv_len):
    # long-seq fallback: dQ only (kv innermost, accumulate in VMEM)
    i, j = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    live = (j * block_k <= i * block_q + block_q - 1) if causal else True

    @pl.when(live)
    def _():
        p = _recompute_p(q_ref, k_ref, lse_ref, scale=scale, causal=causal,
                         block_q=block_q, block_k=block_k, kv_len=kv_len,
                         i=i, j=j)
        do = do_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0, :, :]) * scale
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _():
        dq_ref[0, 0, :, :] = dq_acc[:].astype(dq_ref.dtype)


def _bwd(scale, causal, block_q, block_k, kv_len, interpret, res, g):
    q, k, v, out, lse = res
    do, _ = g  # cotangent of (out, lse); lse cotangent unused
    B, H, Sq, D = q.shape
    Dv = v.shape[-1]   # v and the output may be narrower than q and k
    Sk = k.shape[2]
    nq, nk = Sq // block_q, Sk // block_k

    if nq == 1 and nk == 1:
        dk, dv, dq = _bwd_one_call(
            q, k, v, do, out, lse, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, kv_len=kv_len,
            interpret=interpret, delta_in=False,
            out_dtypes=(k.dtype, v.dtype, q.dtype))
        return dq, dk, dv

    bwd_q_spec = pl.BlockSpec((1, 1, block_q, D),
                              lambda b, h, j, i: (b, h, i, 0))
    bwd_kv_spec = pl.BlockSpec((1, 1, block_k, D),
                               lambda b, h, j, i: (b, h, j, 0))
    bwd_do_spec = pl.BlockSpec((1, 1, block_q, Dv),
                               lambda b, h, j, i: (b, h, i, 0))
    bwd_v_spec = pl.BlockSpec((1, 1, block_k, Dv),
                              lambda b, h, j, i: (b, h, j, 0))
    bwd_lse_spec = pl.BlockSpec((1, 1, block_q, 1),
                                lambda b, h, j, i: (b, h, i, 0))
    in_specs = [bwd_q_spec, bwd_kv_spec, bwd_v_spec, bwd_do_spec,
                bwd_do_spec, bwd_lse_spec]
    kv_scratch = [
        pltpu.VMEM((block_k, D), jnp.float32),
        pltpu.VMEM((block_k, Dv), jnp.float32),
    ]

    if nk <= _MAX_DQ_PARTIALS:
        dk, dv, dq_part = pl.pallas_call(
            functools.partial(_bwd_fused_kernel, scale=scale, causal=causal,
                              block_q=block_q, block_k=block_k,
                              kv_len=kv_len),
            grid=(B, H, nk, nq),
            in_specs=in_specs,
            out_specs=[
                bwd_kv_spec,
                bwd_v_spec,
                pl.BlockSpec((1, 1, 1, block_q, D),
                             lambda b, h, j, i: (j, b, h, i, 0)),
            ],
            out_shape=[
                _sds(k.shape, k.dtype, k),
                _sds(v.shape, v.dtype, v),
                _sds((nk, B, H, Sq, D), jnp.float32, q),
            ],
            scratch_shapes=kv_scratch,
            compiler_params=_compiler_params(3),
            name="flash_bwd_fused",
            interpret=interpret,
        )(q, k, v, do, out, lse)
        dq = (dq_part[0] if nk == 1
              else jnp.sum(dq_part, axis=0)).astype(q.dtype)
        return dq, dk, dv

    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)
    fb_in_specs = [bwd_q_spec, bwd_kv_spec, bwd_v_spec, bwd_do_spec,
                   bwd_lse_spec, bwd_lse_spec]
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_kv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, kv_len=kv_len),
        grid=(B, H, nk, nq),
        in_specs=fb_in_specs,
        out_specs=[bwd_kv_spec, bwd_v_spec],
        out_shape=[
            _sds(k.shape, k.dtype, k),
            _sds(v.shape, v.dtype, v),
        ],
        scratch_shapes=kv_scratch,
        compiler_params=_compiler_params(3),
        name="flash_bwd_dkv",
        interpret=interpret,
    )(q, k, v, do, delta, lse)

    dq_lse_spec = pl.BlockSpec((1, 1, block_q, 1),
                               lambda b, h, i, j: (b, h, i, 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, kv_len=kv_len),
        grid=(B, H, nq, nk),
        in_specs=[_q_spec(block_q, D), _kv_spec(block_k, D),
                  _kv_spec(block_k, Dv), _q_spec(block_q, Dv),
                  dq_lse_spec, dq_lse_spec],
        out_specs=_q_spec(block_q, D),
        out_shape=_sds(q.shape, q.dtype, q),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=_compiler_params(3),
        name="flash_bwd_dq",
        interpret=interpret,
    )(q, k, v, do, delta, lse)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, scale, causal, block_q, block_k, kv_len, interpret):
    return _fwd_call(q, k, v, scale, causal, block_q, block_k, kv_len,
                     interpret)


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, kv_len, interpret):
    out, lse = _fwd_call(q, k, v, scale, causal, block_q, block_k, kv_len,
                         interpret)
    return (out, lse), (q, k, v, out, lse)


_flash.defvjp(_flash_fwd, _bwd)


# --------------------------------------------------------------------------
# block-level entry points (ring attention)
# --------------------------------------------------------------------------
#
# Ring attention (parallel/ring_attention.py) owns its OWN custom_vjp: with
# the GLOBAL logsumexp, exp(QK^T*scale - lse) is the true global softmax
# probability of the block, so the per-block backward is exactly the fused
# kernel fed an externally-computed (lse, delta) — no lse cotangent exists
# anywhere.  These raw entry points run the kernels on one (q-chunk,
# kv-chunk) pair in (B, H, S, D) layout.

def _apply_tuned(block_q, block_k, Sq, Sk, D, causal):
    """Fill unset block sizes from the measured autotune cache (explicit
    args always win; ops/pallas/autotune.py).  Shapes are static under
    jit, so this is a dict lookup at trace time."""
    if block_q is None or block_k is None:
        from hetu_tpu.ops.pallas.autotune import tuned_blocks
        tuned = tuned_blocks(Sq, Sk, D, causal)
        if tuned is not None:
            block_q, block_k = block_q or tuned[0], block_k or tuned[1]
    return block_q, block_k


def _block_sizes(Sq, Sk, D, block_q, block_k, interpret, causal=False):
    block_q, block_k = _apply_tuned(block_q, block_k, Sq, Sk, D, causal)
    bq = block_q or _auto_blocks(Sq, Sk, D)[0]
    bk = block_k or _auto_blocks(Sq, Sk, D)[1]
    bq, bk = min(bq, Sq), min(bk, Sk)
    if Sq % bq or Sk % bk:
        raise ValueError(
            f"ring chunk ({Sq}, {Sk}) not divisible by blocks ({bq}, {bk})")
    if not interpret and (bq % 128 or bk % 128):
        # the compiled Mosaic path needs lane-aligned blocks; interpreter
        # tests may use any size
        raise ValueError(
            f"ring chunk blocks ({bq}, {bk}) not 128-aligned; pad sequence"
            " chunks to 128-multiples on TPU")
    return bq, bk


def flash_block_fwd(q, k, v, *, scale, causal=False, block_q=None,
                    block_k=None, interpret=None):
    """(out, lse) of one block pair; q, k, v: (B, H, S, D)."""
    if interpret is None:
        interpret = pallas_interpret()
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    bq, bk = _block_sizes(Sq, Sk, D, block_q, block_k, interpret, causal)
    return _fwd_call(q, k, v, scale, causal, bq, bk, Sk, interpret)


def flash_block_bwd(q, k, v, do, lse, delta, *, scale, causal=False,
                    block_q=None, block_k=None, interpret=None):
    """(dq, dk, dv) of one block pair given GLOBAL lse/delta for the q
    chunk; all fp32 outputs (ring steps accumulate across blocks).
    q, k, v, do: (B, H, S, D); lse, delta: (B, H, Sq, 1) fp32.

    Past ``_MAX_DQ_PARTIALS`` kv blocks the fused kernel's fp32 dQ
    partials would cost nk x |Q| HBM, so the same two-kernel fallback as
    the standalone path runs instead."""
    if interpret is None:
        interpret = pallas_interpret()
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    bq, bk = _block_sizes(Sq, Sk, D, block_q, block_k, interpret, causal)
    nq, nk = Sq // bq, Sk // bk

    if nq == 1 and nk == 1:
        dk, dv, dq = _bwd_one_call(
            q, k, v, do, delta, lse, scale=scale, causal=causal,
            block_q=bq, block_k=bk, kv_len=Sk, interpret=interpret,
            delta_in=True,
            out_dtypes=(jnp.float32, jnp.float32, jnp.float32))
        return dq, dk, dv

    bwd_q_spec = pl.BlockSpec((1, 1, bq, D), lambda b, h, j, i: (b, h, i, 0))
    bwd_kv_spec = pl.BlockSpec((1, 1, bk, D), lambda b, h, j, i: (b, h, j, 0))
    bwd_lse_spec = pl.BlockSpec((1, 1, bq, 1),
                                lambda b, h, j, i: (b, h, i, 0))
    kv_scratch = [
        pltpu.VMEM((bk, D), jnp.float32),
        pltpu.VMEM((bk, D), jnp.float32),
    ]

    if nk <= _MAX_DQ_PARTIALS:
        dk, dv, dq_part = pl.pallas_call(
            functools.partial(_bwd_fused_kernel, scale=scale, causal=causal,
                              block_q=bq, block_k=bk, kv_len=Sk,
                              delta_in=True),
            grid=(B, H, nk, nq),
            in_specs=[bwd_q_spec, bwd_kv_spec, bwd_kv_spec, bwd_q_spec,
                      bwd_lse_spec, bwd_lse_spec],
            out_specs=[
                bwd_kv_spec,
                bwd_kv_spec,
                pl.BlockSpec((1, 1, 1, bq, D),
                             lambda b, h, j, i: (j, b, h, i, 0)),
            ],
            out_shape=[
                _sds(k.shape, jnp.float32, k),
                _sds(v.shape, jnp.float32, v),
                _sds((nk, B, H, Sq, D), jnp.float32, q),
            ],
            scratch_shapes=kv_scratch,
            compiler_params=_compiler_params(3),
            name="flash_block_bwd_fused",
            interpret=interpret,
        )(q, k, v, do, delta, lse)
        dq = dq_part[0] if nk == 1 else jnp.sum(dq_part, axis=0)
        return dq, dk, dv

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_kv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, kv_len=Sk),
        grid=(B, H, nk, nq),
        in_specs=[bwd_q_spec, bwd_kv_spec, bwd_kv_spec, bwd_q_spec,
                  bwd_lse_spec, bwd_lse_spec],
        out_specs=[bwd_kv_spec, bwd_kv_spec],
        out_shape=[
            _sds(k.shape, jnp.float32, k),
            _sds(v.shape, jnp.float32, v),
        ],
        scratch_shapes=kv_scratch,
        compiler_params=_compiler_params(3),
        name="flash_block_bwd_dkv",
        interpret=interpret,
    )(q, k, v, do, delta, lse)

    dq_q_spec = pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0))
    dq_kv_spec = pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0))
    dq_lse_spec = pl.BlockSpec((1, 1, bq, 1),
                               lambda b, h, i, j: (b, h, i, 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, kv_len=Sk),
        grid=(B, H, nq, nk),
        in_specs=[dq_q_spec, dq_kv_spec, dq_kv_spec, dq_q_spec,
                  dq_lse_spec, dq_lse_spec],
        out_specs=dq_q_spec,
        out_shape=_sds(q.shape, jnp.float32, q),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=_compiler_params(3),
        name="flash_block_bwd_dq",
        interpret=interpret,
    )(q, k, v, do, delta, lse)
    return dq, dk, dv


# --------------------------------------------------------------------------
# public API
# --------------------------------------------------------------------------

def _round_up(x, m):
    return ((x + m - 1) // m) * m


def _auto_blocks(Sq_p: int, Sk_p: int, D: int) -> tuple[int, int]:
    """Block sizes swept on a v5e (fwd+bwd, best-of-chunks):

    D=64 (H=16, B=24/12/6):          D=128 (H=8, B=12/6; fused bwd,
    =====  ===========  =====  ====  causal, fwd+bwd ms, r03):
    seq    best blocks  flash  xla   ==========================
    =====  ===========  =====  ====  seq    best blocks   ms
    512    512 x 512    10.3   15.6  512    256 x 512    0.37
    1024   512 x 512    16.2   22.4  1024   512 x 512    0.60
    2048   512 x 1024   18.3   27.4  ==========================
    =====  ===========  =====  ====
    (bq=128 at D=128 S<=512 — the r02 best — is 1.8x slower than
    bq=256 with the fused single-pass backward.)

    128x128 blocks (the old default) LOSE to XLA at every length — the
    per-block mask/exp/control overhead swamps the small matmuls.  Large
    kv blocks amortize it, but the kv block x head_dim footprint is the
    VMEM budget: the piecewise length rule is additionally capped at
    ~64K elements / D, rounded down to the 128-lane tile (512 at D=128,
    256 at D=256).  q blocks cap at 512 to bound the fp32 accumulators;
    at D>=128 short sequences measured best with bq=256 with the fused
    backward (r03 table above; the r02 two-kernel best was 128).
    """
    # align bq to the sequence so an already-128-aligned Sq (e.g. 384)
    # is not re-padded up to a 256 boundary for nothing
    cap = 256 if D >= 128 and Sq_p <= 512 else 512
    bq = min(cap, Sq_p)
    if Sq_p % bq:
        bq = 128  # falls back to the universal tile; zero padding
    by_len = Sk_p if Sk_p <= 512 else (512 if Sk_p <= 1024 else 1024)
    vmem_cap = max(128, (65536 // max(D, 1)) // 128 * 128)
    return bq, min(by_len, vmem_cap)


def flash_attention(q, k, v, mask=None, *, causal: bool = False,
                    scale: float | None = None, block_q: int | None = None,
                    block_k: int | None = None,
                    interpret: bool | None = None):
    """Fused attention; drop-in for ``dot_product_attention``.

    q,k,v: (batch, seq, heads, head_dim).  Arbitrary ``mask`` falls back to
    the XLA materialized path (the kernel handles causal + ragged-kv only).
    ``block_q``/``block_k`` default to the swept heuristic (_auto_blocks).
    """
    if mask is not None:
        from hetu_tpu.layers.attention import dot_product_attention
        return dot_product_attention(q, k, v, mask, scale=scale,
                                     causal=causal)
    # one block-selection/padding/launch body for both layouts: delegate
    # to the native entry so the two paths can never drift apart
    out = flash_attention_bhsd(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret)
    return jnp.swapaxes(out, 1, 2)


def flash_attention_bhsd(q, k, v, *, causal: bool = False,
                         scale: float | None = None,
                         block_q: int | None = None,
                         block_k: int | None = None,
                         interpret: bool | None = None,
                         window: int | None = None):
    """Fused attention on NATIVE kernel layout: q, k, v (B, H, S, D) ->
    out (B, H, S, D).  No transpose touches the operands — the kernel tiles
    (B, H, S, D) directly, so a model that produces q/k/v in this layout
    (MultiHeadAttention's einsum path) hands buffers straight to Mosaic.
    The (B, S, H, D) entry (``flash_attention``) costs a materialized XLA
    relayout copy per operand AND per gradient around the custom vjp
    (~0.15 ms x 8 operands x depth at BERT-large seq 512 — the r03 ~9%
    residue this entry removes).

    K and V may have fewer heads than Q (a divisor: query head ``h`` reads
    KV head ``h // group``, nothing repeated in HBM), and with ``window``
    (causal only) query ``t`` sees key ``s`` iff ``s <= t`` and ``t - s <
    window``, blocks outside the window skipped.  Either is forward only
    (module docstring)."""
    if interpret is None:
        interpret = pallas_interpret()
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    grouped = k.shape[1] != H or window is not None
    if grouped and (H % k.shape[1] or v.shape[1] != k.shape[1]):
        raise ValueError(f"{H} query heads over {k.shape[1]} key and "
                         f"{v.shape[1]} value heads")
    if window is not None and (not causal or Sq != Sk or window < 1):
        raise ValueError("a window is causal self-attention's: it needs "
                         "causal=True, as many queries as keys and "
                         "window >= 1")
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    block_q, block_k = _apply_tuned(block_q, block_k, Sq, Sk, D, causal)
    auto_q, auto_k = _auto_blocks(_round_up(Sq, 128), _round_up(Sk, 128), D)
    block_q = min(block_q or auto_q, _round_up(Sq, 128))
    block_k = min(block_k or auto_k, _round_up(Sk, 128))
    Sq_p, Sk_p = _round_up(Sq, block_q), _round_up(Sk, block_k)

    def pad_s(x, S_p):
        if x.shape[2] != S_p:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, S_p - x.shape[2]), (0, 0)))
        return x

    if grouped:
        out, _ = _fwd_call(pad_s(q, Sq_p), pad_s(k, Sk_p), pad_s(v, Sk_p),
                           scale, causal, block_q, block_k, Sk, interpret,
                           window=window)
        return out[:, :, :Sq, :]
    out, _ = _flash(pad_s(q, Sq_p), pad_s(k, Sk_p), pad_s(v, Sk_p), scale,
                    causal, block_q, block_k, Sk, interpret)
    return out[:, :, :Sq, :]


def flash_attn_fn(*, block_q: int | None = None,
                  block_k: int | None = None,
                  interpret: bool | None = None,
                  native_layout: bool = False):
    """An ``attn_fn`` for MultiHeadAttention/TransformerBlock that routes
    unmasked (or causal) attention through the Pallas kernel.

    ``native_layout=True`` marks the callable ``bhsd`` so
    MultiHeadAttention projects q/k/v straight into the kernel's
    (B, H, S, D) tiling (einsum path, no relayout copies); the callable
    then expects/returns (B, H, S, D).  The default stays the plain
    (B, S, H, D) drop-in for ``dot_product_attention`` — compositions
    that hand tensors to the callable directly (ulysses_attention's
    inner_fn, ring chunks) rely on that contract."""

    if native_layout:
        def fn(q, k, v, mask=None, *, scale=None, causal=False):
            if mask is not None:
                from hetu_tpu.layers.attention import dot_product_attention
                out = dot_product_attention(
                    jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                    jnp.swapaxes(v, 1, 2), mask, scale=scale, causal=causal)
                return jnp.swapaxes(out, 1, 2)
            return flash_attention_bhsd(q, k, v, causal=causal, scale=scale,
                                        block_q=block_q, block_k=block_k,
                                        interpret=interpret)
        fn.bhsd = True
        return fn

    def fn(q, k, v, mask=None, *, scale=None, causal=False):
        return flash_attention(q, k, v, mask, causal=causal, scale=scale,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)

    return fn
