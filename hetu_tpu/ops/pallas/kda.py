"""Chunked Kimi Delta Attention (KDA): the gated delta rule with a decay a
channel, in its chunkwise-parallel form.

The recurrence, a head, with ``a_t = exp(g_t)`` in (0, 1]^{d_k}::

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T,   S_0 = 0
    o_t = S_t^T q_t * scale

is never run a token at a time.  Writing ``u_t = b_t (v_t - k_t^T Diag(a_t)
S_{t-1})`` gives ``S_t = Diag(a_t) S_{t-1} + k_t u_t^T``, and over a chunk of
C tokens with ``G_t = sum_{r<=t} g_r`` (the decay's cumulative log, float32)
and S the state entering the chunk:

    (I + A) U = Diag(b) (V - (K * exp(G)) S)
    A_ts = b_t sum_d k_t[d] k_s[d] exp(G_t[d] - G_s[d])         (s < t)
    O    = scale (Q * exp(G)) S + P U
    P_ts = scale sum_d q_t[d] k_s[d] exp(G_t[d] - G_s[d])       (s <= t)
    S'   = Diag(exp(G_C)) S + (K * exp(G_C - G))^T U

so with ``T = (I + A)^{-1}`` (the UT transform of the WY representation),
``W_k = T Diag(b) (K * exp(G))`` and ``W_v = T Diag(b) V``: ``U = W_v - W_k S``.

Two stages, both Pallas, each with a VJP of its own:

- within chunks, in parallel over all of them (``kda_chunk_fwd`` and
  ``kda_chunk_bwd``, in ``kda_chunk.py``): the cumulative logs, the two
  decayed Gram matrices A and P, the triangular inverse, W_k and W_v, a few
  chunks of one head a grid step, float32 in VMEM at the highest matmul
  precision; only the six tensors the scan reads are written, in the type of
  ``v``.  Every exponent is of a difference that is <= 0: Gram blocks off
  the diagonal of the 16-token sub-blocks are products of two factors
  normalised at the row block's first token, the diagonal sub-blocks are
  computed pair by pair.  So any decay is exact, however strong; nothing is
  clamped.  The backward kernel holds to the same rule, because the
  cotangent of a decayed Gram matrix is two decayed Gram forms again with
  the same exponentials.  Its VJP saves the inputs and two float32 [C, C]
  matrices a chunk (the Gram matrix of k and the inverse), so the backward
  recomputes the exponentials and not the inverse.
- across chunks, the scan that carries the state (``kda_scan_fwd`` and
  ``kda_scan_bwd``): per chunk three small matmuls forward and eight
  backward, the state in VMEM, kept in the transposed layout ``[d_v, d_k]``
  so that the decay scales lanes.  Its VJP saves the six tensors it read and
  the state entering each chunk.

No float32 tensor of the first stage outlives its grid step, so all heads
go through each kernel in one call; what a layer keeps between its forward
and its backward is 1.4 GB at 64 head-sequences of 8,192 tokens (the staged
tensors 0.60, the states 0.54, M[k] and T 0.27), for one block's backward
under per-block remat.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hetu_tpu.core.runtime import pallas_interpret
from hetu_tpu.ops.pallas.kda_chunk import (SUB, chunks_a_step,
                                           within_chunks)

__all__ = ["chunk_kda"]

_CHUNKS_A_STEP = 8   # chunks one grid step of the scan walks


# --------------------------------------------------------------------------
# stage 2: the scan across chunks (Pallas)
# --------------------------------------------------------------------------

def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


_NN = ((1,), (0,))   # a @ b
_NT = ((1,), (1,))   # a @ b^T
_TN = ((0,), (0,))   # a^T @ b


def _scan_fwd_kernel(qg_ref, kd_ref, wk_ref, wv_ref, p_ref, gam_ref,
                     o_ref, st_ref, st_sc, *, nb):
    @pl.when(pl.program_id(1) == 0)
    def _():
        st_sc[:] = jnp.zeros_like(st_sc)

    for c in range(nb):
        st = st_sc[:]                          # [d_v, d_k], float32
        st_ref[0, c] = st
        io = wk_ref.dtype
        stb = st.astype(io)
        u = wv_ref[0, c].astype(jnp.float32) - _dot(wk_ref[0, c], stb, _NT)
        ub = u.astype(io)
        o = _dot(qg_ref[0, c], stb, _NT) + _dot(p_ref[0, c], ub, _NN)
        o_ref[0, c] = o.astype(o_ref.dtype)
        st_sc[:] = st * gam_ref[0, c] + _dot(ub, kd_ref[0, c], _TN)


def _scan_bwd_kernel(qg_ref, kd_ref, wk_ref, wv_ref, p_ref, gam_ref, st_ref,
                     do_ref, dqg_ref, dkd_ref, dwk_ref, dwv_ref, dp_ref,
                     dgam_ref, dst_sc, *, nb):
    @pl.when(pl.program_id(1) == 0)
    def _():
        dst_sc[:] = jnp.zeros_like(dst_sc)

    for c in reversed(range(nb)):
        io = wk_ref.dtype
        st = st_ref[0, c]                      # the state entering chunk c
        stb = st.astype(io)
        dst = dst_sc[:]                        # wrt the state leaving it
        dstb = dst.astype(io)
        do = do_ref[0, c]
        wk, kd, p, qg = wk_ref[0, c], kd_ref[0, c], p_ref[0, c], qg_ref[0, c]
        u = wv_ref[0, c].astype(jnp.float32) - _dot(wk, stb, _NT)
        ub = u.astype(io)
        du = _dot(p, do, _TN) + _dot(kd, dstb, _NT)
        dub = du.astype(io)
        dp_ref[0, c] = _dot(do, ub, _NT).astype(dp_ref.dtype)
        dqg_ref[0, c] = _dot(do, stb, _NN).astype(dqg_ref.dtype)
        dkd_ref[0, c] = _dot(ub, dstb, _NN).astype(dkd_ref.dtype)
        dgam_ref[0, c] = jnp.sum(st * dst, axis=0, keepdims=True)
        dwv_ref[0, c] = dub.astype(dwv_ref.dtype)
        dwk_ref[0, c] = (-_dot(dub, stb, _NN)).astype(dwk_ref.dtype)
        dst_sc[:] = (_dot(do, qg, _TN) + dst * gam_ref[0, c]
                     - _dot(dub, wk, _TN))


def _specs(nb, C, dk, dv, index):
    blk = lambda *tail: pl.BlockSpec((1, nb) + tail,
                                     lambda b, n: (b, index(n), 0, 0))
    return {"k": blk(C, dk), "v": blk(C, dv), "p": blk(C, C),
            "gam": blk(1, dk), "st": blk(dv, dk)}


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))


def _scan_fwd(qg, kd, wk, wv, p, gam, interpret):
    BH, N, C, dk = qg.shape
    dv = wv.shape[-1]
    nb = chunks_a_step(N, _CHUNKS_A_STEP)
    s = _specs(nb, C, dk, dv, lambda n: n)
    return pl.pallas_call(
        functools.partial(_scan_fwd_kernel, nb=nb),
        grid=(BH, N // nb),
        in_specs=[s["k"], s["k"], s["k"], s["v"], s["p"], s["gam"]],
        out_specs=[s["v"], s["st"]],
        out_shape=[jax.ShapeDtypeStruct((BH, N, C, dv), wv.dtype),
                   jax.ShapeDtypeStruct((BH, N, dv, dk), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=_params(), name="kda_scan_fwd", interpret=interpret,
    )(qg, kd, wk, wv, p, gam)


def _scan_bwd(qg, kd, wk, wv, p, gam, st, do, interpret):
    BH, N, C, dk = qg.shape
    dv = wv.shape[-1]
    nb = chunks_a_step(N, _CHUNKS_A_STEP)
    last = N // nb - 1
    s = _specs(nb, C, dk, dv, lambda n: last - n)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    return pl.pallas_call(
        functools.partial(_scan_bwd_kernel, nb=nb),
        grid=(BH, N // nb),
        in_specs=[s["k"], s["k"], s["k"], s["v"], s["p"], s["gam"],
                  s["st"], s["v"]],
        out_specs=[s["k"], s["k"], s["k"], s["v"], s["p"], s["gam"]],
        out_shape=[like(qg), like(kd), like(wk), like(wv), like(p),
                   like(gam)],
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=_params(), name="kda_scan_bwd", interpret=interpret,
    )(qg, kd, wk, wv, p, gam, st, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(qg, kd, wk, wv, p, gam, interpret):
    return _scan_fwd(qg, kd, wk, wv, p, gam, interpret)[0]


def _scan_vjp_fwd(qg, kd, wk, wv, p, gam, interpret):
    o, st = _scan_fwd(qg, kd, wk, wv, p, gam, interpret)
    return o, (qg, kd, wk, wv, p, gam, st)


def _scan_vjp_bwd(interpret, res, do):
    return tuple(_scan_bwd(*res, do, interpret))


_scan.defvjp(_scan_vjp_fwd, _scan_vjp_bwd)


# --------------------------------------------------------------------------
# public entry
# --------------------------------------------------------------------------

def chunk_kda(q, k, v, g, beta, *, scale: float | None = None,
              chunk: int = 64, interpret: bool | None = None):
    """Chunked KDA.  q, k: [B, H, S, d_k] (the caller L2-normalises them);
    v: [B, H, S, d_v]; g: [B, H, S, d_k], the log of the decay, <= 0;
    beta: [B, H, S] in (0, 1).  Returns o: [B, H, S, d_v] in ``v``'s type.
    A sequence that is no multiple of ``chunk`` is padded with tokens that
    leave the state as it is (k = 0, g = 0, beta = 0)."""
    if chunk % SUB:
        raise ValueError(f"chunk {chunk} is no multiple of {SUB}")
    if interpret is None:
        interpret = pallas_interpret()
    B, H, S, dk = q.shape
    dv = v.shape[-1]
    scale = dk ** -0.5 if scale is None else scale
    pad = -S % chunk
    if pad:
        q, k, v, g = (jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0)))
                      for a in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, 0), (0, pad)))
    N = (S + pad) // chunk
    # [B, H, S, ...] -> [heads, chunks, chunk, ...], beta a row a chunk
    split = lambda a: a.reshape((B * H, N, chunk) + a.shape[3:])
    staged = within_chunks(*(split(a) for a in (q, k, v, g)),
                           beta.reshape(B * H, N, 1, chunk), scale, v.dtype,
                           interpret)
    o = _scan(*staged, interpret)
    return o.reshape(B, H, N * chunk, dv)[:, :, :S]
