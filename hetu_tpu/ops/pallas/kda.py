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

Two stages:

- within chunks, in parallel over all of them, as XLA operations in float32
  (``_within_chunks``): the cumulative logs, the two decayed Gram matrices
  A and P, the triangular inverse, W_k and W_v.  Every exponent is of a
  difference that is <= 0: Gram blocks off the diagonal of the 16-token
  sub-blocks are products of two factors normalised at the row block's
  first token, the diagonal sub-blocks are computed pair by pair.  So any
  decay is exact, however strong; nothing is clamped.
- across chunks, the scan that carries the state (``kda_scan_fwd`` and
  ``kda_scan_bwd``, Pallas): per chunk three small matmuls forward and
  eight backward, the state in VMEM, kept in the transposed layout
  ``[d_v, d_k]`` so that the decay scales lanes.  The forward stores the
  state entering each chunk for the backward.

The first stage is differentiated by JAX, the scan has its own VJP.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hetu_tpu.core.runtime import pallas_interpret

__all__ = ["chunk_kda"]

_HI = jax.lax.Precision.HIGHEST
_SUB = 16            # sub-block of the within-chunk Gram matrices
_CHUNKS_A_STEP = 8   # chunks one grid step of the scan walks
_HEADS_A_PASS = 4    # heads whose first-stage tensors are live at a time


# --------------------------------------------------------------------------
# stage 1: within chunks (XLA, float32)
# --------------------------------------------------------------------------

def _diagonal_grams(x, k, G):
    """Pair by pair inside each sub-block.  x: [..., X, n, sub, d];
    k, G: [..., n, sub, d].  Returns [..., X, n, sub(t), sub(s)] with
    ``sum_d x_t k_s exp(G_t - G_s)`` for s <= t and 0 above: one reduction
    over d of the [sub, sub, d] products, which XLA does not materialise
    for the forward; the exponent is clamped at 0 above the diagonal, where
    it is masked anyway."""
    sub = k.shape[-2]
    decay = jnp.exp(jnp.minimum(G[..., :, None, :] - G[..., None, :, :],
                                0.0))
    grams = jnp.sum(x[..., :, None, :] * (k[..., None, :, :] * decay
                                          )[..., None, :, :, :, :], axis=-1)
    return jnp.where(jnp.tril(jnp.ones((sub, sub), bool)), grams, 0.0)


def _decayed_grams(x, k, G):
    """``M[x]_ts = sum_d x_t[d] k_s[d] exp(G_t[d] - G_s[d])`` for s <= t, 0
    above the diagonal.  x: [..., X, C, d] (X operands share k and G);
    k, G: [..., C, d].  Returns [..., X, C, C]."""
    C, d = k.shape[-2:]
    n = C // _SUB
    lead = k.shape[:-2]
    xs = x.reshape(x.shape[:-2] + (n, _SUB, d))
    ks, Gs = (a.reshape(lead + (n, _SUB, d)) for a in (k, G))
    diag = _diagonal_grams(xs, ks, Gs)
    rows = []
    for i in range(n):
        parts = []
        if i:
            # both factors are normalised at the cumulative log just before
            # row block i, so both exponents are <= 0
            ref = Gs[..., i - 1, _SUB - 1, :]
            xr = xs[..., i, :, :] * jnp.exp(
                Gs[..., i, :, :] - ref[..., None, :])[..., None, :, :]
            kc = (ks[..., :i, :, :] * jnp.exp(
                ref[..., None, None, :] - Gs[..., :i, :, :])
                  ).reshape(lead + (i * _SUB, d))
            parts.append(jnp.einsum("...xtd,...sd->...xts", xr, kc,
                                    precision=_HI))
        parts.append(diag[..., i, :, :])
        if i < n - 1:
            parts.append(jnp.zeros(diag.shape[:-3]
                                   + (_SUB, (n - 1 - i) * _SUB), diag.dtype))
        rows.append(jnp.concatenate(parts, axis=-1))
    return jnp.concatenate(rows, axis=-2)


def _inv_unit_lower(L):
    """Inverse of a batch of unit lower triangular matrices [..., n, n]:
    forward substitution in blocks of ``_SUB`` rows, merged two by two:
    ``[[A, 0], [B, D]]^-1 = [[A^-1, 0], [-D^-1 B A^-1, D^-1]]``."""
    n = L.shape[-1]
    if n <= _SUB:
        eye = jnp.eye(n, dtype=L.dtype)
        rows = []
        for r in range(n):
            row = jnp.broadcast_to(eye[r], L.shape[:-2] + (n,))
            if r:
                row = row - jnp.einsum("...c,...cn->...n", L[..., r, :r],
                                       jnp.stack(rows, axis=-2),
                                       precision=_HI)
            rows.append(row)
        return jnp.stack(rows, axis=-2)
    h = n // 2
    a = _inv_unit_lower(L[..., :h, :h])
    d = _inv_unit_lower(L[..., h:, h:])
    low = -jnp.einsum("...ij,...jk,...kl->...il", d, L[..., h:, :h], a,
                      precision=_HI)
    top = jnp.concatenate([a, jnp.zeros_like(low.swapaxes(-1, -2))], axis=-1)
    return jnp.concatenate([top, jnp.concatenate([low, d], axis=-1)],
                           axis=-2)


def _within_chunks(q, k, v, g, beta, scale, out_dtype):
    """q, k, g: [B, H, N, C, d_k]; v: [B, H, N, C, d_v]; beta: [B, H, N, C];
    all float32.  Returns what the scan takes: (qg, kd, wk, wv, p, gamma)."""
    G = jnp.cumsum(g, axis=-2)
    last = G[..., -1:, :]
    decay = jnp.exp(G)
    C = k.shape[-2]
    grams = _decayed_grams(jnp.stack([k, q * scale], axis=-3), k, G)
    strict = jnp.tril(jnp.ones((C, C), bool), -1)
    A = jnp.where(strict, grams[..., 0, :, :] * beta[..., None], 0.0)
    T = _inv_unit_lower(A + jnp.eye(C, dtype=A.dtype))
    bk = beta[..., None] * k * decay
    bv = beta[..., None] * v
    wk = jnp.einsum("...ts,...sd->...td", T, bk, precision=_HI)
    wv = jnp.einsum("...ts,...sd->...td", T, bv, precision=_HI)
    cast = lambda a: a.astype(out_dtype)
    return (cast(q * scale * decay), cast(k * jnp.exp(last - G)), cast(wk),
            cast(wv), cast(grams[..., 1, :, :]), jnp.exp(last))


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
    nb = _chunks_a_step(N)
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
    nb = _chunks_a_step(N)
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


def _chunks_a_step(n_chunks: int) -> int:
    nb = min(_CHUNKS_A_STEP, n_chunks)
    while n_chunks % nb:
        nb -= 1
    return nb


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
    leave the state as it is (k = 0, g = 0, beta = 0).  The B x H heads are
    walked ``_HEADS_A_PASS`` at a time, each pass recomputed in the backward
    pass, so that the float32 tensors of the first stage are live for one
    pass only."""
    if chunk % _SUB:
        raise ValueError(f"chunk {chunk} is no multiple of {_SUB}")
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
    per = min(_HEADS_A_PASS, B * H)
    while (B * H) % per:
        per -= 1

    @jax.checkpoint
    def one_pass(args):
        f32 = lambda a: a.astype(jnp.float32)
        staged = _within_chunks(*(f32(a) for a in args), scale, v.dtype)
        return _scan(*(a[0] for a in staged), interpret)

    # [B, H, S, ...] -> [passes, 1, heads of a pass, chunks, chunk, ...]
    split = lambda a: a.reshape(
        (B * H // per, 1, per, N, chunk) + a.shape[3:])
    o = jax.lax.map(one_pass, tuple(split(a) for a in (q, k, v, g, beta)))
    return o.reshape(B, H, N * chunk, dv)[:, :, :S]
