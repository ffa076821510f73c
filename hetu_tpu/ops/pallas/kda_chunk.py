"""The within-chunk stage of chunked KDA (``ops.pallas.kda`` has the
mathematics): the Pallas kernels ``kda_chunk_fwd`` and ``kda_chunk_bwd`` and
the ``jax.custom_vjp`` that joins them.

A grid step holds ``_CHUNKS_A_STEP`` chunks of one head in VMEM, every
operand float32 and every product at the highest matmul precision, batched
over the step's chunks so that their chains of products interleave, and
writes what the scan reads.  Nothing else reaches HBM: the exponentials, the
Gram blocks and the inverse's levels live and die inside a step.

Every exponent is of a difference that is <= 0, forward and backward alike,
so any decay is exact.  A decayed Gram matrix ``M_ts = sum_d x_t k_s
exp(G_t - G_s)`` is taken in sub-blocks of ``SUB`` tokens:

- below the sub-block diagonal, row block i against every earlier column
  with both factors normalised at the cumulative log just before the row
  block: ``x_t exp(G_t - ref)`` and ``k_s exp(ref - G_s)``, one matmul;
- inside a sub-block pair by pair, as ``SUB`` bands: band j holds the
  pairs (t, t - j), so k and G rolled by j rows give a whole tile of
  ``exp(G_t - G_{t-j})`` at once and one lane reduction gives the band.

The cotangent of such a matrix is the same two forms again, of D and of its
transpose, with the same exponentials: ``dx_t = sum_s D_ts k_s exp(G_t -
G_s)``, ``dk_s = sum_t D_ts x_t exp(G_t - G_s)``, and for the cumulative
log ``x_t dx_t`` at the row and ``-k_s dk_s`` at the column.  So the
backward takes no exponent of another sign.

The unit lower triangle is inverted by merging diagonal blocks two by two
from single rows up, ``[[A, 0], [B, D]]^-1 = [[A^-1, 0], [-D^-1 B A^-1,
D^-1]]``: a level is two products of the whole matrix with the level's
blocks masked in.

The VJP saves the five inputs and, in float32, the Gram matrix M[k] and the
inverse T (side by side, [C, 2 C] a chunk, written by the forward only when
it runs for a backward): with them the backward kernel recomputes the
cumulative log and the exponentials, which are cheap, and not the inverse,
which is most of the forward's products.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST
SUB = 16              # sub-block of the within-chunk Gram matrices
_CHUNKS_A_STEP = 4    # chunks one grid step holds

# contracted dimension of each side of a product batched over dimension 0
_NN = (2, 1)   # a @ b
_NT = (2, 2)   # a @ b^T
_TN = (1, 1)   # a^T @ b


def _bmm(a, b, dims):
    return jax.lax.dot_general(
        a, b, ((dims[:1], dims[1:]), ((0,), (0,))), precision=_HI,
        preferred_element_type=jnp.float32)


def _iotas(C):
    return (jax.lax.broadcasted_iota(jnp.int32, (1, C, C), 1),
            jax.lax.broadcasted_iota(jnp.int32, (1, C, C), 2))


def _ones_where(mask, nb):
    return jnp.broadcast_to(jnp.where(mask, 1.0, 0.0), (nb,) + mask.shape[1:])


def _pair_decay(k, G, j):
    """k_{t-j} and exp(G_t - G_{t-j}) for every row t of the tile; rows
    whose partner lies outside the chunk wrap around and are masked by the
    caller (their exponent is clamped at 0)."""
    if not j:
        return k, None
    decay = jnp.exp(jnp.minimum(G - pltpu.roll(G, j, 1), 0.0))
    return pltpu.roll(k, j, 1) * decay, decay


def _band_mask(rows, cols, j):
    """Where band j sits in the [C, C] matrix: s = t - j inside t's
    sub-block."""
    return (rows - cols == j) & (rows % SUB >= j)


def _row_block(G, i):
    """The two normalising factors of row block i (i >= 1): for its rows
    ``exp(G_t - ref)``, for every column ``exp(min(ref - G_s, 0))``, which
    is exact for the earlier columns, the only ones used."""
    ref = G[:, i * SUB - 1:i * SUB]
    return (jnp.exp(G[:, i * SUB:(i + 1) * SUB] - ref),
            jnp.exp(jnp.minimum(ref - G, 0.0)))


def _stack_rows(blocks, shape):
    """Row blocks 1.. of a [nb, C, n] array under a zero block 0; with no
    block but the first, zeros of ``shape``."""
    if not blocks:
        return jnp.zeros(shape, jnp.float32)
    return jnp.concatenate([jnp.zeros_like(blocks[0])] + blocks, axis=1)


def _decayed_grams(xs, k, G):
    """``M[x]_ts = sum_d x_t[d] k_s[d] exp(G_t[d] - G_s[d])`` for s <= t and 0
    above the diagonal, for every x of ``xs``; x, k, G: [nb, C, d]."""
    nb, C, _ = k.shape
    rows, cols = _iotas(C)
    off = [[] for _ in xs]
    for i in range(1, C // SUB):
        er, ec = _row_block(G, i)
        # the row blocks of every x stacked: one product against k's factor
        rs = slice(i * SUB, (i + 1) * SUB)
        both = _bmm(jnp.concatenate([x[:, rs] * er for x in xs], axis=1),
                    k * ec, _NT)
        for n, blocks in enumerate(off):
            blocks.append(both[:, n * SUB:(n + 1) * SUB])
    below = cols < rows // SUB * SUB
    grams = [jnp.where(below, _stack_rows(blocks, (nb, C, C)), 0.0)
             for blocks in off]
    for j in range(SUB):
        kd, _ = _pair_decay(k, G, j)
        at = _band_mask(rows, cols, j)
        grams = [jnp.where(at, jnp.sum(x * kd, axis=2, keepdims=True), m)
                 for x, m in zip(xs, grams)]
    return grams


def _grams_bwd(xs, Ds, k, G):
    """The cotangents of ``_decayed_grams``: for every x its ``dx_t = sum_s
    D_ts k_s exp(G_t - G_s)``, and for k the sum over the xs of ``sum_t D_ts
    x_t exp(G_t - G_s)``.  Each D is masked as its matrix is."""
    C = k.shape[1]
    rows, cols = _iotas(C)
    below = cols < rows // SUB * SUB
    Doff = [jnp.where(below, D, 0.0) for D in Ds]
    blocks = [[] for _ in xs]
    dk = jnp.zeros_like(k)
    for i in range(1, C // SUB):
        er, ec = _row_block(G, i)
        rs = slice(i * SUB, (i + 1) * SUB)
        Dr = jnp.concatenate([D[:, rs] for D in Doff], axis=1)
        xr = jnp.concatenate([x[:, rs] * er for x in xs], axis=1)
        dxr = _bmm(Dr, k * ec, _NN)
        for n, b in enumerate(blocks):
            b.append(dxr[:, n * SUB:(n + 1) * SUB] * er)
        dk = dk + _bmm(Dr, xr, _TN) * ec
    dxs = [_stack_rows(b, k.shape) for b in blocks]
    for j in range(SUB):
        kd, decay = _pair_decay(k, G, j)
        at = _band_mask(rows, cols, j)
        back = 0.0
        for n, (x, D) in enumerate(zip(xs, Ds)):
            band = jnp.sum(jnp.where(at, D, 0.0), axis=2, keepdims=True)
            dxs[n] = dxs[n] + band * kd
            back = back + band * x
        if j:
            back = pltpu.roll(back * decay, C - j, 1)
        dk = dk + back
    return dxs, dk


def _inv_unit_lower(A):
    """``(I + A)^-1`` for strictly lower triangular A: [nb, C, C]."""
    C = A.shape[1]
    rows, cols = _iotas(C)
    level = lambda n: (((rows >> n) & 1) == 1) & ((rows >> n) - 1
                                                 == (cols >> n))
    X = jnp.where(rows == cols, 1.0, 0.0) - jnp.where(level(0), A, 0.0)
    n = 1
    while 1 << n < C:
        X = X - _bmm(X, _bmm(jnp.where(level(n), A, 0.0), X, _NN), _NN)
        n += 1
    return X


def _column(row, eye):
    """[nb, 1, C] -> [nb, C, 1]."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=2, keepdims=True)


def _forward(k, g, beta):
    """What both kernels need first of a step's chunks: the cumulative log,
    its last row, exp(G), exp(G_C - G) and beta as a column."""
    nb, C, _ = k.shape
    rows, cols = _iotas(C)
    G = _bmm(_ones_where(rows >= cols, nb), g, _NN)
    # the last row, by a reduction: Mosaic cannot store a row sliced from
    # sublane C - 1 at sublane 0
    last = jnp.sum(jnp.where(rows[:, :, :1] == C - 1, G, 0.0), axis=1,
                   keepdims=True)
    bcol = _column(beta, rows == cols)
    return G, last, jnp.exp(G), jnp.exp(last - G), bcol


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, qg_ref, kd_ref, wk_ref,
                wv_ref, p_ref, gam_ref, *saved, scale):
    f32 = lambda ref: ref[0].astype(jnp.float32)
    q, k, v, g, beta = (f32(r) for r in (q_ref, k_ref, v_ref, g_ref, b_ref))
    C = k.shape[1]
    rows, cols = _iotas(C)
    xq = q * scale
    G, last, decay, rest, bcol = _forward(k, g, beta)
    mk, mq = _decayed_grams((k, xq), k, G)
    T = _inv_unit_lower(jnp.where(rows > cols, mk * bcol, 0.0))
    qg_ref[0] = (xq * decay).astype(qg_ref.dtype)
    kd_ref[0] = (k * rest).astype(kd_ref.dtype)
    wk_ref[0] = _bmm(T, bcol * k * decay, _NN).astype(wk_ref.dtype)
    wv_ref[0] = _bmm(T, bcol * v, _NN).astype(wv_ref.dtype)
    p_ref[0] = mq.astype(p_ref.dtype)
    gam_ref[0] = jnp.exp(last)
    if saved:      # for the backward kernel: M[k] beside the inverse, float32
        saved[0][0] = jnp.concatenate([mk, T], axis=2)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, mt_ref, dqg_ref,
                dkd_ref, dwk_ref, dwv_ref, dp_ref, dgam_ref, dq_ref, dk_ref,
                dv_ref, dg_ref, db_ref, *, scale):
    """With wk = T bk, wv = T bv, T = (I + A)^-1, A = beta M[k] below the
    diagonal and p = M[scale q]: the cotangents of T's two products, then
    ``dA = -T^T dT T^T``, then the two Gram matrices' cotangents, then
    everything that reaches the cumulative log summed back over the
    chunk."""
    f32 = lambda ref: ref[0].astype(jnp.float32)
    q, k, v, g, beta = (f32(r) for r in (q_ref, k_ref, v_ref, g_ref, b_ref))
    dqg, dkd, dwk, dwv, dp, dgam = (f32(r) for r in (
        dqg_ref, dkd_ref, dwk_ref, dwv_ref, dp_ref, dgam_ref))
    nb, C, _ = k.shape
    mk, T = mt_ref[0, :, :, :C], mt_ref[0, :, :, C:]
    rows, cols = _iotas(C)
    strict, eye = rows > cols, rows == cols
    xq = q * scale
    G, last, decay, rest, bcol = _forward(k, g, beta)
    kdec = k * decay
    bk, bv = bcol * kdec, bcol * v
    dT = _bmm(dwk, bk, _NT) + _bmm(dwv, bv, _NT)
    dbk, dbv = _bmm(T, dwk, _TN), _bmm(T, dwv, _TN)
    dA = jnp.where(strict, -_bmm(_bmm(T, dT, _TN), T, _NT), 0.0)
    dbcol = (jnp.sum(dA * mk, axis=2, keepdims=True)
             + jnp.sum(dbk * kdec, axis=2, keepdims=True)
             + jnp.sum(dbv * v, axis=2, keepdims=True))
    # the two Gram matrices: A = beta M[k] below the diagonal, p = M[scale q]
    (dxk, dxq), dkm = _grams_bwd(
        (k, xq), (dA * bcol, jnp.where(rows >= cols, dp, 0.0)), k, G)
    kd = k * rest
    dG = (k * (dxk - dkm) + xq * dxq + dbk * bk + dqg * xq * decay
          - dkd * kd)
    dlast = jnp.sum(dkd * kd, axis=1, keepdims=True) + dgam * jnp.exp(last)
    dG = dG + jnp.where(rows[:, :, :1] == C - 1, dlast, 0.0)
    dq_ref[0] = (scale * (dxq + dqg * decay)).astype(dq_ref.dtype)
    dk_ref[0] = (dxk + dkm + bcol * dbk * decay + dkd * rest
                 ).astype(dk_ref.dtype)
    dv_ref[0] = (bcol * dbv).astype(dv_ref.dtype)
    dg_ref[0] = _bmm(_ones_where(rows <= cols, nb), dG, _NN
                     ).astype(dg_ref.dtype)
    db_ref[0] = jnp.sum(jnp.where(eye, dbcol, 0.0), axis=1, keepdims=True
                        ).astype(db_ref.dtype)


def chunks_a_step(n_chunks: int, most: int) -> int:
    """The largest divisor of ``n_chunks`` that is at most ``most``."""
    nb = min(most, n_chunks)
    while n_chunks % nb:
        nb -= 1
    return nb


def _blocks(nb, *arrays):
    return [pl.BlockSpec((1, nb) + a.shape[2:], lambda b, n: (b, n, 0, 0))
            for a in arrays]


def _params():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"))


def _call_fwd(q, k, v, g, beta, scale, out_dtype, interpret, save=False):
    """The six tensors of the scan and, with ``save``, M[k] beside the
    inverse, [C, 2 C] a chunk in float32, for the backward kernel."""
    BH, N, C, dk = k.shape
    nb = chunks_a_step(N, _CHUNKS_A_STEP)
    shape = jax.ShapeDtypeStruct
    out = [shape(q.shape, out_dtype), shape(k.shape, out_dtype),
           shape(k.shape, out_dtype), shape(v.shape, out_dtype),
           shape((BH, N, C, C), out_dtype),
           shape((BH, N, 1, dk), jnp.float32)]
    out += [shape((BH, N, C, 2 * C), jnp.float32)] * save
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale),
        grid=(BH, N // nb), in_specs=_blocks(nb, q, k, v, g, beta),
        out_specs=_blocks(nb, *out), out_shape=out,
        compiler_params=_params(), name="kda_chunk_fwd", interpret=interpret,
    )(q, k, v, g, beta)


def _call_bwd(q, k, v, g, beta, mt, cots, scale, interpret):
    nb = chunks_a_step(k.shape[1], _CHUNKS_A_STEP)
    out = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in (q, k, v, g, beta)]
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale),
        grid=(k.shape[0], k.shape[1] // nb),
        in_specs=_blocks(nb, q, k, v, g, beta, mt, *cots),
        out_specs=_blocks(nb, *out), out_shape=out,
        compiler_params=_params(), name="kda_chunk_bwd", interpret=interpret,
    )(q, k, v, g, beta, mt, *cots)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def within_chunks(q, k, v, g, beta, scale, out_dtype, interpret):
    """q, k, g: [BH, N, C, d_k]; v: [BH, N, C, d_v]; beta: [BH, N, 1, C];
    any float type (g and beta float32 in practice).  Returns what the scan
    takes: qg, kd, wk, wv and p in ``out_dtype``, gamma in float32."""
    return tuple(_call_fwd(q, k, v, g, beta, scale, out_dtype, interpret))


def _vjp_fwd(q, k, v, g, beta, scale, out_dtype, interpret):
    out = _call_fwd(q, k, v, g, beta, scale, out_dtype, interpret, save=True)
    return tuple(out[:6]), (q, k, v, g, beta, *out[6:])


def _vjp_bwd(scale, out_dtype, interpret, res, cots):
    return tuple(_call_bwd(*res, cots, scale, interpret))


within_chunks.defvjp(_vjp_fwd, _vjp_bwd)
