"""Fused residual + dropout + LayerNorm (forward AND backward) for TPU.

The post-LN transformer block computes ``ln(x + dropout(y))`` twice per
layer.  XLA lowers that as separate stat-reduction and normalize passes
(plus more in the backward), each re-streaming the 25 MB activations from
HBM — measured ~45 ms of the 194 ms BERT-large seq-128 headline step
(ROADMAP 4c; the reference composes it from discrete LayerNorm/Dropout
CUDA kernels, layers/normalization.py + Dropout.cu, which is strictly more
passes).  This kernel does the whole site in ONE pass per direction:

  forward : read x, y -> regenerate the dropout mask IN-REGISTER,
            v = x + drop(y); per-row mean/rstd in-register (rows are the
            minor-most D axis, entirely in VMEM); write out (+ tiny
            per-row stats)
  backward: read dout, x, y -> regenerate mask/v/xhat in-register, the
            two per-row LN reductions, write dx, dy, per-block
            dscale/dbias partials

The dropout mask is NEVER materialized: it is the same 2-round counter
hash as ``ops.dropout`` (ops/nn.py _hash_bits — key words folded over the
global flat index, threshold from ``dropout_keep_thresh``), recomputed
from the block's index range in both directions, so the fused path is
BIT-IDENTICAL to ``ln(x + ops.dropout(y, rate, key))`` with zero mask
HBM traffic or residual storage.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from hetu_tpu.core.runtime import pallas_interpret
from hetu_tpu.ops.nn import _hash_mix, dropout_keep_thresh

__all__ = ["fused_residual_dropout_ln"]


def _block_keep(kw_ref, bt: int, D: int, thresh: int):
    """The boolean keep mask for this grid block, regenerated from the
    key words exactly as ops.dropout computes it: the same 2-round hash
    over the GLOBAL flat index (block row offset folded in), same
    threshold.  A few ALU ops per element instead of an HBM-resident
    mask tensor."""
    base = (pl.program_id(0) * bt).astype(jnp.uint32)
    row = jax.lax.broadcasted_iota(jnp.uint32, (bt, D), 0)
    col = jax.lax.broadcasted_iota(jnp.uint32, (bt, D), 1)
    flat = (base + row) * jnp.uint32(D) + col
    bits = _hash_mix(_hash_mix(flat, kw_ref[0, 0]), kw_ref[0, 1])
    return bits < jnp.uint32(thresh)


def _drop(y, keep_mask, keep: float):
    # same expression as ops.dropout (y / keep, where) so the kept values
    # round identically in every dtype
    return jnp.where(keep_mask, y / jnp.asarray(keep, y.dtype),
                     jnp.zeros((), y.dtype))


def _fwd_kernel(x_ref, y_ref, kw_ref, s_ref, b_ref, out_ref, mean_ref,
                rstd_ref, *, eps: float, bt: int, D: int, thresh: int,
                keep: float):
    x = x_ref[...].astype(jnp.float32)
    y = y_ref[...]
    if thresh:  # dropout folded in (thresh=0 -> plain residual+LN)
        y = _drop(y, _block_keep(kw_ref, bt, D, thresh), keep)
    v = x + y.astype(jnp.float32)
    mean = jnp.mean(v, axis=-1, keepdims=True)
    c = v - mean
    rstd = jax.lax.rsqrt(jnp.mean(c * c, axis=-1, keepdims=True) + eps)
    out = c * rstd * s_ref[...].astype(jnp.float32) \
        + b_ref[...].astype(jnp.float32)
    out_ref[...] = out.astype(out_ref.dtype)
    mean_ref[...] = mean
    rstd_ref[...] = rstd


def _bwd_kernel(do_ref, x_ref, y_ref, kw_ref, s_ref, mean_ref, rstd_ref,
                dx_ref, dy_ref, ds_ref, db_ref, *, bt: int, D: int,
                thresh: int, keep: float):
    do = do_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)
    y = y_ref[...]
    km = _block_keep(kw_ref, bt, D, thresh) if thresh else None
    v = x + (_drop(y, km, keep) if thresh else y).astype(jnp.float32)
    xhat = (v - mean_ref[...]) * rstd_ref[...]
    dxhat = do * s_ref[...].astype(jnp.float32)
    # per-row LN backward:
    # dv = rstd * (dxhat - mean(dxhat) - xhat * mean(dxhat*xhat))
    d1 = jnp.mean(dxhat, axis=-1, keepdims=True)
    d2 = jnp.mean(dxhat * xhat, axis=-1, keepdims=True)
    dv = rstd_ref[...] * (dxhat - d1 - xhat * d2)
    dx_ref[...] = dv.astype(dx_ref.dtype)
    # d(dropout(y))/dy = 1/keep on kept elements (same division form)
    dy_ref[...] = (jnp.where(km, dv / jnp.float32(keep), 0.0) if thresh
                   else dv).astype(dy_ref.dtype)
    # per-block param-grad partials (summed outside; fp32)
    ds_ref[0] = jnp.sum(do * xhat, axis=0, keepdims=True)
    db_ref[0] = jnp.sum(do, axis=0, keepdims=True)


def _pick_block(T: int, D: int, n_streams: int) -> int:
    """Rows per grid step, sized so n_streams double-buffered (bt, D)
    fp32 blocks stay within ~8 MB of VMEM (the backward streams 5 row
    blocks + fp32 temps; at D=1024 this lands on bt=128).  A measured
    autotune-DB entry (ops/pallas/autotune.py ``autotune_fused_ln_rows``)
    outranks the VMEM heuristic whenever it still divides T."""
    from hetu_tpu.ops.pallas.autotune import tuned_entry
    hit = tuned_entry("fused_ln", f"T{T}|D{D}|s{n_streams}")
    if hit and T % int(hit["block_rows"]) == 0:
        return int(hit["block_rows"])
    budget = (8 * 1024 * 1024) // (n_streams * 2 * D * 4)
    bt = max(8, min(512, budget))
    bt = 1 << (bt.bit_length() - 1)  # power of two for even division
    while T % bt and bt > 8:
        bt //= 2
    return bt if T % bt == 0 else math.gcd(T, bt)


def _ln_fwd(x2, y2, kw, scale, bias, rate, eps, interpret):
    T, D = x2.shape
    bt = _pick_block(T, D, 4)
    grid = (T // bt,)
    row = pl.BlockSpec((bt, D), lambda i: (i, 0))
    stat = pl.BlockSpec((bt, 1), lambda i: (i, 0))
    vec = pl.BlockSpec((1, D), lambda i: (0, 0))
    kwspec = pl.BlockSpec((1, 2), lambda i: (0, 0))
    thresh = dropout_keep_thresh(rate) if rate > 0.0 else 0
    out, mean, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps, bt=bt, D=D, thresh=thresh,
                          keep=1.0 - rate),
        grid=grid,
        in_specs=[row, row, kwspec, vec, vec],
        out_specs=[row, stat, stat],
        out_shape=[
            jax.ShapeDtypeStruct((T, D), x2.dtype),
            jax.ShapeDtypeStruct((T, 1), jnp.float32),
            jax.ShapeDtypeStruct((T, 1), jnp.float32),
        ],
        name="fused_ln_fwd",
        interpret=interpret,
    )(x2, y2, kw, scale.reshape(1, D), bias.reshape(1, D))
    return out, mean, rstd


def _ln_bwd(do2, x2, y2, kw, scale, mean, rstd, rate, interpret):
    T, D = x2.shape
    bt = _pick_block(T, D, 6)
    grid = (T // bt,)
    row = pl.BlockSpec((bt, D), lambda i: (i, 0))
    stat = pl.BlockSpec((bt, 1), lambda i: (i, 0))
    vec = pl.BlockSpec((1, D), lambda i: (0, 0))
    # (blocks, 1, D): a (1, D) block of a (blocks, D) array would break
    # Mosaic's rule that a block's last two dims divide by (8, 128) or
    # span the array
    part = pl.BlockSpec((1, 1, D), lambda i: (i, 0, 0))
    kwspec = pl.BlockSpec((1, 2), lambda i: (0, 0))
    thresh = dropout_keep_thresh(rate) if rate > 0.0 else 0
    dx, dy, ds_p, db_p = pl.pallas_call(
        functools.partial(_bwd_kernel, bt=bt, D=D, thresh=thresh,
                          keep=1.0 - rate),
        grid=grid,
        in_specs=[row, row, row, kwspec, vec, stat, stat],
        out_specs=[row, row, part, part],
        out_shape=[
            jax.ShapeDtypeStruct((T, D), x2.dtype),
            jax.ShapeDtypeStruct((T, D), y2.dtype),
            jax.ShapeDtypeStruct((T // bt, 1, D), jnp.float32),
            jax.ShapeDtypeStruct((T // bt, 1, D), jnp.float32),
        ],
        name="fused_ln_bwd",
        interpret=interpret,
    )(do2, x2, y2, kw, scale.reshape(1, D), mean, rstd)
    return dx, dy, ds_p.sum((0, 1)), db_p.sum((0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _fused(x, y, kw, scale, bias, rate, eps, interpret):
    out, _, _ = _ln_fwd(x, y, kw, scale, bias, rate, eps, interpret)
    return out


def _fused_fwd(x, y, kw, scale, bias, rate, eps, interpret):
    out, mean, rstd = _ln_fwd(x, y, kw, scale, bias, rate, eps, interpret)
    return out, (x, y, kw, scale, mean, rstd)


def _fused_bwd(rate, eps, interpret, res, do):
    x, y, kw, scale, mean, rstd = res
    dx, dy, ds, db = _ln_bwd(do, x, y, kw, scale, mean, rstd, rate,
                             interpret)
    # integer primal (key words): float0 cotangent per jax convention
    import numpy as _np
    dkw = _np.zeros(kw.shape, jax.dtypes.float0)
    return dx, dy, dkw, ds.astype(scale.dtype), db.astype(scale.dtype)


_fused.defvjp(_fused_fwd, _fused_bwd)


def fused_residual_dropout_ln(x, y, scale, bias, *, rate: float = 0.0,
                              key=None, eps: float = 1e-5,
                              interpret: bool | None = None):
    """``layer_norm(x + dropout(y, rate, key))`` in one HBM pass per
    direction, bit-identical to the composed ``ops.dropout`` +
    ``ops.layer_norm`` (the mask is the same counter hash, regenerated
    in-register in both passes — never stored).  ``rate=0.0`` or
    ``key=None`` folds to plain residual+LN.  x, y: (..., D); scale/bias:
    (D,).  Compiled path needs D % 128 == 0; any D under the
    interpreter."""
    if interpret is None:
        interpret = pallas_interpret()
    D = x.shape[-1]
    if not interpret and D % 128:
        raise ValueError(f"fused LN needs D % 128 == 0 on TPU, got {D}")
    if not 0.0 <= rate < 1.0:
        # rate=1.0 would make the keep threshold 0, which the kernels'
        # thresh sentinel reads as "no dropout" — the opposite semantics;
        # ops.dropout at rate 1 drops everything.  Nobody trains at
        # rate>=1, so reject instead of special-casing the sentinel.
        raise ValueError(f"dropout rate must be in [0, 1), got {rate}")
    if key is None:
        rate = 0.0
    if rate > 0.0:
        kd = jax.random.key_data(key) if jax.dtypes.issubdtype(
            key.dtype, jax.dtypes.prng_key) else key
        kw = kd.astype(jnp.uint32).reshape(-1)
        if kw.size < 2:  # 1-word raw key: ops.dropout folds words[1 % 1]
            kw = jnp.concatenate([kw, kw])
        kw = kw[:2].reshape(1, 2)
    else:
        kw = jnp.zeros((1, 2), jnp.uint32)
    lead = x.shape[:-1]
    T = math.prod(lead) if lead else 1
    out = _fused(x.reshape(T, D), y.reshape(T, D), kw, scale, bias,
                 float(rate), float(eps), bool(interpret))
    return out.reshape(*lead, D)
