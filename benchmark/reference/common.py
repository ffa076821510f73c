"""What both references share: precisions, layer norm, GELU, seeded normals."""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

F32 = jnp.float32
# the precision below each: what a later PR would be tempted by
PRECISIONS = ("float32", "bfloat16", "float8")
LOWER = {"float32": "bfloat16", "bfloat16": "float8"}


# (exponent bits, mantissa bits) of the formats below float32; float8 is
# e4m3 with one scale a tensor (the largest magnitude maps to 240, the
# largest finite value of the IEEE-style e4m3 that reduce_precision models)
FORMATS = {"bfloat16": (8, 7), "float8": (4, 3)}
FLOAT8_MAX = 240.0


def rounded(x, precision: str):
    """``x`` rounded to ``precision``, in float32.  By
    ``lax.reduce_precision`` and never by ``astype`` there and back: on the
    v5e a float32 -> bfloat16 -> float32 round trip inside one fusion is
    computed in float32 (XLA allows excess precision), so it rounds
    nothing (my chip run, PR 23: PERF.md section 6)."""
    x = x.astype(F32)
    if precision == "float32":
        return x
    if precision not in FORMATS:
        raise ValueError(f"unknown precision {precision!r}")
    e, m = FORMATS[precision]
    if precision == "float8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FLOAT8_MAX
        return jax.lax.reduce_precision(x / scale, e, m) * scale
    return jax.lax.reduce_precision(x, e, m)


def quant(x, precision: str):
    """An operand rounded to ``precision``; its cotangent passes unrounded
    (a gradient of 1e-5 is below the least float8), as in mixed-precision
    training."""
    x = x.astype(F32)
    if precision == "float32":
        return x
    return x + jax.lax.stop_gradient(rounded(x, precision) - x)


def mm(a, b, precision: str = "float32"):
    """``a @ b`` with both operands in ``precision`` and float32
    accumulation at the highest matmul precision of the device."""
    return jnp.matmul(quant(a, precision), quant(b, precision),
                      precision=jax.lax.Precision.HIGHEST)


def einsum(spec, a, b, precision: str = "float32"):
    return jnp.einsum(spec, quant(a, precision), quant(b, precision),
                      precision=jax.lax.Precision.HIGHEST)


def layer_norm(x, gain, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * gain + bias


# Hugging Face's names of the activation (``hidden_act``,
# ``activation_function``): "gelu" is the exact form, x Phi(x)
GELU_FORMS = {"gelu": "erf", "gelu_new": "tanh"}


def gelu(x, form: str):
    """``erf``: the exact form, x Phi(x); ``tanh``: 0.5 x (1 +
    tanh(sqrt(2/pi) (x + 0.044715 x^3)))."""
    return jax.nn.gelu(x, approximate=(form == "tanh"))


def normal(key, name: str, shape, std: float, dtype, mean: float = 0.0):
    """A seeded normal leaf, keyed by the leaf's name so that adding a
    leaf moves no other."""
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return (mean + std * jax.random.normal(k, shape, F32)).astype(dtype)


def seed_key(seed: int):
    """A key from any whole number up to 2**63 (``--seed`` passes 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)
