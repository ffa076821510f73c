"""Kimi Delta Attention (KDA): a linear-attention mixer whose state follows
the delta rule with a decay a channel (Kimi-Linear, ``model_type``
``kimi_linear``).

A token, a head of width ``head_dim`` (d_k = d_v)::

    q = L2norm(SiLU(conv(W_q x))_h)    k likewise    v = SiLU(conv(W_v x))_h
    g = -exp(A_log_h) softplus((W_a_up W_a_down x)_h + dt_bias_h)
    b = sigmoid(W_b x)_h
    S_t = (I - b k k^T) Diag(exp(g)) S_{t-1} + b k v^T;  o = S_t^T q / sqrt(d_k)
    y = W_o [ sigmoid(W_g_up W_g_down x)_h * RMSNorm(o) ]

``conv`` is a causal depthwise convolution over time.  The state is never
stepped a token at a time: ``ops.pallas.chunk_kda`` computes the same
outputs chunk by chunk, forward and backward.  The layer holds no position
encoding; the state carries order.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from hetu_tpu.core.module import Module
from hetu_tpu.core.rng import next_key
from hetu_tpu.init import normal
from hetu_tpu.layers.norm import RMSNorm
from hetu_tpu.ops.pallas.kda import chunk_kda

__all__ = ["KimiDeltaAttention", "causal_depthwise_conv"]

_L2_EPS = 1e-6


def causal_depthwise_conv(x, taps):
    """``y_t = sum_j taps[j] * x_{t - (K - 1) + j}`` with zeros before the
    first token.  x: [batch, seq, channels]; taps: [K, channels]."""
    k, s = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, j:j + s] * taps[j].astype(x.dtype)
               for j in range(k))


def _l2_norm(x):
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt(jnp.sum(jnp.square(xf), axis=-1,
                                       keepdims=True) + _L2_EPS)
            ).astype(x.dtype)


class KimiDeltaAttention(Module):
    def __init__(self, dim: int, num_heads: int, head_dim: int, *,
                 conv_size: int = 4, gate_rank: int = 128,
                 eps: float = 1e-5, init_std: float = 0.02,
                 conv_init_std: float = 0.29, dtype=jnp.float32,
                 interpret=None):
        init = normal(stddev=init_std)
        conv_init = normal(stddev=conv_init_std)
        hk = num_heads * head_dim
        for n in ("q", "k", "v"):
            setattr(self, f"w{n}", init(next_key(), (dim, hk), dtype))
            setattr(self, f"w{n}_axes", ("embed", "heads"))
            setattr(self, f"conv_{n}", conv_init(next_key(), (conv_size, hk),
                                                 dtype))
        self.wa_down = init(next_key(), (dim, gate_rank), dtype)
        self.wa_up = init(next_key(), (gate_rank, hk), dtype)
        # the decay gate's own scale and offset, float32: A in [1, 16], and
        # softplus(dt_bias) log-uniform in [1e-3, 1e-1]
        self.a_log = jnp.log(jax.random.uniform(
            next_key(), (num_heads,), jnp.float32, 1.0, 16.0))
        dt = jnp.exp(jax.random.uniform(
            next_key(), (hk,), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
        self.dt_bias = dt + jnp.log(-jnp.expm1(-dt))
        self.wb = init(next_key(), (dim, num_heads), dtype)
        self.wg_down = init(next_key(), (dim, gate_rank), dtype)
        self.wg_up = init(next_key(), (gate_rank, hk), dtype)
        self.o_norm = RMSNorm(head_dim, eps=eps)
        self.wo = init(next_key(), (hk, dim), dtype)
        self.wo_axes = ("heads", "embed")
        self.num_heads, self.head_dim = num_heads, head_dim
        self.interpret = interpret

    def __call__(self, x):
        b, s, _ = x.shape
        h, dk = self.num_heads, self.head_dim
        heads = lambda t: t.reshape(b, s, h, dk).swapaxes(1, 2)  # [B,H,S,d]
        w = lambda a: a.astype(x.dtype)
        act = lambda n: heads(jax.nn.silu(causal_depthwise_conv(
            x @ w(getattr(self, "w" + n)), getattr(self, "conv_" + n))))
        q, k, v = _l2_norm(act("q")), _l2_norm(act("k")), act("v")
        gate = heads((x @ w(self.wa_down)) @ w(self.wa_up)).astype(
            jnp.float32) + self.dt_bias.reshape(h, dk)[None, :, None, :]
        g = -jnp.exp(self.a_log)[None, :, None, None] * jax.nn.softplus(gate)
        beta = jax.nn.sigmoid((x @ w(self.wb)).astype(jnp.float32)
                              ).swapaxes(1, 2)                   # [B,H,S]
        o = chunk_kda(q, k, v, g, beta, scale=dk ** -0.5,
                      interpret=self.interpret)
        out_gate = jax.nn.sigmoid(heads((x @ w(self.wg_down))
                                        @ w(self.wg_up)))
        o = (out_gate * self.o_norm(o)).swapaxes(1, 2).reshape(b, s, h * dk)
        return o @ w(self.wo)
