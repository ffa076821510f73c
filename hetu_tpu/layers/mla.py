"""Multi-head latent attention (MLA), expanded form for training.

Keys and values come from one latent a token: ``[c, k_pe] = split(W_kva
x)``, ``c = RMSNorm(c)``, ``[k_nope, v]_h = split((W_kvb c)_h)``; queries
``[q_nope, q_pe]_h = (W_q x)_h`` (no low-rank query: ``q_lora_rank`` null).
A head's key is ``[k_nope_h, k_pe]`` with ``k_pe`` shared by all heads, so
the score heads are ``nope + rope`` wide and the value heads ``v_head_dim``.
``rope=False`` (``mla_use_nope``) applies no rotary to the ``pe`` parts;
that is the only form here.  What a cache would hold a token is ``c`` and
``k_pe``; serving from it is not written (ROADMAP R3).
"""

from __future__ import annotations

import jax.numpy as jnp

from hetu_tpu.core.module import Module
from hetu_tpu.core.rng import next_key
from hetu_tpu.init import normal
from hetu_tpu.layers.attention import dot_product_attention
from hetu_tpu.layers.norm import RMSNorm

__all__ = ["MultiHeadLatentAttention"]


class MultiHeadLatentAttention(Module):
    """``attn_fn(q, k, v, causal=True, scale=...)`` takes and returns the
    kernel layout [batch, heads, seq, width] (``ops.pallas.
    flash_attention_bhsd``, which reads the two widths from its operands);
    without one the scores are materialised."""

    def __init__(self, dim: int, num_heads: int, *, kv_lora_rank: int,
                 qk_nope_head_dim: int, qk_rope_head_dim: int,
                 v_head_dim: int, eps: float = 1e-5, init_std: float = 0.02,
                 attn_fn=None, dtype=jnp.float32):
        init = normal(stddev=init_std)
        qk = qk_nope_head_dim + qk_rope_head_dim
        self.wq = init(next_key(), (dim, num_heads * qk), dtype)
        self.wq_axes = ("embed", "heads")
        self.wkva = init(next_key(), (dim, kv_lora_rank + qk_rope_head_dim),
                         dtype)
        self.kv_norm = RMSNorm(kv_lora_rank, eps=eps)
        self.wkvb = init(next_key(), (kv_lora_rank, num_heads * (
            qk_nope_head_dim + v_head_dim)), dtype)
        self.wkvb_axes = (None, "heads")
        self.wo = init(next_key(), (num_heads * v_head_dim, dim), dtype)
        self.wo_axes = ("heads", "embed")
        self.num_heads = num_heads
        self.kv_lora_rank = kv_lora_rank
        self.nope, self.rope, self.v_dim = (qk_nope_head_dim,
                                            qk_rope_head_dim, v_head_dim)
        self.attn_fn = attn_fn

    def __call__(self, x):
        b, s, _ = x.shape
        h, nope, rope, vd = self.num_heads, self.nope, self.rope, self.v_dim
        w = lambda a: a.astype(x.dtype)
        q = (x @ w(self.wq)).reshape(b, s, h, nope + rope)
        kva = x @ w(self.wkva)
        c = self.kv_norm(kva[..., :self.kv_lora_rank])
        k_pe = kva[..., self.kv_lora_rank:]
        kvb = (c @ w(self.wkvb)).reshape(b, s, h, nope + vd)
        k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
            k_pe[:, :, None, :], (b, s, h, rope))], axis=-1)
        v = kvb[..., nope:]
        scale = (nope + rope) ** -0.5
        if self.attn_fn is None:
            o = dot_product_attention(q, k, v, scale=scale, causal=True)
        else:
            o = self.attn_fn(q.swapaxes(1, 2), k.swapaxes(1, 2),
                             v.swapaxes(1, 2), causal=True,
                             scale=scale).swapaxes(1, 2)
        return o.reshape(b, s, h * vd) @ w(self.wo)
