"""Transformer blocks (the building material for BERT/GPT/MoE models —
reference examples/nlp/bert/hetu_bert.py layer structure, re-designed
TPU-first: pre/post-LN options, bf16 compute with fp32 norms, logical axes
for Megatron TP).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from hetu_tpu.core.module import Module
from hetu_tpu.core.rng import next_key
from hetu_tpu.init import normal, zeros
from hetu_tpu.layers.attention import MultiHeadAttention
from hetu_tpu.layers.norm import LayerNorm
from hetu_tpu.ops import dropout as dropout_op
from hetu_tpu.ops import gelu

__all__ = ["TransformerMLP", "SwiGLU", "TransformerBlock"]


class TransformerMLP(Module):
    """2-layer gelu MLP; weights annotated ('embed','mlp')/('mlp','embed')
    for Megatron column→row parallel placement."""

    def __init__(self, dim: int, hidden: int, *, dtype=jnp.float32,
                 init_std: float = 0.02):
        init = normal(stddev=init_std)
        self.w_in = init(next_key(), (dim, hidden), dtype)
        self.w_in_axes = ("embed", "mlp")
        self.b_in = zeros(None, (hidden,), dtype)
        self.b_in_axes = ("mlp",)
        self.w_out = init(next_key(), (hidden, dim), dtype)
        self.w_out_axes = ("mlp", "embed")
        self.b_out = zeros(None, (dim,), dtype)

    def __call__(self, x):
        h = gelu(x @ self.w_in.astype(x.dtype) + self.b_in.astype(x.dtype))
        return h @ self.w_out.astype(x.dtype) + self.b_out.astype(x.dtype)


class SwiGLU(Module):
    """Gated feed-forward without biases: ``(SiLU(x W_gate) * (x W_up))
    W_down``."""

    def __init__(self, dim: int, hidden: int, *, dtype=jnp.float32,
                 init_std: float = 0.02):
        init = normal(stddev=init_std)
        self.w_gate = init(next_key(), (dim, hidden), dtype)
        self.w_gate_axes = ("embed", "mlp")
        self.w_up = init(next_key(), (dim, hidden), dtype)
        self.w_up_axes = ("embed", "mlp")
        self.w_down = init(next_key(), (hidden, dim), dtype)
        self.w_down_axes = ("mlp", "embed")

    def __call__(self, x):
        h = jax.nn.silu(x @ self.w_gate.astype(x.dtype)) * (
            x @ self.w_up.astype(x.dtype))
        return h @ self.w_down.astype(x.dtype)


class TransformerBlock(Module):
    """Attention + MLP with residuals.  ``post_ln=True`` gives the original
    BERT ordering (reference hetu_bert.py); default pre-LN trains stably at
    scale.

    ``mlp`` swaps the FFN for any module with signature
    ``(x, *, training) -> y`` or ``-> (y, aux)`` — an aux-returning FFN
    (e.g. a MoE layer with its load-balancing loss, layers/moe.py MoELayer)
    makes the block return ``(x, aux)`` instead of ``x``.
    """

    def __init__(self, dim: int, num_heads: int, mlp_ratio: int = 4, *,
                 causal: bool = False, post_ln: bool = False,
                 dropout_rate: float = 0.0, attn_fn=None, mlp=None,
                 fused_ln: bool = False, dtype=jnp.float32):
        self.ln1 = LayerNorm(dim)
        self.attn = MultiHeadAttention(
            dim, num_heads, causal=causal, dropout_rate=dropout_rate,
            attn_fn=attn_fn, dtype=dtype,
        )
        self.ln2 = LayerNorm(dim)
        self.mlp = mlp if mlp is not None else TransformerMLP(
            dim, mlp_ratio * dim, dtype=dtype)
        # detect from the signature whether the FFN accepts training=
        # (MoELayer does; a plain (x)->y FFN like TransformerMLP does not)
        import inspect
        try:
            params = inspect.signature(self.mlp.__call__).parameters
            self._mlp_takes_training = "training" in params or any(
                p.kind is inspect.Parameter.VAR_KEYWORD
                for p in params.values())
        except (TypeError, ValueError):
            self._mlp_takes_training = False
        self.post_ln = post_ln
        self.dropout_rate = dropout_rate
        # Pallas fused residual+dropout+LayerNorm for the post-LN sites
        # (ops/pallas/fused_ln.py: one HBM pass per direction instead of
        # XLA's separate stat/normalize/backward-reduction passes).
        if fused_ln and not post_ln:
            raise ValueError(
                "fused_ln fuses the POST-LN residual+dropout+ln(x+y) "
                "sites; a pre-LN block normalizes the sublayer input "
                "(plain LN) and has nothing to fuse — drop the flag or "
                "set post_ln=True")
        self.fused_ln = fused_ln

    def _ffn(self, x, training):
        out = (self.mlp(x, training=training) if self._mlp_takes_training
               else self.mlp(x))
        return out if isinstance(out, tuple) else (out, None)

    def __call__(self, x, mask=None, *, key=None, training: bool = False,
                 kv_cache=None, cache_index=None, paged=None):
        if kv_cache is not None:
            return self._call_cached(x, mask, kv_cache, cache_index,
                                     paged=paged)
        ka = k1 = k2 = None
        if key is not None:
            ka, k1, k2 = jax.random.split(key, 3)
        if self.post_ln:
            if self.fused_ln:
                from hetu_tpu.ops.pallas.fused_ln import (
                    fused_residual_dropout_ln)
                rate = self.dropout_rate if training else 0.0
                a = self.attn(x, mask, key=ka, training=training)
                x = fused_residual_dropout_ln(
                    x, a, self.ln1.scale, self.ln1.bias, rate=rate,
                    key=k1, eps=self.ln1.eps)
                y, aux = self._ffn(x, training)
                x = fused_residual_dropout_ln(
                    x, y, self.ln2.scale, self.ln2.bias, rate=rate,
                    key=k2, eps=self.ln2.eps)
                return x if aux is None else (x, aux)
            x = self.ln1(x + self._drop(self.attn(x, mask, key=ka, training=training), k1, training))
            y, aux = self._ffn(x, training)
            x = self.ln2(x + self._drop(y, k2, training))
        else:
            x = x + self._drop(self.attn(self.ln1(x), mask, key=ka, training=training), k1, training)
            y, aux = self._ffn(self.ln2(x), training)
            x = x + self._drop(y, k2, training)
        return x if aux is None else (x, aux)

    def _call_cached(self, x, mask, kv_cache, cache_index, paged=None):
        """Incremental-decode step: same residual wiring as the training
        paths, attention routed through the KV cache (inference-only — no
        dropout, no fused post-LN kernel, no MoE aux loss).  Returns
        ``(x, (k_cache, v_cache))`` with this block's caches updated.
        With ``paged`` (layers.attention.PagedDecode), the caches are the
        paged pools and attention runs the in-place Pallas kernel."""
        if self.post_ln:
            a, kv = self.attn(x, mask, kv_cache=kv_cache,
                              cache_index=cache_index, paged=paged)
            x = self.ln1(x + a)
            y, aux = self._ffn(x, training=False)
            x = self.ln2(x + y)
        else:
            a, kv = self.attn(self.ln1(x), mask, kv_cache=kv_cache,
                              cache_index=cache_index, paged=paged)
            x = x + a
            y, aux = self._ffn(self.ln2(x), training=False)
            x = x + y
        if aux is not None:
            raise NotImplementedError(
                "aux-returning FFNs (MoE) have no incremental-decode path "
                "yet — serve dense blocks or drop the kv_cache")
        return x, kv

    def _drop(self, x, key, training):
        if training and self.dropout_rate > 0.0 and key is not None:
            return dropout_op(x, self.dropout_rate, key, training=True)
        return x
