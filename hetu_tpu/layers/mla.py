"""Multi-head latent attention (MLA): the expanded form for training and
prefill, the absorbed form over a paged latent cache for decode.

Keys and values come from one latent a token: ``[c, k_pe] = split(W_kva
x)``, ``c = RMSNorm(c)``, ``[k_nope, v]_h = split((W_kvb c)_h)``; queries
``[q_nope, q_pe]_h = (W_q x)_h`` (no low-rank query: ``q_lora_rank`` null).
A head's key is ``[k_nope_h, k_pe]`` with ``k_pe`` shared by all heads, so
the score heads are ``nope + rope`` wide and the value heads ``v_head_dim``.

``rope=None`` (``mla_use_nope``, Kimi-Linear) applies no rotary to the
``pe`` parts.  With a :class:`YarnRope` (DeepSeek-V2) ``q_pe`` a head and
``k_pe`` once are rotated by the token's position at YaRN's frequencies,
and the softmax scale carries YaRN's correction ``m(mscale_all_dim)^2``.

What a cached token holds is ``c`` after its norm and ``k_pe`` after its
rotation, ``kv_lora_rank + qk_rope_head_dim`` values, once, in pages laid
out token-minor, ``[layers, pages, width, page]`` (``ops.pallas.
paged_mla_decode`` says why).  :meth:`prefill`
runs the expanded form over a prompt and writes those into the rows'
pages; :meth:`decode` runs one new token a row in the absorbed form, the
same mathematics with ``W_kvb`` split a head into ``W_uk`` and ``W_uv``:
``a_h(t, s) = ((W_uk_h q_nope_h) . c_s + q_pe_h . k_pe_s) sigma`` and ``o_h
= W_uv_h^T sum_s p_h(t, s) c_s``, so that the cached context is never
expanded into keys and values (``ops.pallas.paged_mla_decode``).
"""

from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp
import numpy as np

from hetu_tpu.core.module import Module
from hetu_tpu.core.rng import next_key
from hetu_tpu.init import normal
from hetu_tpu.layers.attention import (dot_product_attention,
                                       paged_write_slots)
from hetu_tpu.layers.norm import RMSNorm

__all__ = ["MultiHeadLatentAttention", "YarnRope", "rotate_pairs"]


@dataclasses.dataclass(frozen=True)
class YarnRope:
    """Rotary position encoding with YaRN's frequencies (Peng et al. 2023,
    as DeepSeek-V2's modeling code applies it).  ``factor`` 1 is plain
    rotary."""

    dim: int
    theta: float = 10000.0
    factor: float = 1.0
    original_max_position: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    def inv_freq(self) -> np.ndarray:
        """``dim / 2`` angular frequencies: ``f_i = theta^(-2i/dim)``, kept
        for the pairs that turn more than ``beta_fast`` times in the
        original window, divided by ``factor`` for those that turn fewer
        than ``beta_slow`` times, a linear ramp between."""
        d = self.dim
        f = self.theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
        if self.factor == 1.0:
            return f.astype(np.float32)

        def turns_at(turns):        # the pair that turns so often
            return d * math.log(self.original_max_position
                                / (turns * 2 * math.pi)) \
                / (2 * math.log(self.theta))

        low = max(math.floor(turns_at(self.beta_fast)), 0)
        high = min(math.ceil(turns_at(self.beta_slow)), d - 1)
        ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3),
                       0.0, 1.0)
        return (f / self.factor * ramp + f * (1.0 - ramp)).astype(np.float32)

    @staticmethod
    def m(scale: float, mscale: float) -> float:
        """YaRN's attention factor ``0.1 mscale ln(scale) + 1``."""
        return 1.0 if scale <= 1.0 else 0.1 * mscale * math.log(scale) + 1.0

    def amplitude(self) -> float:
        """What cos and sin are scaled by: ``m(mscale) / m(mscale_all_dim)``
        (1 where the two are equal, as published)."""
        return self.m(self.factor, self.mscale) / self.m(
            self.factor, self.mscale_all_dim)

    def softmax_scale(self, qk_dim: int) -> float:
        """``qk_dim^(-1/2)`` times ``m(mscale_all_dim)^2``."""
        return qk_dim ** -0.5 * self.m(self.factor, self.mscale_all_dim) ** 2


def rotate_pairs(x, positions, rope: YarnRope):
    """``x [..., dim]`` with its pairs ``(2i, 2i + 1)`` rotated by
    ``positions * inv_freq_i``; ``positions`` has ``x``'s leading shape up
    to broadcasting.  Computed in float32."""
    angle = positions[..., None].astype(jnp.float32) * rope.inv_freq()
    cos = jnp.cos(angle) * rope.amplitude()
    sin = jnp.sin(angle) * rope.amplitude()
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


class MultiHeadLatentAttention(Module):
    """``attn_fn(q, k, v, causal=True, scale=...)`` takes and returns the
    kernel layout [batch, heads, seq, width] (``ops.pallas.
    flash_attention_bhsd``, which reads the two widths from its operands);
    without one the scores are materialised."""

    def __init__(self, dim: int, num_heads: int, *, kv_lora_rank: int,
                 qk_nope_head_dim: int, qk_rope_head_dim: int,
                 v_head_dim: int, eps: float = 1e-5, init_std: float = 0.02,
                 attn_fn=None, rope: YarnRope | None = None,
                 interpret=None, dtype=jnp.float32):
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
        if rope is not None:
            # not stored where no rotary is applied: a model built before
            # rotary existed flattens to the tree it always had
            self.rotary = rope
            self.interpret = interpret

    def _scale(self) -> float:
        rotary = getattr(self, "rotary", None)
        qk = self.nope + self.rope
        return qk ** -0.5 if rotary is None else rotary.softmax_scale(qk)

    def _projections(self, x, positions):
        """q ``[..., heads, nope + rope]``, the normalised ``c`` and
        ``k_pe``, the ``pe`` parts rotated where the layer has a rotary."""
        h, nope = self.num_heads, self.nope
        w = lambda a: a.astype(x.dtype)
        q = (x @ w(self.wq)).reshape(x.shape[:-1] + (h, nope + self.rope))
        kva = x @ w(self.wkva)
        c = self.kv_norm(kva[..., :self.kv_lora_rank])
        k_pe = kva[..., self.kv_lora_rank:]
        rotary = getattr(self, "rotary", None)
        if rotary is not None:
            q = jnp.concatenate([q[..., :nope], rotate_pairs(
                q[..., nope:], positions[..., None], rotary)], axis=-1)
            k_pe = rotate_pairs(k_pe, positions, rotary)
        return q, c, k_pe

    def _expanded(self, q, c, k_pe):
        """Causal attention of q over the keys and values that ``c`` and
        ``k_pe`` expand to, all ``[batch, seq, ...]``."""
        b, s = c.shape[:2]
        h, nope, rope, vd = self.num_heads, self.nope, self.rope, self.v_dim
        kvb = (c @ self.wkvb.astype(c.dtype)).reshape(b, s, h, nope + vd)
        k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
            k_pe[:, :, None, :], (b, s, h, rope))], axis=-1)
        v = kvb[..., nope:]
        scale = self._scale()
        if self.attn_fn is None:
            o = dot_product_attention(q, k, v, scale=scale, causal=True)
        else:
            o = self.attn_fn(q.swapaxes(1, 2), k.swapaxes(1, 2),
                             v.swapaxes(1, 2), causal=True,
                             scale=scale).swapaxes(1, 2)
        return o.reshape(b, s, h * vd) @ self.wo.astype(c.dtype)

    def __call__(self, x, positions=None):
        if positions is None and hasattr(self, "rotary"):
            positions = jnp.broadcast_to(jnp.arange(x.shape[1]),
                                         x.shape[:2])
        return self._expanded(*self._projections(x, positions))

    # -- serving: a paged latent cache --------------------------------------

    def prefill(self, x, cache, page_idx, *, layer: int):
        """A prompt from its first token on, ``x [batch, bucket, dim]``:
        the expanded form over the bucket, and every position's latent
        written into the row's pages (``page_idx [batch, pages_per_seq]``;
        positions past the row's allocation land in the scratch page its
        table is padded with).  Returns ``(out, cache)``."""
        b, s = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        q, c, k_pe = self._projections(x, positions)
        page = cache.shape[-1]
        n = -(-s // page)
        latent = jnp.concatenate([c, k_pe], axis=-1).astype(cache.dtype)
        latent = jnp.pad(latent, ((0, 0), (0, n * page - s), (0, 0)))
        cache = cache.at[layer, page_idx[:, :n]].set(
            latent.reshape(b, n, page, latent.shape[-1]).swapaxes(-1, -2))
        return self._expanded(q, c, k_pe), cache

    def decode(self, x, cache, page_tables, lengths, *, layer: int):
        """One new token a row, ``x [batch, dim]`` at position
        ``lengths[b]``: its latent written at that index of the row's
        pages, then the absorbed form over the ``lengths + 1`` cached
        latents, read in place.  Returns ``(out [batch, dim], cache)``."""
        from hetu_tpu.ops.pallas.paged_mla_decode import paged_mla_decode
        b = x.shape[0]
        h, nope, vd, r = (self.num_heads, self.nope, self.v_dim,
                          self.kv_lora_rank)
        q, c, k_pe = self._projections(x, lengths)
        page_of, slot = paged_write_slots(page_tables, lengths,
                                          cache.shape[-1])
        # the new column goes in by whole pages, read, changed and written
        # back: scattering 576 values down a column makes the v5e compiler
        # re-lay the whole pool width-minor for the scatter and copy it
        # back row-major for the kernel, twice 3.4 GB a step (seen in the
        # compiled program); a page a row is 7 MB a layer
        pages = cache[layer, page_of]                      # (b, W, page)
        lane = jnp.arange(cache.shape[-1])[None, None, :]
        latent = jnp.concatenate([c, k_pe], axis=-1).astype(cache.dtype)
        cache = cache.at[layer, page_of].set(jnp.where(
            lane == slot[:, None, None], latent[:, :, None], pages))
        wkvb = self.wkvb.astype(x.dtype).reshape(r, h, nope + vd)
        q_abs = jnp.concatenate([
            jnp.einsum("bhn,rhn->bhr", q[..., :nope], wkvb[..., :nope]),
            q[..., nope:]], axis=-1)
        o = paged_mla_decode(q_abs, cache, page_tables, lengths + 1,
                             value_width=r, scale=self._scale(), layer=layer,
                             interpret=self.interpret)
        o = jnp.einsum("bhr,rhv->bhv", o, wkvb[..., nope:])
        return o.reshape(b, h * vd) @ self.wo.astype(x.dtype), cache
