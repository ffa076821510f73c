"""DeepSeek-V2 (``model_type`` ``deepseek_v2``): a decoder of latent
attention over a sparse feed-forward, with the entry points
``serve.ServingEngine`` calls.

Pre-norm RMSNorm blocks, ``x = x + MLA(norm(x)); x = x + FFN(norm(x))``.
``MLA`` is ``layers.MultiHeadLatentAttention`` with rotary on the ``pe``
parts at YaRN's frequencies; ``FFN`` is a dense ``SwiGLU`` for the first
``first_k_dense`` layers and ``layers.HeldExpertsMoE`` under a softmax
router after (the chosen weights as they stand, the shared experts as one
gated feed-forward added once).  A last RMSNorm and an untied head.

Served, a cached token holds ``kv_lora_rank + qk_rope_head_dim`` values a
layer, once (:meth:`DeepseekV2.cache_spec`): prefill runs the expanded form
over the prompt and writes the latents into the pages, decode attends in
the absorbed form over the pages read in place, and both bring the expert
layers' routing counts out as ``aux``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from hetu_tpu.core.module import Module
from hetu_tpu.core.rng import next_key
from hetu_tpu.init import normal
from hetu_tpu.layers import Embedding, RMSNorm
from hetu_tpu.layers.cache import CacheSpec
from hetu_tpu.layers.mla import MultiHeadLatentAttention, YarnRope
from hetu_tpu.layers.moe import HeldExpertsMoE
from hetu_tpu.layers.transformer import SwiGLU

__all__ = ["DeepseekV2Config", "DeepseekV2Block", "DeepseekV2"]


@dataclasses.dataclass(frozen=True)
class DeepseekV2Config:
    """Defaults are DeepSeek-V2-Lite's published values."""

    vocab_size: int = 102400
    hidden_size: int = 2048
    num_layers: int = 27
    first_k_dense: int = 1
    intermediate_size: int = 10944
    num_heads: int = 16
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # the router's width, and the experts of it whose weights live here
    num_experts: int = 64
    held_experts: tuple = tuple(range(64))
    top_k: int = 6
    moe_intermediate_size: int = 1408
    num_shared_experts: int = 2
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 163840
    rope_theta: float = 10000.0
    # YaRN (``rope_scaling``); factor 1 is plain rotary
    rope_factor: float = 40.0
    rope_original_max_position: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.707
    rope_mscale_all_dim: float = 0.707
    initializer_range: float = 0.02
    # None: kernels compiled on a TPU, interpreted on the CPU
    pallas_interpret: object = None
    dtype: object = jnp.float32

    def rope(self) -> YarnRope:
        return YarnRope(
            dim=self.qk_rope_head_dim, theta=self.rope_theta,
            factor=self.rope_factor,
            original_max_position=self.rope_original_max_position,
            beta_fast=self.rope_beta_fast, beta_slow=self.rope_beta_slow,
            mscale=self.rope_mscale, mscale_all_dim=self.rope_mscale_all_dim)


@functools.lru_cache(maxsize=None)
def _flash_attn(interpret):
    """One callable an ``interpret`` value, so that two models of one
    configuration flatten to the same tree."""
    from hetu_tpu.ops.pallas import flash_attention_bhsd
    return functools.partial(flash_attention_bhsd, interpret=interpret)


class DeepseekV2Block(Module):
    def __init__(self, cfg: DeepseekV2Config, layer: int):
        d, std, dt = cfg.hidden_size, cfg.initializer_range, cfg.dtype
        self.norm1 = RMSNorm(d, eps=cfg.rms_norm_eps)
        self.norm2 = RMSNorm(d, eps=cfg.rms_norm_eps)
        self.attn = MultiHeadLatentAttention(
            d, cfg.num_heads, kv_lora_rank=cfg.kv_lora_rank,
            qk_nope_head_dim=cfg.qk_nope_head_dim,
            qk_rope_head_dim=cfg.qk_rope_head_dim,
            v_head_dim=cfg.v_head_dim, eps=cfg.rms_norm_eps, init_std=std,
            attn_fn=_flash_attn(cfg.pallas_interpret), rope=cfg.rope(),
            interpret=cfg.pallas_interpret, dtype=dt)
        self.sparse = layer >= cfg.first_k_dense
        if self.sparse:
            self.ffn = HeldExpertsMoE(
                d, cfg.moe_intermediate_size, cfg.num_experts,
                cfg.held_experts, top_k=cfg.top_k,
                scale=cfg.routed_scaling_factor,
                shared_hidden=cfg.moe_intermediate_size
                * cfg.num_shared_experts, init_std=std, dtype=dt,
                interpret=cfg.pallas_interpret, router="softmax")
        else:
            self.ffn = SwiGLU(d, cfg.intermediate_size, dtype=dt,
                              init_std=std)

    def __call__(self, x, mix):
        """x -> (x, the expert layer's routing counts or None); ``mix`` is
        the attention over the normed input, one of the layer's forms."""
        with jax.named_scope("deepseek.mla"):
            x = x + mix(self.norm1(x))
        h = self.norm2(x)
        if not self.sparse:
            with jax.named_scope("deepseek.dense_ffn"):
                return x + self.ffn(h), None
        with jax.named_scope("deepseek.moe"):   # over the layer's moe.*
            y, stats = self.ffn.infer(h)
        return x + y, stats


def _add_routing(routing: dict, stats) -> dict:
    """The counts of one more expert layer folded into a program's."""
    if stats is None:
        return routing
    if not routing:
        routing = {"moe_held": jnp.int32(0), "moe_assignments": jnp.int32(0),
                   "moe_experts_hit": jnp.int32(0),
                   "moe_load_max_over_mean": jnp.float32(0.0)}
    return {
        "moe_held": routing["moe_held"] + stats["held"],
        "moe_assignments": routing["moe_assignments"] + stats["assignments"],
        "moe_experts_hit": routing["moe_experts_hit"] + stats["experts_hit"],
        "moe_load_max_over_mean": jnp.maximum(
            routing["moe_load_max_over_mean"], stats["load_max_over_mean"])}


class DeepseekV2(Module):
    def __init__(self, cfg: DeepseekV2Config):
        init = normal(stddev=cfg.initializer_range)
        self.embed = Embedding(cfg.vocab_size, cfg.hidden_size,
                               initializer=init, dtype=cfg.dtype)
        self.blocks = [DeepseekV2Block(cfg, l)
                       for l in range(cfg.num_layers)]
        self.norm_f = RMSNorm(cfg.hidden_size, eps=cfg.rms_norm_eps)
        self.lm_head = init(next_key(), (cfg.hidden_size, cfg.vocab_size),
                            cfg.dtype)
        self.lm_head_axes = ("embed", "vocab")
        self.config = cfg

    def _blocks(self, x, latents, attend):
        """x through every block, ``attend(attn, h, latents, layer) -> (out,
        latents)`` being the form its attention takes: ``(x, latents,
        routing counts over the expert layers)``."""
        routing = {}
        for li, blk in enumerate(self.blocks):
            def mix(h):
                nonlocal latents
                out, latents = attend(blk.attn, h, latents, li)
                return out
            x, stats = blk(x, mix)
            routing = _add_routing(routing, stats)
        return x, latents, routing

    def hidden_states(self, input_ids):
        """The whole sequence at once, no cache: (hidden states after the
        last norm, routing counts over the expert layers)."""
        x, _, routing = self._blocks(
            self.embed(input_ids), None, lambda attn, h, lat, li: (attn(h),
                                                                   lat))
        return self.norm_f(x), routing

    def __call__(self, input_ids):
        """Logits [batch, seq, vocab]."""
        x, _ = self.hidden_states(input_ids)
        return x @ self.lm_head.astype(x.dtype)

    # -- what serve.ServingEngine asks of a model it serves ------------------

    def head(self):
        return self.lm_head, 1

    def cache_spec(self):
        """One latent a token a layer: ``kv_lora_rank + qk_rope_head_dim``
        values, the normalised ``c`` and the rotated ``k_pe``."""
        cfg = self.config
        return CacheSpec.latent(cfg.num_layers,
                                cfg.kv_lora_rank + cfg.qk_rope_head_dim,
                                cfg.dtype)

    def prefill(self, cache, page_idx, cache_index, tokens, seq_lengths):
        """A prompt bucket a row from its first token on (``cache_index``
        is not read: a prompt whose head is already cached is the engine's
        prefix sharing, which a latent pool refuses): the expanded form
        through flash attention, every position's latent written into the
        row's pages, and the logits at each row's last valid position:
        ``(logits, (latents,), routing counts)``."""
        del cache_index
        x, latents, routing = self._blocks(
            self.embed(tokens), cache[0], lambda attn, h, lat, li:
            attn.prefill(h, lat, page_idx, layer=li))
        if seq_lengths is None:
            last = x[:, -1]
        else:
            last = jnp.take_along_axis(
                x, (seq_lengths - 1)[:, None, None], axis=1)[:, 0]
        last = self.norm_f(last)
        return last @ self.lm_head.astype(last.dtype), (latents,), routing

    def decode(self, cache, page_tables, lengths, tokens):
        """One token a row (``tokens [rows, 1]`` at position ``lengths``)
        in the absorbed form over the pages read in place: ``(hidden
        states of the new tokens, (latents,), routing counts)``."""
        x, latents, routing = self._blocks(
            self.embed(tokens[:, 0]), cache[0], lambda attn, h, lat, li:
            attn.decode(h, lat, page_tables, lengths, layer=li))
        return self.norm_f(x), (latents,), routing
