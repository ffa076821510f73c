"""Kimi-Linear (``model_type`` ``kimi_linear``): a hybrid decoder of
linear-attention (KDA) and latent-attention (MLA) layers over a sparse
feed-forward whose experts are shared out over chips.

Pre-norm RMSNorm blocks, ``x = x + Mix(norm(x)); x = x + FFN(norm(x))``;
``Mix`` is ``layers.KimiDeltaAttention`` for the layers named in
``kda_layers`` and ``layers.MultiHeadLatentAttention`` (no rotary) for those
in ``full_attn_layers``, numbered from 1 as the published configuration
numbers them; ``FFN`` is a dense ``SwiGLU`` for the first
``first_k_dense`` layers and ``layers.HeldExpertsMoE`` after: a router of
the published width over the experts this chip holds.  No position
encoding anywhere; a last RMSNorm and an untied head.  Training only:
serving it needs a cache for the latents and a place for KDA's state
(ROADMAP R0, R3, R5).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from hetu_tpu.core.module import Module, maybe_remat
from hetu_tpu.core.rng import next_key
from hetu_tpu.init import normal
from hetu_tpu.layers import Embedding, RMSNorm
from hetu_tpu.layers.kda import KimiDeltaAttention
from hetu_tpu.layers.mla import MultiHeadLatentAttention
from hetu_tpu.layers.moe import HeldExpertsMoE
from hetu_tpu.layers.transformer import SwiGLU
from hetu_tpu.ops import softmax_cross_entropy_sparse

__all__ = ["KimiLinearConfig", "KimiLinearBlock", "KimiLinear"]


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    vocab_size: int = 163840
    hidden_size: int = 2304
    num_layers: int = 27
    # 1-based, as published (linear_attn_config)
    kda_layers: tuple = (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                         21, 22, 23, 25, 26)
    full_attn_layers: tuple = (4, 8, 12, 16, 20, 24, 27)
    first_k_dense: int = 1
    intermediate_size: int = 9216
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    conv_size: int = 4
    kda_gate_rank: int = 128
    num_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # the router's width, and the experts of it whose weights live here
    num_experts: int = 256
    held_experts: tuple = tuple(range(256))
    top_k: int = 8
    moe_intermediate_size: int = 1024
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.446
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    conv_initializer_range: float = 0.29
    # None: kernels compiled on a TPU, interpreted on the CPU
    pallas_interpret: object = None
    # per-block rematerialization policy (hetu_tpu.mem.policy registry)
    remat: object = "none"
    dtype: object = jnp.float32

    def __post_init__(self):
        from hetu_tpu.mem.policy import normalize_remat_field
        normalize_remat_field(self)
        mixers = sorted(self.kda_layers + self.full_attn_layers)
        if mixers != list(range(1, self.num_layers + 1)):
            raise ValueError(
                f"kda_layers {self.kda_layers} and full_attn_layers "
                f"{self.full_attn_layers} do not cover layers 1 to "
                f"{self.num_layers} once each")


@functools.lru_cache(maxsize=None)
def _flash_attn(interpret):
    """One callable an ``interpret`` value, so that two models of one
    configuration flatten to the same tree."""
    from hetu_tpu.ops.pallas import flash_attention_bhsd
    return functools.partial(flash_attention_bhsd, interpret=interpret)


class KimiLinearBlock(Module):
    def __init__(self, cfg: KimiLinearConfig, layer: int):
        d, std, dt = cfg.hidden_size, cfg.initializer_range, cfg.dtype
        self.norm1 = RMSNorm(d, eps=cfg.rms_norm_eps)
        self.norm2 = RMSNorm(d, eps=cfg.rms_norm_eps)
        self.linear = layer in cfg.kda_layers
        if self.linear:
            self.mix = KimiDeltaAttention(
                d, cfg.kda_num_heads, cfg.kda_head_dim,
                conv_size=cfg.conv_size, gate_rank=cfg.kda_gate_rank,
                eps=cfg.rms_norm_eps, init_std=std,
                conv_init_std=cfg.conv_initializer_range, dtype=dt,
                interpret=cfg.pallas_interpret)
        else:
            self.mix = MultiHeadLatentAttention(
                d, cfg.num_heads, kv_lora_rank=cfg.kv_lora_rank,
                qk_nope_head_dim=cfg.qk_nope_head_dim,
                qk_rope_head_dim=cfg.qk_rope_head_dim,
                v_head_dim=cfg.v_head_dim, eps=cfg.rms_norm_eps,
                init_std=std, attn_fn=_flash_attn(cfg.pallas_interpret),
                dtype=dt)
        self.sparse = layer > cfg.first_k_dense
        if self.sparse:
            self.ffn = HeldExpertsMoE(
                d, cfg.moe_intermediate_size, cfg.num_experts,
                cfg.held_experts, top_k=cfg.top_k,
                scale=cfg.routed_scaling_factor,
                shared_hidden=cfg.moe_intermediate_size
                * cfg.num_shared_experts, init_std=std, dtype=dt,
                interpret=cfg.pallas_interpret)
        else:
            self.ffn = SwiGLU(d, cfg.intermediate_size, dtype=dt,
                              init_std=std)

    def __call__(self, x):
        """x -> (x, the expert layer's routing counts or None)."""
        with jax.named_scope("kimi.kda" if self.linear else "kimi.mla"):
            x = x + self.mix(self.norm1(x))
        h = self.norm2(x)
        if not self.sparse:
            with jax.named_scope("kimi.dense_ffn"):
                return x + self.ffn(h), None
        with jax.named_scope("kimi.moe"):   # over the layer's own moe.*
            y, stats = self.ffn(h)
        return x + y, stats


class KimiLinear(Module):
    def __init__(self, cfg: KimiLinearConfig):
        init = normal(stddev=cfg.initializer_range)
        self.embed = Embedding(cfg.vocab_size, cfg.hidden_size,
                               initializer=init, dtype=cfg.dtype)
        self.blocks = [KimiLinearBlock(cfg, l)
                       for l in range(1, cfg.num_layers + 1)]
        self.norm_f = RMSNorm(cfg.hidden_size, eps=cfg.rms_norm_eps)
        self.head = init(next_key(), (cfg.hidden_size, cfg.vocab_size),
                         cfg.dtype)
        self.head_axes = ("embed", "vocab")
        self.config = cfg

    def hidden_states(self, input_ids):
        """(hidden states after the last norm, routing counts over the
        expert layers: pairs on held experts, all pairs, held experts that
        got a row, and the worst layer's busiest held expert over its
        mean)."""
        x = self.embed(input_ids)
        step = maybe_remat(lambda blk, xx: blk(xx), self.config.remat)
        routing = {"moe_held": jnp.int32(0), "moe_assignments": jnp.int32(0),
                   "moe_experts_hit": jnp.int32(0),
                   "moe_load_max_over_mean": jnp.float32(0.0)}
        for blk in self.blocks:
            x, stats = step(blk, x)
            if stats is not None:
                routing = {
                    "moe_held": routing["moe_held"] + stats["held"],
                    "moe_assignments": routing["moe_assignments"]
                    + stats["assignments"],
                    "moe_experts_hit": routing["moe_experts_hit"]
                    + stats["experts_hit"],
                    "moe_load_max_over_mean": jnp.maximum(
                        routing["moe_load_max_over_mean"],
                        stats["load_max_over_mean"])}
        return self.norm_f(x), routing

    def __call__(self, input_ids):
        """Logits [batch, seq, vocab]."""
        x, _ = self.hidden_states(input_ids)
        return x @ self.head.astype(x.dtype)

    def loss(self, input_ids, labels):
        """Mean cross entropy of ``labels`` (the next token at every
        position, drawn by the caller) and the routing counts as
        metrics."""
        x, routing = self.hidden_states(input_ids)
        logits = x @ self.head.astype(x.dtype)
        nll = softmax_cross_entropy_sparse(logits, labels)
        return nll.mean(), routing
