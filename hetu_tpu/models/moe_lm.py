"""MoE transformer LM — the expert-parallel benchmark model family
(reference: examples/moe/test_moe_top.py:44-56 — model_dim 2048 decoder with
per-device experts and (H)AllToAll; gates from examples/moe/scripts/).

Which MoE path this is: the capacity gates of ``layers/moe.py``
(``moe_transformer_mlp``: ``TopKGate`` into padded ``[E, C, d]`` buckets,
tokens past capacity dropped, two-matrix GELU experts, an auxiliary balance
loss).  The other path, ``layers.HeldExpertsMoE`` (a router of the published
width over the experts one chip holds, no capacity, no drop, gated experts),
belongs to ``models/kimi_linear.py``; the two share no code.

TPU-native composition: one definition serves dp/ep/sp simultaneously —
experts shard over ``ep`` (layers/moe.py), attention optionally runs
ring/Ulysses sequence parallelism over ``sp`` (parallel/ring_attention.py),
the batch shards over ``dp`` (and ``ep``), all in one jitted train step.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from hetu_tpu.core.module import Module, maybe_remat
from hetu_tpu.core.rng import next_key
from hetu_tpu.init import normal
from hetu_tpu.layers import Embedding, LayerNorm, MultiHeadAttention
from hetu_tpu.layers.moe import MoELayer, moe_transformer_mlp
from hetu_tpu.ops import softmax_cross_entropy_sparse

__all__ = ["MoELMConfig", "MoEBlock", "MoELM"]


@dataclasses.dataclass(frozen=True)
class MoELMConfig:
    vocab_size: int = 32000
    hidden_size: int = 512
    num_layers: int = 4
    num_heads: int = 8
    num_experts: int = 8
    mlp_ratio: int = 4
    top_k: int = 2
    capacity_factor: float = 1.25
    max_seq_len: int = 1024
    aux_weight: float = 0.01
    initializer_range: float = 0.02
    # thread per-step routing observability (capacity-overflow fraction +
    # expert-load entropy, layer-averaged) into the loss metrics, where
    # the Trainer/Logger pick them up — the numbers that catch silent
    # router collapse or capacity starvation (layers.moe.routing_stats)
    log_routing_stats: bool = False
    # per-block rematerialization policy (hetu_tpu.mem.policy registry):
    # exact numerics; the backward recomputes what the policy drops,
    # including the expert dispatch.  Legacy booleans deprecation-warned.
    remat: object = "none"
    dtype: object = jnp.float32

    def __post_init__(self):
        from hetu_tpu.mem.policy import normalize_remat_field
        normalize_remat_field(self)


class MoEBlock(Module):
    """Pre-LN attention + MoE FFN (reference moe examples replace every
    FFN; every-other-layer variants just pass moe=None)."""

    def __init__(self, cfg: MoELMConfig, *, mesh=None, attn_fn=None,
                 use_moe: bool = True):
        d = cfg.hidden_size
        self.ln1 = LayerNorm(d)
        self.attn = MultiHeadAttention(d, cfg.num_heads, causal=True,
                                       attn_fn=attn_fn, dtype=cfg.dtype)
        self.ln2 = LayerNorm(d)
        self.moe = moe_transformer_mlp(
            d, cfg.mlp_ratio * d, cfg.num_experts, k=cfg.top_k,
            capacity_factor=cfg.capacity_factor, mesh=mesh, dtype=cfg.dtype,
        ) if use_moe else None

    def __call__(self, x, *, training: bool = False,
                 with_stats: bool = False):
        x = x + self.attn(self.ln1(x))
        if self.moe is None:
            zero = jnp.float32(0.0)
            return x, ((zero, None) if with_stats else zero)
        y, aux = self.moe(self.ln2(x), training=training,
                          with_stats=with_stats)
        return x + y, aux


class MoELM(Module):
    """Decoder-only MoE LM; returns (logits, total_aux_loss)."""

    def __init__(self, cfg: MoELMConfig, *, mesh=None, attn_fn=None):
        init = normal(stddev=cfg.initializer_range)
        self.wte = Embedding(cfg.vocab_size, cfg.hidden_size,
                             initializer=init, dtype=cfg.dtype)
        self.wpe = Embedding(cfg.max_seq_len, cfg.hidden_size,
                             initializer=init, dtype=cfg.dtype,
                             axes=(None, "embed"))
        self.blocks = [
            MoEBlock(cfg, mesh=mesh, attn_fn=attn_fn)
            for _ in range(cfg.num_layers)
        ]
        self.ln_f = LayerNorm(cfg.hidden_size)
        self.config = cfg

    def __call__(self, input_ids, *, training: bool = False,
                 with_stats: bool = False):
        s = input_ids.shape[-1]
        x = self.wte(input_ids) + self.wpe(jnp.arange(s))
        aux_total = 0.0
        stats_acc, n_moe = None, 0
        step = maybe_remat(
            lambda b, xx: b(xx, training=training, with_stats=with_stats),
            self.config.remat)
        for blk in self.blocks:
            x, aux = step(blk, x)
            if with_stats:
                aux, stats = aux
                if stats is not None:
                    n_moe += 1
                    stats_acc = stats if stats_acc is None else {
                        k: stats_acc[k] + v for k, v in stats.items()}
            aux_total = aux_total + aux
        x = self.ln_f(x)
        logits = x @ self.wte.weight.T.astype(x.dtype)
        if with_stats:
            stats = ({k: v / n_moe for k, v in stats_acc.items()}
                     if stats_acc else {})
            return logits, (aux_total, stats)
        return logits, aux_total

    def loss(self, input_ids, *, training: bool = True):
        with_stats = self.config.log_routing_stats
        out = self(input_ids, training=training, with_stats=with_stats)
        metrics = {}
        if with_stats:
            logits, (aux, stats) = out
            metrics.update(stats)  # overflow_frac, load_entropy
        else:
            logits, aux = out
        nll = softmax_cross_entropy_sparse(logits[:, :-1], input_ids[:, 1:])
        metrics["aux"] = aux
        return nll.mean() + self.config.aux_weight * aux, metrics
