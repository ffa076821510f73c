"""AFMoE (``model_type`` ``afmoe``, Arcee's Trinity): a decoder whose layers
mix sliding-window and full attention over grouped KV heads, with a sparse
feed-forward, and the entry points ``serve.ServingEngine`` calls.

``x_0 = sqrt(hidden) E[token]`` (muP's embedding multiplier).  Block l has
four RMSNorms: ``h = x + N2(Attn(N1(x)))``, ``x' = h + N4(FFN(N3(h)))``.
``Attn`` is ``layers.GroupedQueryAttention``: RMSNorm a head on q and k, an
output gate, and by the layer's type (``layer_types``) either a window with
rotary on q and k (``sliding_attention``) or neither (``full_attention``:
no position encoding at all).  ``FFN`` is a dense ``SwiGLU`` for the first
``num_dense_layers`` layers and ``layers.HeldExpertsMoE`` under a sigmoid
router after (the chosen weights normalised over the chosen and scaled by
``route_scale``, the shared expert added once).  A last RMSNorm and an
untied head.

Served, the cache is two GROUPS of layers (:meth:`Afmoe.cache_spec`): the
window layers', whose sequence holds a ring of ``window / page + 1`` pages
however long it grows, and the full layers', whose sequence holds every
token; each group has its own pool of pages and its own table a sequence,
and ``prefill`` and ``decode`` are handed the groups' arrays one after
another and one table a group.
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
from hetu_tpu.layers.attention import GroupedQueryAttention
from hetu_tpu.layers.cache import GroupedCacheSpec, ring_order
from hetu_tpu.layers.moe import HeldExpertsMoE
from hetu_tpu.layers.transformer import SwiGLU
from hetu_tpu.models.deepseek_v2 import _add_routing

__all__ = ["AfmoeConfig", "AfmoeBlock", "Afmoe"]

WINDOW, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    """Defaults are Trinity-Mini's published values."""

    vocab_size: int = 200192
    hidden_size: int = 2048
    layer_types: tuple = ((WINDOW,) * 3 + (FULL,)) * 8
    num_dense_layers: int = 2
    intermediate_size: int = 6144
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 2048
    rope_theta: float = 10000.0
    # the router's width, and the experts of it whose weights live here
    num_experts: int = 128
    held_experts: tuple = tuple(range(128))
    top_k: int = 8
    moe_intermediate_size: int = 1024
    num_shared_experts: int = 1
    route_scale: float = 2.826
    mup_enabled: bool = True
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 131072
    initializer_range: float = 0.02
    # None: kernels compiled on a TPU, interpreted on the CPU
    pallas_interpret: object = None
    dtype: object = jnp.float32

    def __post_init__(self):
        bad = set(self.layer_types) - {WINDOW, FULL}
        if bad or not self.layer_types:
            raise ValueError(f"layer_types holds {sorted(bad)}; each is "
                             f"{WINDOW!r} or {FULL!r}")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    def layers_of(self, kind: str) -> tuple:
        return tuple(l for l, t in enumerate(self.layer_types) if t == kind)


@functools.lru_cache(maxsize=None)
def _flash_attn(interpret):
    """One callable an ``interpret`` value, so that two models of one
    configuration flatten to the same tree."""
    from hetu_tpu.ops.pallas import flash_attention_bhsd
    return functools.partial(flash_attention_bhsd, interpret=interpret)


class AfmoeBlock(Module):
    def __init__(self, cfg: AfmoeConfig, layer: int):
        d, std, dt = cfg.hidden_size, cfg.initializer_range, cfg.dtype
        for n in ("norm1", "norm2", "norm3", "norm4"):
            setattr(self, n, RMSNorm(d, eps=cfg.rms_norm_eps))
        self.windowed = cfg.layer_types[layer] == WINDOW
        self.attn = GroupedQueryAttention(
            d, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            window=cfg.sliding_window if self.windowed else None,
            rope_theta=cfg.rope_theta if self.windowed else None,
            eps=cfg.rms_norm_eps, init_std=std,
            attn_fn=_flash_attn(cfg.pallas_interpret),
            interpret=cfg.pallas_interpret, dtype=dt)
        self.sparse = layer >= cfg.num_dense_layers
        if self.sparse:
            self.ffn = HeldExpertsMoE(
                d, cfg.moe_intermediate_size, cfg.num_experts,
                cfg.held_experts, top_k=cfg.top_k, scale=cfg.route_scale,
                shared_hidden=cfg.moe_intermediate_size
                * cfg.num_shared_experts, init_std=std, dtype=dt,
                interpret=cfg.pallas_interpret, router="sigmoid")
        else:
            self.ffn = SwiGLU(d, cfg.intermediate_size, dtype=dt,
                              init_std=std)

    def __call__(self, x, mix):
        """x -> (x, the expert layer's routing counts or None); ``mix`` is
        the attention over the normed input, one of the layer's forms.

        The residual stream ``x`` is float32 whatever the weights' dtype: it
        is a sum of as many terms of one size as there are sub-layers (each
        leaves its own norm at unit scale), and rounding the running sum to
        bfloat16 at every add would cost more than all the sub-layers'
        arithmetic does (what moves a token across the edge of its router's
        top k is the noise in ``x``).  The sub-layers read it through their
        norm in the weights' dtype and the norm after them widens what they
        return."""
        f32, dt = jnp.float32, self.attn.wq.dtype
        with jax.named_scope("afmoe.attn.window" if self.windowed
                             else "afmoe.attn.full"):
            x = x + self.norm2(mix(self.norm1(x).astype(dt)).astype(f32))
        h = self.norm3(x).astype(dt)
        if not self.sparse:
            with jax.named_scope("afmoe.dense_ffn"):
                return x + self.norm4(self.ffn(h).astype(f32)), None
        with jax.named_scope("afmoe.moe"):      # over the layer's moe.*
            y, stats = self.ffn.infer(h)
            return x + self.norm4(y.astype(f32)), stats


class Afmoe(Module):
    def __init__(self, cfg: AfmoeConfig):
        init = normal(stddev=cfg.initializer_range)
        self.embed = Embedding(cfg.vocab_size, cfg.hidden_size,
                               initializer=init, dtype=cfg.dtype)
        self.blocks = [AfmoeBlock(cfg, l) for l in range(cfg.num_layers)]
        self.norm_f = RMSNorm(cfg.hidden_size, eps=cfg.rms_norm_eps)
        self.lm_head = init(next_key(), (cfg.hidden_size, cfg.vocab_size),
                            cfg.dtype)
        self.lm_head_axes = ("embed", "vocab")
        self.config = cfg

    def _embed(self, tokens):
        x = self.embed(tokens).astype(jnp.float32)   # the residual stream
        if self.config.mup_enabled:
            x = x * self.config.hidden_size ** 0.5
        return x

    def _blocks(self, x, cache, attend):
        """x through every block, ``attend(attn, h, (k, v) of the layer's
        group, group, layer within it) -> (out, (k, v))`` being the form its
        attention takes: ``(x, cache, routing counts over the expert
        layers)``.  ``cache`` is ``(k, v)`` of the window group, then of
        the full group, or ``None``."""
        groups = None if cache is None else [cache[:2], cache[2:]]
        seen = [0, 0]
        routing = {}
        for blk in self.blocks:
            g = 0 if blk.windowed else 1

            def mix(h):
                out, kv = attend(blk.attn, h,
                                 None if groups is None else groups[g], g,
                                 seen[g])
                if groups is not None:
                    groups[g] = kv
                return out
            x, stats = blk(x, mix)
            seen[g] += 1
            routing = _add_routing(routing, stats)
        return (x, None if groups is None else (*groups[0], *groups[1]),
                routing)

    def hidden_states(self, input_ids):
        """The whole sequence at once, no cache: (hidden states after the
        last norm, routing counts over the expert layers)."""
        x, _, routing = self._blocks(
            self._embed(input_ids), None,
            lambda attn, h, kv, g, li: (attn(h), kv))
        return self.norm_f(x), routing       # float32, as the stream is

    def __call__(self, input_ids):
        """Logits [batch, seq, vocab]."""
        x, _ = self.hidden_states(input_ids)
        return x.astype(self.lm_head.dtype) @ self.lm_head

    # -- what serve.ServingEngine asks of a model it serves ------------------

    def head(self):
        return self.lm_head, 1

    def cache_spec(self) -> GroupedCacheSpec:
        """Two groups of layers, ``window`` and ``full``, each of keys and
        values of ``num_kv_heads`` heads in head-major pages: ``2 x
        num_kv_heads x head_dim`` values a token a layer, and in a window
        layer only the last ``sliding_window`` tokens'."""
        cfg = self.config
        by_kind = {}
        for blk in self.blocks:
            by_kind.setdefault(blk.windowed, blk.attn)
        return GroupedCacheSpec(tuple(
            by_kind[windowed].cache_spec(len(cfg.layers_of(kind)),
                                         cfg.dtype, name)
            for windowed, kind, name in ((True, WINDOW, "window"),
                                         (False, FULL, "full"))
            if windowed in by_kind))

    def _check_groups(self, cache):
        if len(cache) != 4:
            raise ValueError(
                "this model is served from a window group and a full "
                "group of layers; layer_types needs both kinds")

    def prefill(self, cache, page_idx, cache_index, tokens, seq_lengths):
        """A prompt bucket a row from its first token on (``cache_index``
        is not read: a prompt whose head is already cached is the engine's
        prefix sharing, which a grouped pool refuses): flash attention over
        the bucket, each layer's keys and values written into its group's
        pages (``page_idx``: the window group's ring, the full group's
        table), and the logits at each row's last valid position:
        ``(logits, cache, routing counts)``."""
        del cache_index
        self._check_groups(cache)
        if seq_lengths is None:
            seq_lengths = jnp.full((tokens.shape[0],), tokens.shape[1],
                                   jnp.int32)
        x, cache, routing = self._blocks(
            self._embed(tokens), cache, lambda attn, h, kv, g, li:
            attn.prefill(h, kv, page_idx[g], seq_lengths, layer=li))
        last = jnp.take_along_axis(
            x, (seq_lengths - 1)[:, None, None], axis=1)[:, 0]
        last = self.norm_f(last).astype(self.lm_head.dtype)
        return last @ self.lm_head, cache, routing

    def decode(self, cache, page_tables, lengths, tokens):
        """One token a row (``tokens [rows, 1]`` at position ``lengths``)
        over the pages read in place, the window layers over their ring in
        the order of its positions: ``(hidden states of the new tokens,
        cache, routing counts)``."""
        self._check_groups(cache)
        page = cache[0].shape[-2]
        ring, first = ring_order(page_tables[0], lengths + 1, page)
        tables, firsts = (ring, page_tables[1]), (first, None)
        x, cache, routing = self._blocks(
            self._embed(tokens[:, 0]), cache, lambda attn, h, kv, g, li:
            attn.decode(h, kv, tables[g], lengths, layer=li,
                        first_position=firsts[g]))
        return self.norm_f(x).astype(self.lm_head.dtype), cache, routing
