"""GPT decoder-only LM (reference examples/auto_parallel GPT configs;
Galvatron's GPT target).  Pre-LN causal transformer with tied output head
option — the model family used by the auto-parallel searcher benchmarks.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from hetu_tpu.core.module import Module, maybe_remat
from hetu_tpu.init import normal
from hetu_tpu.core.rng import next_key
from hetu_tpu.layers import Embedding, LayerNorm, TransformerBlock
from hetu_tpu.layers.cache import CacheSpec, gather_views, scatter_views
from hetu_tpu.ops import softmax_cross_entropy_sparse
from hetu_tpu.ops.losses import lm_head_cross_entropy

__all__ = ["GPTConfig", "GPT", "gpt2_small", "gpt2_medium", "gpt2_large"]


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_seq_len: int = 1024
    dropout_rate: float = 0.0
    initializer_range: float = 0.02
    tie_embeddings: bool = True
    # stream the LM-head CE over vocab chunks of this size instead of
    # materializing (tokens, vocab) logits — a MEMORY knob for huge vocabs
    # / very long sequences (ops.lm_head_cross_entropy; where the logits
    # fit, the default materialized path is faster)
    streamed_head_chunk: int = 0
    # per-block rematerialization policy (hetu_tpu.mem.policy registry:
    # 'none', 'full', 'dots_saveable', 'offload_dots', ...): exact
    # numerics, the policy picks what the backward saves — the
    # long-context batch-cap knob (same as BertConfig.remat).  Legacy
    # booleans still work (True -> 'full'), deprecation-warned.
    remat: object = "none"
    dtype: object = jnp.float32

    def __post_init__(self):
        from hetu_tpu.mem.policy import normalize_remat_field
        normalize_remat_field(self)


def gpt2_small(**kw):
    return GPTConfig(**kw)


def gpt2_medium(**kw):
    return GPTConfig(hidden_size=1024, num_layers=24, num_heads=16, **kw)


def gpt2_large(**kw):
    return GPTConfig(hidden_size=1280, num_layers=36, num_heads=20, **kw)


class GPT(Module):
    def __init__(self, cfg: GPTConfig, attn_fn=None):
        init = normal(stddev=cfg.initializer_range)
        self.wte = Embedding(cfg.vocab_size, cfg.hidden_size, initializer=init,
                             dtype=cfg.dtype)
        self.wpe = Embedding(cfg.max_seq_len, cfg.hidden_size, initializer=init,
                             dtype=cfg.dtype, axes=(None, "embed"))
        self.blocks = [
            TransformerBlock(cfg.hidden_size, cfg.num_heads, 4, causal=True,
                             dropout_rate=cfg.dropout_rate, attn_fn=attn_fn,
                             dtype=cfg.dtype)
            for _ in range(cfg.num_layers)
        ]
        self.ln_f = LayerNorm(cfg.hidden_size)
        self.lm_head = (
            None if cfg.tie_embeddings
            else init(next_key(), (cfg.hidden_size, cfg.vocab_size), cfg.dtype)
        )
        self.lm_head_axes = ("embed", "vocab")
        self.config = cfg

    def _head(self):
        """(hidden, vocab) projection — tied to the token embedding unless
        an untied lm_head exists."""
        weight, vocab_axis = self.head()
        return weight if vocab_axis else weight.T

    # -- what serve.ServingEngine asks of a model it serves ------------------

    def head(self):
        """The projection as it is stored, and its vocabulary axis: the
        tied table is ``(vocab, hidden)``, and no transposed copy of it is
        made for a kernel that must find its operand in memory."""
        if self.lm_head is None:
            return self.wte.weight, 0
        return self.lm_head, 1

    def cache_spec(self):
        """Keys and values, ``(heads, head_dim)`` each a token a layer."""
        cfg = self.config
        return CacheSpec.kv(cfg.num_layers, cfg.num_heads,
                            cfg.hidden_size // cfg.num_heads, cfg.dtype)

    def prefill(self, cache, page_idx, cache_index, tokens, seq_lengths):
        """The new tokens of each row through the incremental path over the
        gathered views of its pages, the updated views scattered back:
        ``(last logits, (k, v), {})``."""
        k, v = cache
        k_view, v_view = gather_views(k, v, page_idx)
        kv = [(k_view[i], v_view[i]) for i in range(len(self.blocks))]
        logits, new_kv = self(tokens, kv_cache=kv, cache_index=cache_index,
                              seq_lengths=seq_lengths)
        k_upd = jnp.stack([kv_l[0] for kv_l in new_kv])
        v_upd = jnp.stack([kv_l[1] for kv_l in new_kv])
        return logits, scatter_views(k, v, page_idx, k_upd, v_upd), {}

    def decode(self, cache, page_tables, lengths, tokens):
        """One token a row over the pages read in place: ``(hidden states
        of the new tokens, (k, v), {})``."""
        x, cache = self.hidden_states(tokens, kv_cache=tuple(cache),
                                      cache_index=lengths,
                                      paged_tables=page_tables)
        return x[:, -1], cache, {}

    def __call__(self, input_ids, *, key=None, training: bool = False,
                 compute_dtype=None, kv_cache=None, cache_index=None,
                 seq_lengths=None, paged_tables=None):
        """Logits.  Training/eval (``kv_cache=None``): full (batch, seq,
        vocab) logits, as before.

        Incremental decode (``kv_cache`` = per-block list of ``(k_cache,
        v_cache)`` pairs, ``cache_index`` = per-sequence history lengths):
        ``input_ids`` (batch, s) are s NEW tokens appended at each row's
        offset — s = the padded prompt bucket on prefill, 1 per decode
        step after.  Returns ``(last_logits, new_kv_cache)`` where
        ``last_logits`` (batch, vocab) is the next-token distribution at
        each row's LAST VALID new position (``seq_lengths``, default s —
        pass true prompt lengths when the prefill batch is right-padded
        to a bucket), so the (s, vocab) logits matrix is never
        materialized during serving.

        Paged decode (``paged_tables`` set, s == 1): ``kv_cache`` is ONE
        ``(k_pool, v_pool)`` pair of stacked ``(layers, pages, page_size,
        H, D)`` pool arrays and attention runs the in-place Pallas
        paged-decode kernel — no contiguous K/V view is ever built."""
        if kv_cache is None:
            x = self.hidden_states(input_ids, key=key, training=training,
                                   compute_dtype=compute_dtype)
            return x @ self._head().astype(x.dtype)
        x, new_kv = self.hidden_states(
            input_ids, training=False, compute_dtype=compute_dtype,
            kv_cache=kv_cache, cache_index=cache_index,
            paged_tables=paged_tables)
        if seq_lengths is None:
            last = x[:, -1]
        else:
            last = jnp.take_along_axis(
                x, (seq_lengths - 1)[:, None, None], axis=1)[:, 0]
        return last @ self._head().astype(last.dtype), new_kv

    def hidden_states(self, input_ids, *, key=None, training: bool = False,
                      compute_dtype=None, kv_cache=None, cache_index=None,
                      paged_tables=None):
        """Final-layer-norm hidden states (no LM-head projection).  With
        ``kv_cache``/``cache_index``, runs the incremental-decode path and
        returns ``(hidden, new_kv_cache)``; positions are each row's
        ``cache_index + arange(s)`` so ragged batches place the new
        tokens' position embeddings correctly.  With ``paged_tables``,
        ``kv_cache`` is the stacked pool pair and each block attends in
        place at its own layer index (see ``__call__``)."""
        s = input_ids.shape[-1]
        if kv_cache is not None and paged_tables is not None:
            from hetu_tpu.layers.attention import PagedDecode
            positions = cache_index[:, None] + jnp.arange(s)[None, :]
            x = self.wte(input_ids) + self.wpe(positions)
            if compute_dtype is not None:
                x = x.astype(compute_dtype)
            k, v = kv_cache
            for li, blk in enumerate(self.blocks):
                x, (k, v) = blk(x, kv_cache=(k, v), cache_index=cache_index,
                                paged=PagedDecode(paged_tables, layer=li))
            return self.ln_f(x), (k, v)
        if kv_cache is not None:
            positions = cache_index[:, None] + jnp.arange(s)[None, :]
            x = self.wte(input_ids) + self.wpe(positions)
            if compute_dtype is not None:
                x = x.astype(compute_dtype)
            new_kv = []
            for blk, kv in zip(self.blocks, kv_cache):
                x, kv = blk(x, kv_cache=kv, cache_index=cache_index)
                new_kv.append(kv)
            return self.ln_f(x), new_kv
        x = self.wte(input_ids) + self.wpe(jnp.arange(s))
        if compute_dtype is not None:
            x = x.astype(compute_dtype)
        keys = (
            jax.random.split(key, len(self.blocks)) if key is not None
            else [None] * len(self.blocks)
        )
        step = maybe_remat(
            lambda b, xx, kk: b(xx, key=kk, training=training),
            self.config.remat)
        for blk, k in zip(self.blocks, keys):
            x = step(blk, x, k)
        return self.ln_f(x)

    def loss(self, input_ids, *, key=None, training: bool = True,
             compute_dtype=None):
        """Next-token cross entropy.  With ``streamed_head_chunk`` set, the
        head never materializes the (tokens, vocab) logits."""
        chunk = self.config.streamed_head_chunk
        if chunk > 0:
            x = self.hidden_states(input_ids, key=key, training=training,
                                   compute_dtype=compute_dtype)
            b, sm1 = input_ids.shape[0], input_ids.shape[1] - 1
            nll = lm_head_cross_entropy(
                x[:, :-1].reshape(b * sm1, -1), self._head().astype(x.dtype),
                input_ids[:, 1:].reshape(-1), chunk=chunk)
            return nll.mean()
        logits = self(input_ids, key=key, training=training,
                      compute_dtype=compute_dtype)
        nll = softmax_cross_entropy_sparse(logits[:, :-1], input_ids[:, 1:])
        return nll.mean()
