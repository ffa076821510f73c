"""Multi-head attention.

Reference: python/hetu/layers/attention.py:5 (an OpLayer composing matmul/
softmax ops; materialized QK^T).  TPU-native design: einsum formulation with
head axes annotated for tensor parallelism ('heads' logical axis → 'tp' mesh
axis under the Megatron preset), fp32 softmax statistics, and a pluggable
attention core so the Pallas flash-attention kernel (ops/pallas/flash.py) or
ring attention (parallel/ring_attention.py) can replace the reference
materialized path.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from hetu_tpu.core.module import Module
from hetu_tpu.core.rng import next_key
from hetu_tpu.init import normal, xavier_uniform, zeros
from hetu_tpu.layers.norm import RMSNorm
from hetu_tpu.ops import dropout as dropout_op

__all__ = ["MultiHeadAttention", "GroupedQueryAttention", "PagedDecode",
           "dot_product_attention", "dot_product_attention_bhsd",
           "decode_attention", "ragged_cache_update", "paged_write_slots",
           "rotate_halves"]


class PagedDecode(NamedTuple):
    """Routing record for the paged decode path: with this passed,
    ``decode_attention``'s ``k_cache``/``v_cache`` are the PAGED pools
    (``(pages, page_size, H, D)``, or the stacked ``(layers, ...)`` form
    with ``layer`` set) and attention runs the Pallas paged-decode kernel
    (ops/pallas/paged_decode.py) — K/V pages are read in place, no
    contiguous per-sequence view is ever materialized."""

    tables: object                   # (batch, pages_per_seq) int32
    layer: Optional[int] = None      # static layer into a stacked pool
    interpret: Optional[bool] = None


def _dpa_core(q, k, v, mask, scale, causal, qk_spec: str, pv_spec: str):
    """One materialized-attention body for both layouts (the einsum specs
    carry the layout): fp32 softmax statistics, -1e30 mask fill."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d**0.5)
    logits = jnp.einsum(qk_spec, q, k).astype(jnp.float32) * scale
    if causal:
        qlen, klen = logits.shape[-2], logits.shape[-1]
        cmask = jnp.tril(jnp.ones((qlen, klen), bool), k=klen - qlen)
        logits = jnp.where(cmask, logits, -1e30)
    if mask is not None:
        logits = jnp.where(mask.astype(bool), logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum(pv_spec, probs, v)


def dot_product_attention(q, k, v, mask=None, *, scale: float | None = None,
                          causal: bool = False):
    """Reference attention core: softmax(QK^T/sqrt(d))V, fp32 statistics.

    q,k,v: (batch, seq, heads, head_dim).  mask: broadcastable to
    (batch, heads, q_seq, kv_seq), True/1 = attend.
    """
    return _dpa_core(q, k, v, mask, scale, causal,
                     "bqhd,bkhd->bhqk", "bhqk,bkhd->bqhd")


def dot_product_attention_bhsd(q, k, v, mask=None, *,
                               scale: float | None = None,
                               causal: bool = False):
    """The XLA materialized core in native (batch, heads, seq, head_dim)
    layout, marked ``bhsd`` so MultiHeadAttention projects q/k/v straight
    into it (einsum path, no split/transpose copies).  Not just for the
    Pallas kernel: at BERT-large seq 128 batch 96 on one v5e this core
    measured 193.7 ms/step vs 201.1 for the (B,S,H,D) path — the ~9 ms of
    qkv split/relayout copies disappear here too (MFU 0.634 -> 0.658)."""
    return _dpa_core(q, k, v, mask, scale, causal,
                     "bhqd,bhkd->bhqk", "bhqk,bhkd->bhqd")


dot_product_attention_bhsd.bhsd = True


def ragged_cache_update(cache, new, index):
    """Write ``new`` (batch, s, heads, head_dim) into ``cache`` (batch,
    max_len, heads, head_dim) at per-row offsets ``index`` (batch,) —
    the ragged KV-cache append of a continuous-batching decode step,
    where every sequence in the batch sits at a different length.
    Functional (returns the updated cache); offsets must satisfy
    ``index + s <= max_len`` (dynamic_update_slice clamps, which would
    silently shift the write)."""
    return jax.vmap(
        lambda c, n, i: jax.lax.dynamic_update_slice(
            c, n.astype(c.dtype), (i, 0, 0)))(cache, new, index)


def paged_write_slots(tables, cache_index, page_size: int):
    """Physical (page, slot) each batch row's new K/V lands at: row
    ``b`` writes into ``tables[b, cache_index[b] // page_size]`` at slot
    ``cache_index[b] % page_size``.

    This is the speculative-decode seam: several rows may share ONE page
    table at consecutive ``cache_index`` values (a verify chain), and
    because these writes are element-level scatters into the pool —
    distinct (page, slot) per chain row — they compose within a single
    step, with each row's attention then reading its predecessors'
    fresh K/V (writes precede the kernel).  Rollback is the host's move:
    a rejected chain suffix simply never advances ``PageTable.length``,
    leaving its K/V as dead bytes beyond every future step's validity
    mask until overwritten — the same contract bucket-pad garbage
    already relies on."""
    page_of = jnp.take_along_axis(
        tables, (cache_index // page_size)[:, None], axis=1)[:, 0]
    return page_of, cache_index % page_size


def decode_attention(q, k_cache, v_cache, cache_index, *,
                     scale: float | None = None, mask=None,
                     paged: PagedDecode | None = None):
    """Causal attention of ``s`` new query positions against a padded KV
    cache holding each sequence's full history at a per-row offset.

    q: (batch, s, heads, head_dim) — queries for the s NEW tokens, whose
    global positions are ``cache_index[b] + i`` (i in [0, s)).
    k_cache/v_cache: (batch, max_len, heads, head_dim) with rows
    [0, cache_index[b] + s) valid (the new tokens already appended via
    :func:`ragged_cache_update`); everything at or beyond is masked out,
    so padded garbage never contributes.  This is the incremental-decode
    core: with ``cache_index = 0`` and ``s = seq_len`` it is exactly
    ``dot_product_attention(..., causal=True)`` restricted to the valid
    prefix — the prefill-vs-incremental parity guarantee the serving
    tests assert.

    With ``paged`` (a :class:`PagedDecode`), the caches are instead the
    PAGED pools and ``s`` must be 1: the Pallas paged-decode kernel reads
    each row's K/V pages in place via ``paged.tables``, the masking
    contract unchanged (rows ``[0, cache_index + 1)`` valid)."""
    if paged is not None:
        from hetu_tpu.ops.pallas.paged_decode import paged_decode_attention
        if q.shape[1] != 1:
            raise ValueError(f"paged decode attends one new token per "
                             f"sequence, got s={q.shape[1]}")
        if mask is not None:
            raise ValueError("paged decode does not take an extra mask; "
                             "validity comes from cache_index")
        out = paged_decode_attention(
            q[:, 0], k_cache, v_cache, paged.tables,
            cache_index + 1, layer=paged.layer, scale=scale,
            interpret=paged.interpret)
        return out[:, None]
    s = q.shape[1]
    max_len = k_cache.shape[1]
    jpos = jnp.arange(max_len)[None, None, :]                  # (1, 1, L)
    ipos = cache_index[:, None, None] + jnp.arange(s)[None, :, None]
    valid = (jpos <= ipos)[:, None, :, :]                      # (b, 1, s, L)
    if mask is not None:
        valid = valid & mask.astype(bool)
    return dot_product_attention(q, k_cache, v_cache, valid, scale=scale,
                                 causal=False)


class MultiHeadAttention(Module):
    """MHA with fused qkv projection (reference layers/attention.py:5)."""

    def __init__(self, dim: int, num_heads: int, *, bias: bool = True,
                 causal: bool = False, dropout_rate: float = 0.0,
                 attn_fn: Optional[Callable] = None, dtype=jnp.float32):
        assert dim % num_heads == 0
        init = xavier_uniform()
        self.wqkv = init(next_key(), (dim, 3 * dim), dtype)
        self.wqkv_axes = ("embed", "qkv_three_heads")
        self.bqkv = zeros(None, (3 * dim,), dtype) if bias else None
        self.bqkv_axes = ("qkv_three_heads",)
        self.wo = init(next_key(), (dim, dim), dtype)
        self.wo_axes = ("heads_merged", "embed")
        self.bo = zeros(None, (dim,), dtype) if bias else None
        self.num_heads = num_heads
        self.head_dim = dim // num_heads
        self.causal = causal
        self.dropout_rate = dropout_rate
        self.attn_fn = attn_fn  # static; None -> dot_product_attention

    def __call__(self, x, mask=None, *, key=None, training: bool = False,
                 kv_cache=None, cache_index=None, paged=None):
        if kv_cache is not None:
            if paged is not None:
                if mask is not None:
                    raise ValueError(
                        "paged decode does not take an extra mask; "
                        "validity comes from cache_index")
                return self._call_paged(x, kv_cache, cache_index, paged)
            return self._call_cached(x, mask, kv_cache, cache_index)
        if getattr(self.attn_fn, "bhsd", False):
            return self._call_bhsd(x, mask, key=key, training=training)
        b, s, d = x.shape
        qkv = x @ self.wqkv.astype(x.dtype)
        if self.bqkv is not None:
            qkv = qkv + self.bqkv.astype(x.dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, s, self.num_heads, self.head_dim)
        k = k.reshape(b, s, self.num_heads, self.head_dim)
        v = v.reshape(b, s, self.num_heads, self.head_dim)
        attn = self.attn_fn or dot_product_attention
        out = attn(q, k, v, mask, causal=self.causal)
        out = out.reshape(b, s, d)
        if training and self.dropout_rate > 0.0 and key is not None:
            out = dropout_op(out, self.dropout_rate, key, training=True)
        y = out @ self.wo.astype(x.dtype)
        if self.bo is not None:
            y = y + self.bo.astype(x.dtype)
        return y

    def _call_cached(self, x, mask, kv_cache, cache_index):
        """Incremental-decode path: project the s new tokens, append their
        K/V into the per-sequence cache at ragged offsets, and attend each
        query over the full valid prefix.  Returns ``(y, (k_cache,
        v_cache))`` with the caches updated — the serving engine threads
        them back into its page pool.  Inference-only (no dropout); the
        (B, S, H, D) reference core is used regardless of ``attn_fn``
        because flash/ring tilings assume untruncated causal layouts."""
        b, s, d = x.shape
        qkv = x @ self.wqkv.astype(x.dtype)
        if self.bqkv is not None:
            qkv = qkv + self.bqkv.astype(x.dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, s, self.num_heads, self.head_dim)
        k = k.reshape(b, s, self.num_heads, self.head_dim)
        v = v.reshape(b, s, self.num_heads, self.head_dim)
        k_cache, v_cache = kv_cache
        k_cache = ragged_cache_update(k_cache, k, cache_index)
        v_cache = ragged_cache_update(v_cache, v, cache_index)
        out = decode_attention(q, k_cache, v_cache, cache_index, mask=mask)
        y = out.reshape(b, s, d) @ self.wo.astype(x.dtype)
        if self.bo is not None:
            y = y + self.bo.astype(x.dtype)
        return y, (k_cache, v_cache)

    def _call_paged(self, x, kv_cache, cache_index, paged: PagedDecode):
        """Paged-decode step: project the ONE new token per row, scatter
        its K/V into the pool at each row's (physical page, slot), and
        attend in place over the page tables via the Pallas paged kernel
        — no contiguous per-sequence K/V view is ever materialized.
        ``kv_cache`` = (k_pool, v_pool), per layer or stacked with
        ``paged.layer``; ``cache_index`` = per-row history lengths (the
        fed token's K/V lands at that index).  Returns ``(y, (k_pool,
        v_pool))`` with the pools updated — one small scatter each."""
        b, s, d = x.shape
        if s != 1:
            raise ValueError(f"paged decode takes one new token per row, "
                             f"got s={s}")
        qkv = x @ self.wqkv.astype(x.dtype)
        if self.bqkv is not None:
            qkv = qkv + self.bqkv.astype(x.dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, s, self.num_heads, self.head_dim)
        k = k.reshape(b, self.num_heads, self.head_dim)
        v = v.reshape(b, self.num_heads, self.head_dim)
        k_pool, v_pool = kv_cache
        page_of, slot = paged_write_slots(paged.tables, cache_index,
                                          k_pool.shape[-3])
        if k_pool.ndim == 5:
            k_pool = k_pool.at[paged.layer, page_of, slot].set(
                k.astype(k_pool.dtype))
            v_pool = v_pool.at[paged.layer, page_of, slot].set(
                v.astype(v_pool.dtype))
        else:
            k_pool = k_pool.at[page_of, slot].set(k.astype(k_pool.dtype))
            v_pool = v_pool.at[page_of, slot].set(v.astype(v_pool.dtype))
        out = decode_attention(q, k_pool, v_pool, cache_index, paged=paged)
        y = out.reshape(b, s, d) @ self.wo.astype(x.dtype)
        if self.bo is not None:
            y = y + self.bo.astype(x.dtype)
        return y, (k_pool, v_pool)

    def _call_bhsd(self, x, mask=None, *, key=None, training: bool = False):
        """Native-kernel-layout path: q/k/v are PROJECTED into (B, H, S, D)
        — ``einsum('bsd,dkhe->kbhse')`` — and the output projection
        contracts (h, e) straight out of (B, H, S, D), so no transpose op
        (forward or vjp) ever sits between the projection matmuls and a
        ``bhsd``-marked attention core (the Pallas flash kernel's tiling).
        The (B, S, H, D) path materializes an XLA relayout copy around
        every kernel operand and gradient instead — ~9% of the BERT-large
        seq-512 step (ROADMAP r03 4b).  Same math, same weights, same
        parameter layout; only the activation layout differs."""
        h, e = self.num_heads, self.head_dim
        d = x.shape[-1]
        # THREE separate projection einsums, not one fused "bsd,dkhe->
        # kbhse": measured on one v5e at BERT-large seq 512 (examples/
        # profile_qkv_variants.py) the per-operand dots let XLA absorb the
        # (b,s,h,e)->(b,h,s,e) permutation into each dot's output layout,
        # while the fused 5-d variant pays ~9 ms/step of slice_bitcast
        # fusions for qkv[k] and the matmul+transpose variant pays ~22 ms
        # of relayout copies.  A=241.3 / B=237.0 / C(this)=225.1 /
        # D=247.7 ms per step.
        w4 = self.wqkv.astype(x.dtype).reshape(d, 3, h, e)
        b4 = (None if self.bqkv is None
              else self.bqkv.astype(x.dtype).reshape(3, 1, h, 1, e))
        parts = []
        for i in range(3):
            p = jnp.einsum("bsd,dhe->bhse", x, w4[:, i])
            if b4 is not None:
                p = p + b4[i]
            parts.append(p)
        q, k, v = parts
        out = self.attn_fn(q, k, v, mask, causal=self.causal)  # (b,h,s,e)
        if training and self.dropout_rate > 0.0 and key is not None:
            # elementwise iid mask: applying it in (b,h,s,e) is the same
            # distribution as the (b,s,d) path (different RNG alignment)
            out = dropout_op(out, self.dropout_rate, key, training=True)
        y = jnp.einsum("bhse,hed->bsd",
                       out, self.wo.astype(x.dtype).reshape(h, e, d))
        if self.bo is not None:
            y = y + self.bo.astype(x.dtype)
        return y


def rotate_halves(x, positions, theta: float):
    """Rotary position encoding on plain heads: ``x [..., dim]`` with its
    pairs ``(i, i + dim / 2)`` rotated by ``positions * theta^(-2i/dim)``
    over all of ``dim``; ``positions`` has ``x``'s leading shape up to
    broadcasting.  Computed in float32."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions[..., None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = (x[..., :half].astype(jnp.float32),
            x[..., half:].astype(jnp.float32))
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


class GroupedQueryAttention(Module):
    """Causal attention of ``num_heads`` query heads over ``num_kv_heads``
    key and value heads (query head ``h`` reads KV head ``h // group``),
    with what today's decoders put around it: an RMSNorm a head on q and on
    k (one set of ``head_dim`` gains for q, one for k), an output gate (``o
    * sigmoid(W_g x)`` before the output projection), and by the layer's
    kind rotary on plain q and k (``rope_theta``; ``None`` applies no
    position encoding at all) and a window (``window``: key ``s`` is seen
    from query ``t`` iff ``s <= t`` and ``t - s < window``; ``None``: every
    earlier key).  No projection has a bias; ``head_dim`` is free of
    ``dim``.

    ``attn_fn(q, k, v, causal=True, window=...)`` takes and returns the
    kernel layout [batch, heads, seq, head_dim] with K and V at their own
    head count (``ops.pallas.flash_attention_bhsd``); without one the
    scores are materialised over K and V repeated.

    Served, a cached token holds k after its norm and its rotation and v,
    ``2 x num_kv_heads x head_dim`` values, in head-major pages ``[layers,
    pages, kv_heads, page, head_dim]`` (:meth:`cache_spec`).
    :meth:`prefill` runs the whole prompt through ``attn_fn`` and writes
    the pages a later token can still read; :meth:`decode` writes one new
    token a row and attends over the pages in place
    (``ops.pallas.paged_decode_attention``)."""

    def __init__(self, dim: int, num_heads: int, num_kv_heads: int,
                 head_dim: int, *, window: int | None = None,
                 rope_theta: float | None = None, eps: float = 1e-5,
                 init_std: float = 0.02, attn_fn: Optional[Callable] = None,
                 interpret=None, dtype=jnp.float32):
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads over {num_kv_heads} "
                             f"KV heads")
        init = normal(stddev=init_std)
        h, kh, e = num_heads, num_kv_heads, head_dim
        self.wq = init(next_key(), (dim, h * e), dtype)
        self.wq_axes = ("embed", "heads")
        self.wk = init(next_key(), (dim, kh * e), dtype)
        self.wk_axes = ("embed", "kv_heads")
        self.wv = init(next_key(), (dim, kh * e), dtype)
        self.wv_axes = ("embed", "kv_heads")
        self.wg = init(next_key(), (dim, h * e), dtype)
        self.wg_axes = ("embed", "heads")
        self.wo = init(next_key(), (h * e, dim), dtype)
        self.wo_axes = ("heads", "embed")
        self.q_norm = RMSNorm(e, eps=eps)
        self.k_norm = RMSNorm(e, eps=eps)
        self.num_heads, self.num_kv_heads, self.head_dim = h, kh, e
        self.window, self.rope_theta = window, rope_theta
        self.attn_fn, self.interpret = attn_fn, interpret

    def _heads(self, x, positions, spec: str):
        """q, k, v and the gate's pre-activation of ``x``, heads
        split off by the einsum ``spec`` (its output is what the form
        wants: ``bhse`` for the kernels, ``bhe`` for one token a row), q and
        k normed and rotated; ``positions`` broadcasts against q's leading
        dimensions."""
        h, kh, e = self.num_heads, self.num_kv_heads, self.head_dim
        d = x.shape[-1]
        w = lambda a, n: a.astype(x.dtype).reshape(d, n, e)
        q = jnp.einsum(spec, x, w(self.wq, h))
        k = jnp.einsum(spec, x, w(self.wk, kh))
        v = jnp.einsum(spec, x, w(self.wv, kh))
        g = jnp.einsum(spec, x, w(self.wg, h))
        q, k = self.q_norm(q), self.k_norm(k)
        if self.rope_theta is not None:
            q = rotate_halves(q, positions, self.rope_theta)
            k = rotate_halves(k, positions, self.rope_theta)
        return q, k, v, g

    def _out(self, o, g, spec: str):
        """The gate and the output projection of ``o`` (heads as the form
        has them, ``spec`` contracting them away)."""
        o = o * jax.nn.sigmoid(g.astype(jnp.float32)).astype(o.dtype)
        return jnp.einsum(spec, o, self.wo.astype(o.dtype).reshape(
            self.num_heads, self.head_dim, -1))

    def _attend(self, q, k, v):
        """Causal (windowed) attention in the kernel layout."""
        if self.attn_fn is not None:
            return self.attn_fn(q, k, v, causal=True, window=self.window)
        group, s = self.num_heads // self.num_kv_heads, q.shape[2]
        mask = None
        if self.window is not None:
            t = jnp.arange(s)
            mask = (t[:, None] - t[None, :] < self.window)[None, None]
        return dot_product_attention_bhsd(
            q, jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1),
            mask, causal=True)

    def __call__(self, x, positions=None):
        """The whole sequence at once, no cache: x [batch, seq, dim]."""
        b, s = x.shape[:2]
        if positions is None:
            positions = jnp.arange(s)
        q, k, v, g = self._heads(x, positions, "bsd,dhe->bhse")
        return self._out(self._attend(q, k, v), g, "bhse,hed->bsd")

    # -- serving: a paged cache of keys and values --------------------------

    def cache_spec(self, num_layers: int, dtype, name: str = "all"):
        """The cache of ``num_layers`` layers like this one."""
        from hetu_tpu.layers.cache import CacheSpec
        if self.num_kv_heads == self.num_heads:
            raise ValueError(
                "the cached forms write head-major pages, which are grouped "
                "heads': with equal head counts serve MultiHeadAttention")
        return CacheSpec.kv(num_layers, self.num_kv_heads, self.head_dim,
                            dtype, name=name, window=self.window,
                            query_heads=self.num_heads)

    def prefill(self, x, cache, page_idx, seq_lengths, *, layer: int):
        """A prompt from its first token on, ``x [batch, bucket, dim]``
        whose rows hold ``seq_lengths`` tokens: attention over the bucket,
        and k and v written into the row's pages ``page_idx [batch, pages a
        sequence]``, ``cache = (k_pool, v_pool)``.  Without a window every
        page of the bucket is written (those past the row's allocation land
        in the scratch page its table is padded with).  With one the table
        is a ring (``layers.cache``): only the last ``ring`` pages up to the
        row's last token are written, each to its slot, which is all that
        a later token of this layer can read.  Returns ``(out, cache)``."""
        from hetu_tpu.layers.cache import ring_order
        b, s = x.shape[:2]
        q, k, v, g = self._heads(x, jnp.arange(s), "bsd,dhe->bhse")
        k_pool, v_pool = cache
        page, ring = k_pool.shape[-2], page_idx.shape[1]
        n = -(-s // page)

        def pages_of(a):            # [batch, n, kv_heads, page, head_dim]
            a = jnp.pad(a.astype(k_pool.dtype),
                        ((0, 0), (0, 0), (0, n * page - s), (0, 0)))
            return a.reshape(b, self.num_kv_heads, n, page,
                             self.head_dim).swapaxes(1, 2)

        kp, vp = pages_of(k), pages_of(v)
        if n <= ring:
            at = page_idx[:, :n]
        else:       # the ring's slots, oldest first, and the pages for them
            at, first = ring_order(page_idx, seq_lengths, page)
            logical = first[:, None] // page + jnp.arange(ring,
                                                          dtype=jnp.int32)
            pick = jnp.minimum(logical, n - 1)[:, :, None, None, None]
            kp = jnp.take_along_axis(kp, pick, axis=1)
            vp = jnp.take_along_axis(vp, pick, axis=1)
        k_pool = k_pool.at[layer, at].set(kp)
        v_pool = v_pool.at[layer, at].set(vp)
        out = self._out(self._attend(q, k, v), g, "bhse,hed->bsd")
        return out, (k_pool, v_pool)

    def decode(self, x, cache, tables, lengths, *, layer: int,
               first_position=None):
        """One new token a row, ``x [batch, dim]`` at position
        ``lengths[b]``: its k and v written at that position of the row's
        pages, then attention over the ``lengths + 1`` cached tokens (the
        window's last, with one), read in place.  ``tables [batch,
        entries]`` are the row's pages in the order of the positions they
        hold, from ``first_position [batch]`` on (nought if not given; a
        ring is put in that order by ``layers.cache.ring_order``).
        Returns ``(out [batch, dim], cache)``."""
        from hetu_tpu.ops.pallas.paged_decode import paged_decode_attention
        q, k, v, g = self._heads(x, lengths[:, None], "bd,dhe->bhe")
        k_pool, v_pool = cache
        held = lengths if first_position is None else lengths - first_position
        page = k_pool.shape[-2]
        page_of, slot = paged_write_slots(tables, held, page)
        # the new row goes in by whole pages, read, changed and written
        # back (as the latent cache's does, layers/mla.py): scattering
        # kv_heads rows of head_dim down a page makes the v5e compiler
        # re-lay the whole pool token-major for the scatter and copy it
        # back for the kernel (22 pool-sized copies in the compiled step);
        # a page a row is 128 KB
        here = (jnp.arange(page)[None, None, :, None]
                == slot[:, None, None, None])

        def write(pool, new):
            return pool.at[layer, page_of].set(jnp.where(
                here, new.astype(pool.dtype)[:, :, None, :],
                pool[layer, page_of]))

        k_pool, v_pool = write(k_pool, k), write(v_pool, v)
        o = paged_decode_attention(
            q, k_pool, v_pool, tables, lengths + 1, layer=layer,
            window=self.window, first_position=first_position,
            kv_heads=self.num_kv_heads, interpret=self.interpret)
        return self._out(o, g, "bhe,hed->bd"), (k_pool, v_pool)
