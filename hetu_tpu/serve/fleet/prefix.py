"""Copy-on-write prefix sharing over the paged KV pool.

Production prompt traffic is template-heavy: the same system prompt /
few-shot preamble arrives thousands of times with different suffixes,
and a single-replica engine recomputes and re-stores the identical
prefill every time.  The page tables are exactly the right substrate to
stop that: a prefix of ``n`` full KV pages is suffix-independent state
(K/V at positions < n depend only on the tokens at positions < n under
causal attention), so two prompts that agree on their leading blocks can
ALIAS the same physical pages.

:class:`PrefixTrie` indexes published prefixes by token-block hash, one
node per full ``page_size`` block.  Hashes only route: every match and
every insert re-checks TOKEN EQUALITY against the stored block, so a
hash collision degrades to a miss — two prompts differing anywhere
inside a block can never alias (property-tested).

:class:`PrefixSharer` is the engine-facing policy:

- :meth:`~PrefixSharer.lookup` returns the longest trie match as a list
  of shared pages (capped one token short of the prompt, so prefill
  always has at least one suffix token to compute the first sample
  from), counting ``hetu_serve_prefix_{hits,misses}_total``;
- :meth:`~PrefixSharer.publish` inserts a prefilled prompt's full blocks
  into the trie, RETAINING each newly published page
  (:meth:`~hetu_tpu.serve.kv_cache.KVCachePool.retain`) so the prefix
  outlives the sequence that computed it — that is what makes the cache
  useful across requests, not just across concurrent ones;
- :meth:`~PrefixSharer.reclaim` evicts trie-only pages (refcount 1,
  held by no table) leaves-first in least-recently-matched order when
  the allocator runs short — cached prefixes are a performance loan the
  admission gate can call in.

Sharing never changes what a write sees: prefill computes only the
suffix at ``cache_index = shared_tokens`` (page-aligned by
construction, so the suffix always starts in a private page), and the
engine runs :meth:`KVCachePool.copy_on_write` before every decode write
as the guard rail for any path that would touch a shared page.
"""

from __future__ import annotations

import zlib

import numpy as np

from hetu_tpu.obs import registry as _obs
from hetu_tpu.serve.kv_cache import KVCachePool, PageTable

__all__ = ["PrefixTrie", "PrefixSharer", "block_key"]

_prefix_metrics = None


def _prefix_m() -> dict:
    global _prefix_metrics
    if _prefix_metrics is None:
        reg = _obs.get_registry()
        _prefix_metrics = {
            "hits": reg.counter(
                "hetu_serve_prefix_hits_total",
                "prompt-prefix KV pages served by aliasing a shared page "
                "instead of recomputing the prefill block"),
            "misses": reg.counter(
                "hetu_serve_prefix_misses_total",
                "shareable full prompt blocks that had no trie match and "
                "were computed (and published) fresh"),
            "shared": reg.gauge(
                "hetu_serve_pages_shared",
                "KV pages currently aliased by more than one reference "
                "(tables and/or the prefix trie)"),
        }
    return _prefix_metrics


def block_key(block) -> int:
    """Deterministic hash of one token block (crc32 of the little-endian
    u32 token ids — stable across processes, unlike ``hash()``).  Keys
    only ROUTE; aliasing always re-checks token equality."""
    return zlib.crc32(np.asarray(block, "<u4").tobytes())


class _Node:
    __slots__ = ("tokens", "page", "children", "last_used")

    def __init__(self, tokens: tuple, page: int, last_used: int):
        self.tokens = tokens
        self.page = page
        self.children: dict = {}
        self.last_used = last_used


class PrefixTrie:
    """Token-block-hash trie: one node per published full block, each
    holding the block's tokens (the collision guard) and the physical
    page its K/V lives in."""

    def __init__(self, page_size: int):
        self.page_size = page_size
        self.children: dict = {}   # root level: block key -> _Node
        self._clock = 0            # monotonic use counter (LRU, no wall time)
        self.nodes = 0

    def _blocks(self, prompt):
        ps = self.page_size
        for i in range(len(prompt) // ps):
            yield tuple(int(t) for t in prompt[i * ps:(i + 1) * ps])

    def match(self, prompt, max_blocks: int | None = None, *,
              peek: bool = False) -> list:
        """Pages of the longest published prefix of ``prompt`` (full
        blocks only, token-verified per block).  Bumps recency unless
        ``peek`` (the router's affinity probe must not perturb LRU
        eviction order between replays)."""
        pages = []
        level = self.children
        for bi, block in enumerate(self._blocks(prompt)):
            if max_blocks is not None and bi >= max_blocks:
                break
            node = level.get(block_key(block))
            if node is None or node.tokens != block:
                break  # miss — or a hash collision, which must be a miss
            if not peek:
                self._clock += 1
                node.last_used = self._clock
            pages.append(node.page)
            level = node.children
        return pages

    def insert(self, prompt, table: PageTable, pool: KVCachePool,
               max_blocks: int | None = None) -> int:
        """Publish ``prompt``'s full blocks, pointing new nodes at the
        sequence's own pages and RETAINING each (the trie's reference).
        Existing nodes keep their page (first publisher wins — later
        identical prefills computed a duplicate only for themselves); a
        colliding node (same hash, different tokens) stops publication
        at that depth.  Returns the number of newly published blocks."""
        level = self.children
        new = 0
        for bi, block in enumerate(self._blocks(prompt)):
            if max_blocks is not None and bi >= max_blocks:
                break
            key = block_key(block)
            node = level.get(key)
            if node is None:
                page = table.pages[bi]
                pool.retain(page)
                self._clock += 1
                node = _Node(block, page, self._clock)
                level[key] = node
                self.nodes += 1
                new += 1
            elif node.tokens != block:
                break  # hash collision: never alias, never overwrite
            level = node.children
        return new

    def evict_reclaimable(self, pool: KVCachePool, n_pages: int) -> int:
        """Drop trie leaves whose page the trie alone keeps alive
        (refcount 1), least-recently-matched first, until ``n_pages``
        pages returned to the free list or nothing is evictable.
        Deterministic: recency is the use counter, ties broken by page
        index."""
        freed = 0
        while freed < n_pages:
            leaves = []  # (last_used, page, parent_level, key)
            stack = [(self.children, k, n) for k, n in self.children.items()]
            while stack:
                level, key, node = stack.pop()
                if not node.children:
                    if pool.refcount(node.page) == 1:
                        leaves.append((node.last_used, node.page,
                                       level, key))
                else:
                    stack.extend((node.children, k, c)
                                 for k, c in node.children.items())
            if not leaves:
                break
            _, page, level, key = min(leaves)
            del level[key]
            self.nodes -= 1
            pool.release(page)
            freed += 1
        return freed


class PrefixSharer:
    """The engine-facing prefix-sharing policy over one pool + one trie
    (per replica — the router compares tries across replicas for
    affinity placement)."""

    def __init__(self, pool: KVCachePool):
        # a shared prefix's suffix is prefilled against the cached keys
        # and values of the prefix; no model does that over latents yet
        pool.require_kv("prefix sharing")
        self.pool = pool
        self.trie = PrefixTrie(pool.page_size)

    def _max_share_blocks(self, prompt_len: int) -> int:
        # never share the whole prompt: prefill must keep >= 1 suffix
        # token to compute the first sampled token's logits from
        return max(0, (prompt_len - 1) // self.pool.page_size)

    def lookup(self, prompt, max_tokens: int | None = None) -> tuple:
        """``(shared_pages, shared_tokens)`` for a prompt about to be
        allocated; counts block hits and (shareable) misses.
        ``max_tokens`` further caps the share (the engine trims so that
        ``shared + suffix_bucket`` always fits the serving window, and
        drops sharing entirely under a bucket-growth freeze when the
        suffix bucket would be a cold compile)."""
        cap = self._max_share_blocks(len(prompt))
        if max_tokens is not None:
            cap = min(cap, max_tokens // self.pool.page_size)
        pages = self.trie.match(prompt, cap)
        m = _prefix_m()
        if pages:
            m["hits"].inc(len(pages))
        if cap > len(pages):
            m["misses"].inc(cap - len(pages))
        return pages, len(pages) * self.pool.page_size

    def match_tokens(self, prompt) -> int:
        """Affinity probe: how many leading tokens of ``prompt`` this
        replica's trie already holds.  Read-only (no recency bump, no
        hit/miss counting) so routing probes across N replicas leave
        every trie bitwise unchanged."""
        return len(self.trie.match(
            prompt, self._max_share_blocks(len(prompt)), peek=True)) \
            * self.pool.page_size

    def publish(self, prompt, table: PageTable) -> int:
        """Publish a prefilled prompt's fully-written blocks; updates the
        shared-pages gauge.  Returns newly published block count."""
        new = self.trie.insert(prompt, table, self.pool,
                               max_blocks=len(prompt) // self.pool.page_size)
        # one cheap refcount pass — publish is on the per-request prefill
        # path, so no stats() invariant sweep here
        _prefix_m()["shared"].set(self.pool.shared_pages_count())
        return new

    def reclaim(self, n_pages: int) -> int:
        """Evict trie-only pages to unblock an allocation; returns pages
        actually freed."""
        return self.trie.evict_reclaimable(self.pool, n_pages)

    def stats(self) -> dict:
        return {"trie_nodes": self.trie.nodes,
                "page_size": self.pool.page_size}
