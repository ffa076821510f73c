"""What a served model states about its cache, and the contiguous views of a
paged pool that a model's cached forward reads and writes.

A model names its cache in a :class:`CacheSpec` (``cache_spec()``), or, where
its layers are of more than one kind (window and full attention in one
model), in a :class:`GroupedCacheSpec` of one :class:`CacheSpec` a group of
layers; ``serve.kv_cache`` builds its arrays from it (one pool of pages a
group, one page table a group a sequence), so the serving package reads
this module and no model reads the serving package.

Keys and values may have fewer heads than the queries (``CacheSpec.kv`` is
given the KV heads: what a cached token holds).  A spec with a ``window``
says that a layer of the group reads position ``s`` from position ``t`` only
while ``t - s < window``: its sequence holds ``window / page_size + 1`` pages
at most, a ring in which logical page ``p`` lives in slot ``p mod ring``
(:func:`ring_slots`), and what falls out of the window is overwritten.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

__all__ = ["CacheSpec", "GroupedCacheSpec", "ring_slots", "ring_order",
           "gather_views", "scatter_views",
           "gather_view_count", "reset_gather_view_count"]


@dataclasses.dataclass(frozen=True)
class CacheSpec:
    """What a served model states about its cache: ``entries`` names the
    arrays a cached token is held in and the shape of one token's share of
    each, a layer (``("k", (heads, head_dim))`` and ``("v", ...)`` for
    keys and values, ``("latent", (width,))`` for one latent).  The pool
    holds one array ``(num_layers, pages, page_size) + shape`` an entry,
    once; with ``token_minor`` ``(num_layers, pages) + shape +
    (page_size,)``, for a share whose last dimension is no multiple of the
    device's 128 lanes and would be padded (or re-laid by the compiler
    and copied around every kernel) if it were the minor one.

    With ``head_major`` a page of an entry ``(heads, width)`` is ``(heads,
    page_size, width)``: few heads (grouped KV heads) fill a fraction of the
    device's ``(sublanes, 128)`` tile as a second-minor dimension, where
    ``(page_size, width)`` are whole tiles.  No caller chooses it:
    :meth:`kv` lays keys and values of fewer heads than the queries out so
    (the paged kernel reads such pages a quarter to a third faster on the
    v5e: ``ops/pallas/paged_decode.py`` has the readings).

    ``name`` is the group's, where the model states several
    (:class:`GroupedCacheSpec`); ``window`` bounds what a layer of the group
    still reads (module docstring), ``None`` for all of the sequence."""

    num_layers: int
    entries: tuple
    dtype: object = jnp.float32
    token_minor: bool = False
    name: str = "all"
    window: int | None = None
    head_major: bool = False

    @property
    def groups(self) -> tuple:
        return (self,)

    def page_shape(self, shape: tuple, page_size: int) -> tuple:
        """One page of an entry whose token's share is ``shape``."""
        shape = tuple(shape)
        if self.head_major:
            return shape[:1] + (page_size,) + shape[1:]
        return shape + (page_size,) if self.token_minor \
            else (page_size,) + shape

    @classmethod
    def kv(cls, num_layers: int, num_heads: int, head_dim: int,
           dtype=jnp.float32, *, name: str = "all",
           window: int | None = None,
           query_heads: int | None = None) -> "CacheSpec":
        """Keys and values of ``num_heads`` KV heads.  ``query_heads``: the
        queries' heads where they are more (a multiple); the pages are then
        head-major."""
        shape = (int(num_heads), int(head_dim))
        grouped = query_heads is not None and int(query_heads) > shape[0]
        return cls(int(num_layers), (("k", shape), ("v", shape)), dtype,
                   name=name, window=window, head_major=grouped)

    @classmethod
    def latent(cls, num_layers: int, width: int,
               dtype=jnp.float32) -> "CacheSpec":
        return cls(int(num_layers), (("latent", (int(width),)),), dtype,
                   token_minor=True)

    @property
    def holds_kv(self) -> bool:
        return tuple(n for n, _ in self.entries) == ("k", "v")

    @property
    def plain_kv(self) -> bool:
        """Keys and values of whole sequences in token-major pages
        ``(page_size, heads, head_dim)``: what the gather path, page export
        and import, prefix sharing and speculative decoding read."""
        return self.holds_kv and self.window is None and not self.head_major

    @property
    def values_per_token(self) -> int:
        """Values one cached token holds in one layer."""
        return sum(int(np.prod(shape)) for _, shape in self.entries)

    @property
    def bytes_per_token(self) -> int:
        """Bytes one cached token holds in one layer."""
        return self.values_per_token * int(np.dtype(self.dtype).itemsize)

    @property
    def token_bytes(self) -> int:
        """Bytes one cached token holds over the group's layers."""
        return self.num_layers * self.bytes_per_token

    def ring_pages(self, page_size: int) -> int | None:
        """Pages a sequence of a window group holds at most: the window's
        and the one being filled.  ``None`` without a window."""
        if self.window is None:
            return None
        if self.window % page_size:
            raise ValueError(f"window {self.window} of group {self.name!r} "
                             f"is no multiple of page_size {page_size}")
        return self.window // page_size + 1

    def pages_per_seq(self, page_size: int, max_seq_len: int) -> int:
        """Pages a sequence of the group holds at most: every page of
        ``max_seq_len``, or a window's ring if that is fewer."""
        whole = max_seq_len // page_size
        return min(whole, self.ring_pages(page_size) or whole)

    def describe(self) -> dict:
        out = {"entries": {n: list(shape) for n, shape in self.entries},
               "layers": self.num_layers, "dtype": str(np.dtype(self.dtype)),
               "token_minor": self.token_minor,
               "values_per_token_per_layer": self.values_per_token,
               "bytes_per_token_per_layer": self.bytes_per_token}
        if self.window is not None:
            out["window"] = self.window
        if self.head_major:
            out["head_major"] = True
        return out


@dataclasses.dataclass(frozen=True)
class GroupedCacheSpec:
    """The cache of a model whose layers are of several kinds: one
    :class:`CacheSpec` a group of layers, each with its ``name``, its own
    pages and its own table a sequence.  A served model's ``prefill`` and
    ``decode`` get the groups' arrays one after another (``cache``) and one
    page table a group (``page_idx`` / ``page_tables``, a tuple in the
    groups' order)."""

    specs: tuple

    def __post_init__(self):
        names = [g.name for g in self.specs]
        if len(set(names)) != len(names) or not names:
            raise ValueError(f"groups need distinct names, got {names}")

    @property
    def groups(self) -> tuple:
        return self.specs

    @property
    def num_layers(self) -> int:
        return sum(g.num_layers for g in self.specs)

    @property
    def holds_kv(self) -> bool:
        return all(g.holds_kv for g in self.specs)

    plain_kv = False     # several pools, several tables a sequence

    @property
    def token_bytes(self) -> int:
        """Bytes one cached token inside every window holds over all
        layers."""
        return sum(g.token_bytes for g in self.specs)

    def describe(self) -> dict:
        return {"groups": {g.name: g.describe() for g in self.specs},
                "layers": self.num_layers}


def ring_slots(first_page, ring: int):
    """Ring slot of each of the ``ring`` consecutive logical pages from
    ``first_page [rows]`` on: ``[rows, ring]``.  Logical page ``p`` of a
    window group's sequence lives in slot ``p mod ring`` of its table."""
    return (first_page[:, None] + jnp.arange(ring, dtype=jnp.int32)) % ring


def ring_order(tables, lengths, page_size: int):
    """A window group's tables ``[rows, ring]`` (slot order) in logical
    order, and the position the first entry holds: the pages that can hold
    positions ``lengths - window .. lengths - 1`` of each row, oldest
    first.  ``lengths`` counts the row's tokens, the newest included."""
    ring = tables.shape[1]
    last = jnp.maximum(lengths.astype(jnp.int32) - 1, 0) // page_size
    first = jnp.maximum(last - (ring - 1), 0)
    return (jnp.take_along_axis(tables, ring_slots(first, ring), axis=1),
            first * page_size)


# Counting seam for the no-materialization acceptance test: gather_views
# is THE place a contiguous (L, batch, max_len, H, D) view of the pool is
# built, and it runs at trace time (inside jit), so counting its calls
# proves which jitted programs gather.  The paged decode step must trace
# to zero gathers; prefill (bucketed, once per request) still gathers.
_gather_view_calls = 0


def gather_view_count() -> int:
    """How many times :func:`gather_views` has traced a contiguous view."""
    return _gather_view_calls


def reset_gather_view_count() -> None:
    global _gather_view_calls
    _gather_view_calls = 0


def gather_views(k, v, page_idx):
    """Inside-jit helper: materialize the bucket-padded contiguous views
    ``(L, batch, max_len, H, D)`` from the page arrays — one gather each.
    Counted (at trace time) so the paged-decode acceptance test can prove
    the decode program never builds a view."""
    global _gather_view_calls
    _gather_view_calls += 1
    L, _, page, H, D = k.shape
    b, P = page_idx.shape
    kv_shape = (L, b, P * page, H, D)
    return (k[:, page_idx].reshape(kv_shape),
            v[:, page_idx].reshape(kv_shape))


def scatter_views(k, v, page_idx, k_view, v_view):
    """Inside-jit helper: write updated contiguous views back into the
    page arrays.  Every live page belongs to exactly one (sequence, slot),
    so the scatter is conflict-free except for the scratch page, whose
    content is never read unmasked."""
    L, _, page, H, D = k.shape
    b, P = page_idx.shape
    pg_shape = (L, b, P, page, H, D)
    return (k.at[:, page_idx].set(k_view.reshape(pg_shape)),
            v.at[:, page_idx].set(v_view.reshape(pg_shape)))
