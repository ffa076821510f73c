"""What a served model states about its cache, and the contiguous views of a
paged pool that a model's cached forward reads and writes.

A model names its cache in a :class:`CacheSpec` (``cache_spec()``);
``serve.kv_cache.KVCachePool`` builds its arrays from it, so the serving
package reads this module and no model reads the serving package.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

__all__ = ["CacheSpec", "gather_views", "scatter_views",
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
    and copied around every kernel) if it were the minor one."""

    num_layers: int
    entries: tuple
    dtype: object = jnp.float32
    token_minor: bool = False

    def page_shape(self, shape: tuple, page_size: int) -> tuple:
        """One page of an entry whose token's share is ``shape``."""
        return (tuple(shape) + (page_size,) if self.token_minor
                else (page_size,) + tuple(shape))

    @classmethod
    def kv(cls, num_layers: int, num_heads: int, head_dim: int,
           dtype=jnp.float32) -> "CacheSpec":
        shape = (int(num_heads), int(head_dim))
        return cls(int(num_layers), (("k", shape), ("v", shape)), dtype)

    @classmethod
    def latent(cls, num_layers: int, width: int,
               dtype=jnp.float32) -> "CacheSpec":
        return cls(int(num_layers), (("latent", (int(width),)),), dtype,
                   token_minor=True)

    @property
    def holds_kv(self) -> bool:
        return tuple(n for n, _ in self.entries) == ("k", "v")

    @property
    def values_per_token(self) -> int:
        """Values one cached token holds in one layer."""
        return sum(int(np.prod(shape)) for _, shape in self.entries)

    @property
    def bytes_per_token(self) -> int:
        """Bytes one cached token holds in one layer."""
        return self.values_per_token * int(np.dtype(self.dtype).itemsize)

    def describe(self) -> dict:
        return {"entries": {n: list(shape) for n, shape in self.entries},
                "layers": self.num_layers, "dtype": str(np.dtype(self.dtype)),
                "token_minor": self.token_minor,
                "values_per_token_per_layer": self.values_per_token,
                "bytes_per_token_per_layer": self.bytes_per_token}


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
