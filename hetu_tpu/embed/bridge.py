"""Bridging the host embedding engine into jitted programs.

The reference reaches its PS/cache from the executor's Python compute loop
(EmbeddingLookUp.py:34-47 dispatches to SparsePull RPC or the HET cache;
ParameterServerCommunicate.py pushes IndexedSlices grads).  Under XLA the
train step is one compiled program, so the host path enters via
``io_callback``: the forward lookup is an ordered host callback, and the
gradient push rides the backward pass of a ``custom_vjp`` — preserving the
reference's semantics (lookup-then-async-push) inside one jitted step.

Perf notes: host→TPU transfers for looked-up rows ride the callback; the
``Prefetcher`` overlaps next-batch row pulls with the current step
(reference prefetch path, executor.py:770-775), and the engine's thread pool
makes pushes async so the step never waits on the host optimizer.
"""

from __future__ import annotations

from typing import Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import io_callback

from hetu_tpu.embed.engine import AsyncEngine, CacheTable, HostEmbeddingTable

__all__ = ["make_host_lookup", "Prefetcher", "host_callbacks_supported",
           "sync_fn"]

Store = Union[HostEmbeddingTable, CacheTable]


_CALLBACK_PROBE: dict = {}


def host_callbacks_supported() -> bool:
    """Whether the default backend supports host send/recv callbacks
    (jax io_callback / pure_callback).  Feature-probed by compiling and
    running a trivial callback once (cached per process).  A backend
    without them rejects the program with UNIMPLEMENTED; that one error
    reads ``False`` and anything else propagates, so a broken backend is
    not mistaken for one that merely lacks callbacks.  Used to pick the
    host-embedding bridge (io_callback vs staged) automatically: the CPU
    and a directly attached TPU both answer ``True``."""
    key = jax.default_backend()
    if key not in _CALLBACK_PROBE:
        try:
            # probe with pure_callback: a backend lacking host callbacks
            # rejects it fast, whereas an unsupported ORDERED io_callback
            # can hang instead of erroring — same capability either way
            out = jax.jit(lambda x: jax.pure_callback(
                lambda a: np.asarray(a), jax.ShapeDtypeStruct((), jnp.int32),
                x))(jnp.int32(7))
            _CALLBACK_PROBE[key] = int(out) == 7
        except jax.errors.JaxRuntimeError as e:
            if "UNIMPLEMENTED" not in str(e):
                raise
            _CALLBACK_PROBE[key] = False
    return _CALLBACK_PROBE[key]


def sync_fn(store: Store):
    """The store's row-pull entry point: cache-aware ``sync`` for
    CacheTable, plain ``pull`` otherwise."""
    return store.sync if isinstance(store, CacheTable) else store.pull



def make_host_lookup(store: Store, dim: int):
    """Returns ``lookup(ids, anchor) -> rows`` usable inside jit/grad.

    Forward: ordered host callback into ``store.sync``/``pull``.
    Backward: ordered host callback into ``store.push`` (the engine applies
    its server-side optimizer).

    ``anchor`` must be a *differentiated* float scalar (a trainable model
    leaf — ``HostEmbedding`` carries one).  Without it the whole lookup has
    only the int ids as input, JAX prunes its backward as unreachable from
    any differentiable input, and gradients would silently never reach the
    host table.
    """
    pull = sync_fn(store)

    def _raw_lookup(ids):
        shape = jax.ShapeDtypeStruct(tuple(ids.shape) + (dim,), jnp.float32)

        def host(i):
            i = np.asarray(i)
            return pull(i.ravel().astype(np.int64)).reshape(
                tuple(i.shape) + (dim,))

        return io_callback(host, shape, ids, ordered=True)

    @jax.custom_vjp
    def lookup(ids, anchor):
        return _raw_lookup(ids)

    def fwd(ids, anchor):
        return _raw_lookup(ids), ids

    def bwd(ids, g):
        def host(i, gg):
            store.push(np.asarray(i).ravel().astype(np.int64),
                       np.asarray(gg, np.float32).reshape(-1, dim))
            return np.zeros((), np.float32)

        io_callback(host, jax.ShapeDtypeStruct((), jnp.float32), ids, g,
                    ordered=True)
        return (np.zeros(ids.shape, jax.dtypes.float0),
                jnp.zeros((), jnp.float32))

    lookup.defvjp(fwd, bwd)
    return lookup


class Prefetcher:
    """Double-buffered async row pulls (reference ParameterServerSparsePullOp
    overlap, executor.py:770-775).

    ``prefetch(next_ids)`` starts an async sync on the engine's thread pool;
    ``get(ids)`` returns the prefetched rows if they match, else pulls
    synchronously.
    """

    def __init__(self, store, engine: AsyncEngine | None = None):
        self.store = store
        # engine CacheTable: async pulls run on the C++ engine thread pool;
        # any other store with a row-pull entry point (net.RemoteCacheTable,
        # remote stubs) overlaps on a Python thread instead
        self._native = isinstance(store, CacheTable)
        if self._native:
            self.engine = engine or AsyncEngine(2)
        else:
            from concurrent.futures import ThreadPoolExecutor
            import weakref
            self._pool = ThreadPoolExecutor(1)
            weakref.finalize(self, self._pool.shutdown, wait=False)
        self._pending = None  # (ticket_or_future, ids_key, out_or_None)

    def _drain(self):
        """Retire the pending pull (wait + drop) — an abandoned ticket would
        keep its buffers pinned in the engine's live set."""
        if self._pending is not None:
            ticket, _, _ = self._pending
            self._pending = None
            if self._native:
                self.engine.wait(ticket)
            else:
                ticket.result()

    def __del__(self):
        # drain before teardown: Python gives no destruction order between
        # this object's engine and the CacheTable it pulls through, so an
        # in-flight async pull must not outlive either
        try:
            self._drain()
        except Exception:
            pass

    def prefetch(self, ids):
        self._drain()
        ids = np.asarray(ids, np.int64).ravel()
        if self._native:
            ticket, out = self.engine.sync_async(self.store, ids)
            self._pending = (ticket, ids.tobytes(), out)
        else:
            fut = self._pool.submit(sync_fn(self.store), ids)
            self._pending = (fut, ids.tobytes(), None)

    def get(self, ids) -> np.ndarray:
        ids = np.asarray(ids, np.int64).ravel()
        if self._pending is not None and self._pending[1] == ids.tobytes():
            ticket, _, out = self._pending
            self._pending = None
            if self._native:
                self.engine.wait(ticket)
                return out
            return ticket.result()
        # mismatch: retire the stale pull NOW — matching it against a
        # same-ids stage() many pushes later would serve rows of unbounded
        # staleness
        self._drain()
        return sync_fn(self.store)(ids)
