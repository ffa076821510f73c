"""HostEmbedding — the Hybrid-mode embedding layer.

Reference semantics (executor.py:276-283 + optimizer.py:170-178): dense
params train on-chip with allreduce DP; embedding tables route through the
PS — always PS in hybrid mode, with the HET cache when a policy is set.
Here the dense model is ordinary on-chip pytree params and this layer holds
a host-side table (optionally cached), reached one of two ways:

- ``HostEmbedding``: io_callback bridge — the lookup/push happen INSIDE the
  jitted step (hetu_tpu/embed/bridge.py).  Needs a backend with host
  send/recv callback support (CPU, direct TPU).
- ``StagedHostEmbedding``: pull-outside/push-outside — ``stage(ids)`` pulls
  the batch's rows on the host and installs them as a pytree leaf, the
  jitted step consumes the leaf and returns its gradient, and the caller
  (exec.Trainer does it automatically) pushes the gradient back to the host
  engine.  Works on ANY backend, host callbacks or not, and is closest to
  the reference's actual
  sequencing: SparsePull before compute, SparsePush after
  (EmbeddingLookUp.py:34-40, ParameterServerCommunicate.py).
"""

from __future__ import annotations

import itertools

import jax.numpy as jnp
import numpy as np

from hetu_tpu.core.module import Module
from hetu_tpu.embed.bridge import Prefetcher, make_host_lookup, sync_fn
from hetu_tpu.embed.engine import (CacheTable, HostEmbeddingTable,
                                   publish_cache_stats)
from hetu_tpu.obs import journal as _obs_journal
from hetu_tpu.obs import registry as _obs

__all__ = ["HostEmbedding", "StagedHostEmbedding", "HBMCachedEmbedding"]

# deterministic telemetry labels for layers constructed without a name
# (process-local, so labels follow construction order like cache names)
_layer_names = itertools.count(0)


class _HostEmbeddingBase(Module):
    """Shared host-engine plumbing: table/cache construction, flush,
    save/load.  Subclasses differ only in how lookups/pushes cross the
    host<->device boundary."""

    def __init__(self, num_embeddings: int, dim: int, *,
                 optimizer: str = "sgd", lr: float = 0.01,
                 weight_decay: float = 0.0, seed: int = 0,
                 init_scale: float = 0.01, cache_capacity: int = 0,
                 policy: str = "lru", pull_bound: int = 0,
                 push_bound: int = 0, dtype=jnp.float32,
                 storage: str = "f32", name: str | None = None):
        self.num_embeddings = num_embeddings
        self.dim = dim
        self.dtype = dtype
        self.name = name if name is not None else f"embed{next(_layer_names)}"
        self.table = HostEmbeddingTable(
            num_embeddings, dim, optimizer=optimizer, lr=lr,
            weight_decay=weight_decay, seed=seed, init_scale=init_scale,
            storage=storage)
        if cache_capacity > 0:
            self.store = CacheTable(self.table, cache_capacity,
                                    policy=policy, pull_bound=pull_bound,
                                    push_bound=push_bound,
                                    name=f"{self.name}.host")
        else:
            self.store = self.table

    def flush(self):
        # engine CacheTable or PythonCacheTable (int8 tables); bare tables
        # have nothing to flush
        if getattr(self.store, "is_het_cache", False):
            self.store.flush()

    def attach_snapshot_writer(self, writer) -> None:
        """Register a :class:`~hetu_tpu.embed.stream.SnapshotWriter`: every
        gradient push's ids are reported so delta snapshots cover exactly
        the rows that changed.  Staged subclasses only (the callback
        bridge pushes inside jit, outside this hook's reach)."""
        h = getattr(self, "_handle", None)
        if h is None:
            raise TypeError(
                f"{type(self).__name__} has no host-side push hook; attach "
                f"the writer to a staged/HBM-cached embedding instead")
        h.snapshot_writers.append(writer)

    def _note_push(self, ids) -> None:
        h = getattr(self, "_handle", None)
        if h is not None:
            for w in h.snapshot_writers:
                w.note_push(ids)

    def save(self, path: str):
        # staged subclasses may have queued async pushes: drain them before
        # the (lockless) table snapshot or the checkpoint misses/tears rows
        flush_pushes = getattr(self, "flush_pushes", None)
        if flush_pushes is not None:
            flush_pushes()
        self.flush()
        self.table.save(path)

    def load(self, path: str):
        self.table.load(path)


class HostEmbedding(_HostEmbeddingBase):
    """Embedding whose rows live in host memory (HET capability).

    No on-chip parameters: lookups and gradient pushes go through the host
    engine, whose server-side optimizer owns the update rule.  ``cache``
    enables the worker-side cache with staleness bounds.
    """

    def __init__(self, num_embeddings: int, dim: int, **kw):
        super().__init__(num_embeddings, dim, **kw)
        self._lookup = make_host_lookup(self.store, dim)
        # Differentiable anchor keeping the lookup's backward (the host grad
        # push) alive in every grad trace; receives zero gradient itself.
        self.anchor = jnp.zeros((), jnp.float32)

    def __call__(self, ids):
        return self._lookup(ids, self.anchor).astype(self.dtype)


class _HostHandle:
    """Mutable host-side bookkeeping shared across pytree unflattens.

    Not an array and not a Module, so it lands in the static-aux partition
    of the pytree (compared by identity — the object never changes, only its
    contents, which are read exclusively OUTSIDE jit)."""

    __slots__ = ("ids", "prefetcher", "pusher", "push_err", "autosave",
                 "autosave_n", "snapshot_writers", "__weakref__")

    def __init__(self):
        self.ids = None
        self.prefetcher = None
        self.pusher = None    # ThreadPoolExecutor(1): FIFO async pushes
        self.push_err = None  # first exception from an async push
        self.autosave = None  # (path, every) from ShardedHostEmbedding
        self.autosave_n = 0
        self.snapshot_writers = []  # stream.SnapshotWriter note_push hooks


class StagedHostEmbedding(_HostEmbeddingBase):
    """Host-engine embedding with the pull/push staged OUTSIDE the jitted
    step — no host-callback support required from the backend.

    Per step: call ``stage(ids)`` (host pull → ``self.rows`` leaf), run the
    jitted step (it reads ``rows`` and produces its gradient), then
    ``push_grads(grad_rows)`` (host push; ``exec.Trainer`` detects staged
    embeddings and does this automatically).  ``__call__`` ignores its
    ``ids`` argument inside jit — the staged rows ARE that batch's rows;
    callers must stage the same ids they feed the model.

    Not compatible with sharding strategies that repartition the model
    (each worker owns its own host store, as in the reference's PS workers).
    """

    is_staged_host_embedding = True
    _state_fields = ("rows",)  # excluded from optimizer updates
    # async_push = the reference's ASP mode (PS default, executor.py:203
    # bsp=-1): gradient pushes apply on a worker thread, off the step's
    # critical path; rows pulled by the next stage() may be one push
    # stale.  Class-level default so subclasses with their own __init__
    # (RemoteHostEmbedding et al.) inherit BSP-strict behavior.
    async_push = False

    def __init__(self, num_embeddings: int, dim: int, *,
                 async_push: bool = False, **kw):
        super().__init__(num_embeddings, dim, **kw)
        self._handle = _HostHandle()
        if async_push:
            # the bare (uncached) table's pull is a lockless read in the C
            # engine; only the cache path serializes reader and writer, so
            # async pushes against a bare table would race stage() pulls
            if not getattr(self.store, "is_het_cache", False):
                raise ValueError(
                    "async_push needs cache_capacity > 0: the engine cache "
                    "serializes the worker thread's pushes against stage() "
                    "pulls; a bare table read would race them")
            self.async_push = True
        self.rows = jnp.zeros((1, dim), jnp.float32)  # placeholder leaf

    def prefetch(self, ids):
        """Start an async pull of the NEXT batch's rows on the engine's
        thread pool, overlapping with the current step (the reference's
        ParameterServerSparsePullOp overlap, executor.py:770-775).  A
        prefetch issued before the current step's gradient push may serve
        rows that miss that push for overlapping ids — the reference's
        bounded-staleness prefetch semantics; prefetch after ``step`` for
        strict freshness.  No-op for uncached stores (the C engine's async
        pull is cache-based).  The Prefetcher lives on the identity-stable
        host handle, so lazy creation does not perturb the module pytree."""
        # cached stores only — anything with a cache-aware ``sync`` entry
        # point (engine CacheTable, net.RemoteCacheTable, cached shard
        # routers); plain tables have no cache for a prefetch to warm
        if not hasattr(self.store, "sync"):
            return
        if self._handle.prefetcher is None:
            self._handle.prefetcher = Prefetcher(self.store)
        self._handle.prefetcher.prefetch(np.asarray(ids, np.int64))

    def stage(self, ids):
        """Host-side pull of this batch's rows into the ``rows`` leaf
        (serving from the prefetch buffer when the ids match).  Mutates the
        module in place; call OUTSIDE jit, before the step."""
        ids = np.asarray(ids, np.int64)
        if self._handle.prefetcher is not None:
            rows = self._handle.prefetcher.get(ids.ravel())
        else:
            rows = sync_fn(self.store)(ids.ravel())
        self.rows = jnp.asarray(
            np.asarray(rows).reshape(ids.shape + (self.dim,)), jnp.float32)
        self._handle.ids = ids

    def __call__(self, ids):
        # trace-time consistency check: the staged rows must cover exactly
        # this ids batch (catches step/eval without a fresh stage())
        if tuple(ids.shape) != tuple(self.rows.shape[:-1]):
            raise ValueError(
                f"staged rows {self.rows.shape[:-1]} do not match ids batch "
                f"{tuple(ids.shape)}: call stage(ids) with this batch's ids "
                f"before the jitted step")
        return self.rows.astype(self.dtype)

    def is_fresh(self) -> bool:
        """True if stage() has been called since the last push_grads —
        i.e. the rows leaf holds the current batch."""
        return self._handle.ids is not None

    def push_grads(self, grad_rows):
        """Host-side push of the staged batch's row gradients; the engine's
        server-side optimizer applies them.  Consumes the staged ids: a
        second push (or a step run without a fresh ``stage``) raises instead
        of silently corrupting the table with stale ids.

        With ``async_push`` the device→host materialization and the engine
        push run on a single worker thread (FIFO, so pushes apply in step
        order) instead of blocking the training loop — on a
        high-dispatch-latency link this is the difference between the push
        round trip serializing every step or hiding under the next one.
        Call ``flush_pushes()`` before checkpointing or evaluation."""
        h = self._handle
        if h.push_err is not None:
            # surface a worker-side failure BEFORE consuming this step's
            # staged ids, so the caller can handle it and retry this push
            err, h.push_err = h.push_err, None
            raise err
        ids = h.ids
        if ids is None:
            raise RuntimeError(
                "push_grads without a fresh stage(): call stage(ids) before "
                "every training step")
        h.ids = None
        self._note_push(ids)
        if not self.async_push:
            self.store.push(ids.ravel(), np.asarray(
                grad_rows, np.float32).reshape(-1, self.dim))
            return
        if h.pusher is None:
            from concurrent.futures import ThreadPoolExecutor
            import weakref
            h.pusher = ThreadPoolExecutor(1)
            # finalize on the identity-stable HANDLE: the module itself is
            # rebuilt on every pytree unflatten and would tear the pool
            # down after the first step
            weakref.finalize(h, h.pusher.shutdown, wait=False)
        try:  # start the device->host copy without blocking this thread
            grad_rows.copy_to_host_async()
        except AttributeError:
            pass

        def apply(ids=ids, g=grad_rows):
            try:
                self.store.push(ids.ravel(), np.asarray(
                    g, np.float32).reshape(-1, self.dim))
            except Exception as e:  # surfaced on the next push/flush
                h.push_err = e
        h.pusher.submit(apply)

    def flush_pushes(self):
        """Block until every queued async push has applied (checkpoint /
        eval barrier); re-raises the first worker-side failure."""
        h = self._handle
        if h.pusher is not None:
            h.pusher.submit(lambda: None).result()
        if h.push_err is not None:
            err, h.push_err = h.push_err, None
            raise err


class _HBMHandle:
    """Mutable host-side cache directory (identity-stable across pytree
    unflattens, read/written exclusively OUTSIDE jit).  All-numpy: per-step
    bookkeeping over ~10k unique ids must be vectorized, not dict loops —
    measured 25 ms/step of pure Python otherwise.  The id-indexed arrays
    cost 12 bytes/row of the FULL table (the reference's HET keeps per-row
    version metadata at the same order)."""

    __slots__ = ("slot_of", "id_of", "staleness", "last_used", "tick",
                 "ids", "touched_ids", "prefetcher", "pushed_since_prefetch",
                 "hits", "misses", "evictions", "overflows",
                 "snapshot_writers", "rows_dirty", "tier")

    def __init__(self, capacity: int, num_embeddings: int):
        self.slot_of = np.full(num_embeddings, -1, np.int64)  # id -> slot
        self.id_of = np.full(capacity, -1, np.int64)          # slot -> id
        self.staleness = np.zeros(num_embeddings, np.int32)
        self.last_used = np.zeros(capacity, np.int64)
        self.tick = 0
        self.ids = None
        self.touched_ids = None
        self.prefetcher = None
        self.pushed_since_prefetch = None  # ids pushed after prefetch issue
        # cumulative HBM-tier accounting (unique rows per stage: resident-
        # and-fresh = hit, refreshed/overflowed = miss)
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.overflows = 0
        self.snapshot_writers = []  # stream.SnapshotWriter note_push hooks
        self.rows_dirty = False  # rows leaf carries overflow values
        self.tier = None  # tier.TieredEmbedding bookkeeping (_TierState)


class HBMCachedEmbedding(_HostEmbeddingBase):
    """Host-store embedding whose HOT ROWS are staged into device HBM —
    the north-star layout for huge tables (BASELINE.json: "the hetu_cache
    sparse-embedding module keeps host-side caching but stages hot rows to
    HBM").

    The full table lives in the host engine (server-side optimizer, like
    the reference's PS); a fixed-capacity ``cache`` array lives in HBM and
    is managed as an LRU cache with HET-style bounded staleness:

    - ``stage(ids)`` refreshes only MISSING or TOO-STALE rows (one small
      host→device scatter, padded to power-of-two buckets so it compiles
      once per bucket), and installs the batch's slot indices — warm steps
      upload O(refreshed) bytes instead of O(batch) like
      StagedHostEmbedding.
    - ``__call__`` gathers from the HBM cache inside jit.  Values flow
      from the cache under ``stop_gradient``; the gradient rides a zeros
      ``rows`` leaf added to the gather, so the cotangent arrives
      batch-shaped ((..., dim) like StagedHostEmbedding) instead of as a
      dense (capacity, dim) scatter buffer.
    - ``push_grads`` (Trainer calls it) ships the batch row-gradients to
      the host engine (duplicate ids accumulate there) and advances each
      pushed id's staleness — rows are re-pulled once they exceed
      ``hbm_pull_bound`` server updates (0 = strict freshness).

    Wins over StagedHostEmbedding when the id distribution is skewed and
    a staleness bound amortizes refreshes (HET's regime, VLDB'22) or when
    per-row bytes are large; at small dim / uniform ids the plain staged
    transfer is already cheap — measure both (examples/train_ctr.py
    --embedding host|hbm).
    """

    is_staged_host_embedding = True
    is_hbm_cached_embedding = True
    _state_fields = ("cache", "rows", "slots", "refresh_slots",
                     "refresh_rows")  # no optimizer updates

    def __init__(self, num_embeddings: int, dim: int, *,
                 hbm_capacity: int = 4096, hbm_pull_bound: int = 0, **kw):
        super().__init__(num_embeddings, dim, **kw)
        if hbm_capacity <= 0:
            raise ValueError("hbm_capacity must be > 0")
        if hbm_capacity >= (1 << 24):
            raise ValueError("hbm_capacity must stay below 2**24: slot "
                             "indices ride a float32 leaf (see below) and "
                             "larger values are not exactly representable")
        self.capacity = int(hbm_capacity)
        self.pull_bound = int(hbm_pull_bound)
        self._handle = _HBMHandle(self.capacity, num_embeddings)
        self.cache = jnp.zeros((self.capacity, dim), jnp.float32)
        # zero-valued gradient channel: cotangent of the lookup lands here
        # batch-shaped; the buffer itself never changes between same-shape
        # batches (no per-step upload)
        self.rows = jnp.zeros((1, dim), jnp.float32)
        # slot indices ride a float32 leaf: the Trainer differentiates the
        # whole module pytree and jax.grad rejects integer leaves; float32
        # is exact for slot ids < 2^24 and gets a zero cotangent
        self.slots = jnp.zeros((1,), jnp.float32)  # placeholder leaf
        # pending refresh, applied INSIDE the jitted step: stage() only
        # sets these leaves (their upload rides the step's own dispatch);
        # Trainer.apply_refresh folds them into the cache so the scatter
        # is not a separate device dispatch (which measured slower than
        # the plain staged path on a high-latency link, ROADMAP #5)
        self.refresh_slots = jnp.full((1,), self.capacity, jnp.float32)
        self.refresh_rows = jnp.zeros((1, dim), jnp.float32)

    def _merged_cache(self):
        # mode="drop": the (1,) no-op placeholder indexes == capacity
        return self.cache.at[self.refresh_slots.astype(jnp.int32)].set(
            self.refresh_rows, mode="drop")

    def apply_refresh(self):
        """Fold the pending refresh into the cache leaf and reset the
        pending leaves to their no-op shape; called by the Trainer inside
        the jitted step so the merged cache persists into the next state."""
        return self.replace(
            cache=self._merged_cache(),
            refresh_slots=jnp.full((1,), self.capacity, jnp.float32),
            refresh_rows=jnp.zeros((1, self.dim), jnp.float32))

    def prefetch(self, ids):
        """Async host pull of the next batch's unique rows (overlap with
        the current step); stage() serves the refresh subset from it."""
        if not hasattr(self.store, "sync"):
            return
        if self._handle.prefetcher is None:
            self._handle.prefetcher = Prefetcher(self.store)
        self._handle.prefetcher.prefetch(np.unique(np.asarray(ids, np.int64)))
        # rows pushed AFTER this point are newer than the buffered pull;
        # stage() must not install them from the buffer as "fresh"
        self._handle.pushed_since_prefetch = []

    def _split_residency(self, uniq: np.ndarray):
        """Partition the batch's unique rows into ``(cached, overflow)``:
        rows that may occupy HBM slots this stage vs rows served through
        the host path for this batch only.  The base rule is capacity:
        more unique rows than slots keeps every currently-resident row,
        fills the remaining capacity, and spills the rest (journaled) —
        a fat batch degrades to the staged transfer instead of killing
        the step.  ``TieredEmbedding`` layers its promotion policy on
        top."""
        h = self._handle
        if uniq.size <= self.capacity:
            return uniq, np.empty(0, np.int64)
        cached_mask = h.slot_of[uniq] >= 0
        resident, nonres = uniq[cached_mask], uniq[~cached_mask]
        budget = self.capacity - resident.size
        cuniq = np.sort(np.concatenate([resident, nonres[:budget]]))
        overflow = nonres[budget:]  # sorted (nonres is)
        h.overflows += int(overflow.size)
        _obs_journal.record(
            "hbm_overflow", table=self.name,
            batch_rows=int(uniq.size), overflow=int(overflow.size),
            capacity=int(self.capacity))
        return cuniq, overflow

    def stage(self, ids):
        h = self._handle
        if self.refresh_slots.shape != (1,):
            # the previous refresh was never folded in (standalone/eval use
            # without the Trainer's in-step apply): fold it now before the
            # leaves are overwritten — in the Trainer loop apply_refresh
            # already reset the leaves and this never dispatches
            self.cache = self._merged_cache()
            self.refresh_slots = jnp.full((1,), self.capacity, jnp.float32)
            self.refresh_rows = jnp.zeros((1, self.dim), jnp.float32)
        ids = np.asarray(ids, np.int64)
        uniq = np.unique(ids.ravel())
        h.tick += 1
        cuniq, overflow = self._split_residency(uniq)
        cur_slots = h.slot_of[cuniq]
        cached = cur_slots >= 0
        need_mask = (~cached) | (h.staleness[cuniq] > self.pull_bound)
        need = cuniq[need_mask]
        h.hits += int(cuniq.size - need.size)
        h.misses += int(need.size + overflow.size)
        over_rows = None
        if need.size or overflow.size:
            need_slots = cur_slots[need_mask]  # -1 where not resident
            miss = need_slots < 0
            n_miss = int(miss.sum())
            if n_miss:
                free = np.flatnonzero(h.id_of < 0)
                if free.size < n_miss:
                    # LRU victims among OCCUPIED slots not used by this
                    # batch (free slots must not be re-picked as victims:
                    # that would hand one slot to two ids, and id_of[-1]
                    # bookkeeping would corrupt the directory)
                    in_batch = np.zeros(self.capacity + 1, bool)
                    in_batch[cur_slots[cached]] = True
                    order = np.argsort(h.last_used, kind="stable")
                    occupied = h.id_of[order] >= 0
                    victims = order[occupied & ~in_batch[order]]
                    extra = n_miss - free.size
                    # always satisfiable: free + occupied-not-in-batch =
                    # capacity - cached >= cuniq - cached >= n_miss (the
                    # uniq > capacity case was trimmed to cuniq above)
                    assert victims.size >= extra, "slot accounting broken"
                    evict = victims[:extra]
                    h.evictions += int(evict.size)
                    h.slot_of[h.id_of[evict]] = -1
                    free = np.concatenate([free, evict])
                alloc = free[:n_miss]
                need_slots[miss] = alloc
            h.slot_of[need] = need_slots
            h.id_of[need_slots] = need
            h.staleness[need] = 0
            # one batched host fetch covers the cache refresh AND the
            # overflow rows served host-side this batch
            fetch = np.concatenate([need, overflow])
            if h.prefetcher is not None:
                rows_all = np.asarray(h.prefetcher.get(uniq))
                fetched = rows_all[np.searchsorted(uniq, fetch)].copy()
                # the buffered pull predates any push issued after
                # prefetch(): re-pull those rows synchronously so a stale
                # snapshot is never installed (or served) with staleness 0
                pushed = h.pushed_since_prefetch or []
                if pushed:
                    dirty = np.isin(fetch, np.concatenate(pushed))
                    if dirty.any():
                        fetched[dirty] = np.asarray(
                            sync_fn(self.store)(fetch[dirty])).reshape(
                                -1, self.dim)
            else:
                fetched = np.asarray(sync_fn(self.store)(fetch))
            fetched = fetched.reshape(fetch.size, self.dim).astype(
                np.float32)
            fresh, over_rows = fetched[:need.size], fetched[need.size:]
        if need.size:
            # pad the refresh to a power-of-two bucket so the step
            # compiles once per bucket instead of once per distinct
            # refresh size (a per-step recompile would dwarf the transfer
            # saving the cache exists for); padded slots index out of
            # range and mode="drop" discards them
            bucket = max(8, 1 << (need.size - 1).bit_length())
            # COUPLING: stage() detects a pending refresh by
            # refresh_slots.shape != (1,), which is only unambiguous
            # because the bucket floor keeps every real refresh >= 8
            # rows.  A floor of 1 would make a one-row refresh
            # indistinguishable from the no-op placeholder and silently
            # dropped.
            assert bucket > 1, "bucket floor must exceed the (1,) no-op"
            pad = bucket - need.size
            if pad:
                need_slots = np.concatenate(
                    [need_slots, np.full(pad, self.capacity, np.int64)])
                fresh = np.concatenate(
                    [fresh, np.zeros((pad, self.dim), np.float32)])
            # leaves only — the scatter itself runs inside the jitted step
            self.refresh_slots = jnp.asarray(need_slots, jnp.float32)
            self.refresh_rows = jnp.asarray(fresh)
        else:
            if h.prefetcher is not None and not overflow.size:
                h.prefetcher.get(uniq)  # retire the pending pull
            self.refresh_slots = jnp.full((1,), self.capacity, jnp.float32)
            self.refresh_rows = jnp.zeros((1, self.dim), jnp.float32)
        slot_lut = h.slot_of[uniq]          # -1 for overflow ids
        live = slot_lut >= 0
        h.last_used[slot_lut[live]] = h.tick
        batch_slots = slot_lut[np.searchsorted(uniq, ids.ravel())]
        # overflow ids gather the fill row (zeros) from the cache; their
        # values ride the ``rows`` leaf instead
        batch_slots = np.where(batch_slots >= 0, batch_slots, self.capacity)
        self.slots = jnp.asarray(batch_slots.reshape(ids.shape), jnp.float32)
        if overflow.size:
            rows_arr = np.zeros(tuple(ids.shape) + (self.dim,), np.float32)
            flat = ids.ravel()
            m = np.isin(flat, overflow)
            rows_flat = rows_arr.reshape(-1, self.dim)
            rows_flat[m] = over_rows[np.searchsorted(overflow, flat[m])]
            # explicit copy: the leaf is donate-eligible in the jitted
            # step, and a zero-copy view of rows_arr's host buffer being
            # donated would free memory numpy still owns
            self.rows = jnp.array(rows_arr)
            h.rows_dirty = True
        elif (h.rows_dirty
              or tuple(self.rows.shape) != tuple(ids.shape) + (self.dim,)):
            self.rows = jnp.zeros(tuple(ids.shape) + (self.dim,),
                                  jnp.float32)
            h.rows_dirty = False
        h.ids = ids
        h.touched_ids = uniq

    def __call__(self, ids):
        if tuple(ids.shape) != tuple(self.slots.shape):
            raise ValueError(
                f"staged slots {tuple(self.slots.shape)} do not match ids "
                f"batch {tuple(ids.shape)}: call stage(ids) with this "
                f"batch's ids before the jitted step")
        import jax

        # gather from the cache WITH the pending refresh merged in (a
        # no-op scatter once the Trainer has applied it); values are
        # stop_gradient'd — the cotangent rides the ``rows`` leaf, which
        # is zeros except at overflow positions (whose values it carries:
        # slot == capacity gathers the fill row)
        gathered = jax.lax.stop_gradient(
            jnp.take(self._merged_cache(), self.slots.astype(jnp.int32),
                     axis=0, mode="fill", fill_value=0.0))
        return (gathered + self.rows).astype(self.dtype)

    def is_fresh(self) -> bool:
        return self._handle.ids is not None

    def push_grads(self, grad_rows):
        """``grad_rows`` is the batch-shaped cotangent of the lookup; ship
        it to the host engine and bump the pushed ids' staleness.
        Duplicate ids are accumulated HERE (one optimizer apply per unique
        row): the bare table dedups internally, but the HET cache's push
        path applies per occurrence, and the tiered layer routes pushes
        through the host cache — pre-deduping keeps both stores on the
        reference ReduceIndexedSlice-then-update semantics (and halves
        push bytes on skewed batches for free)."""
        h = self._handle
        if h.ids is None:
            raise RuntimeError(
                "push_grads without a fresh stage(): call stage(ids) before "
                "every training step")
        flat = h.ids.ravel()
        g = np.asarray(grad_rows, np.float32).reshape(-1, self.dim)
        uniq, inv = np.unique(flat, return_inverse=True)
        acc = np.zeros((uniq.size, self.dim), np.float32)
        np.add.at(acc, inv, g)
        self.store.push(uniq, acc)
        h.staleness[h.touched_ids] += 1
        if h.pushed_since_prefetch is not None:
            h.pushed_since_prefetch.append(h.touched_ids)
        self._note_push(h.ids)
        h.ids = None
        h.touched_ids = None

    def invalidate_rows(self, ids) -> None:
        """Force a host re-pull of ``ids`` on their next stage regardless
        of ``hbm_pull_bound`` — the hook a snapshot install (or any
        external ``set_rows``) uses so the device copies never serve
        pre-install values."""
        ids = np.asarray(ids, np.int64).ravel()
        self._handle.staleness[ids] = np.iinfo(np.int32).max

    def hit_stats(self) -> dict:
        """HBM-tier cache accounting (unique rows per stage: resident-and-
        fresh = hit, refreshed or overflowed = miss), mirrored onto
        /metrics via :func:`~hetu_tpu.embed.engine.publish_cache_stats`
        under this layer's ``name`` — embedding hit rates scrape beside
        the serve tier's prefix-cache rates."""
        h = self._handle
        total = h.hits + h.misses
        out = {"hits": int(h.hits), "misses": int(h.misses),
               "size": int((h.id_of >= 0).sum()),
               "hit_rate": h.hits / total if total else 0.0,
               "evictions": int(h.evictions),
               "overflows": int(h.overflows),
               "resident": int((h.id_of >= 0).sum()),
               "capacity": self.capacity}
        if _obs.enabled():
            publish_cache_stats(self.name, out)
        return out
