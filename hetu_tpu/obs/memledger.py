"""The HBM ledger: exact, bitwise-deterministic device-memory attribution.

The reference stack dedicates a whole subsystem (``src/memory_pool/``, a
BFC allocator) to knowing where device memory lives, because at scale
HBM is the binding constraint.  This module is the rebuild's equivalent,
built observability-first: a process-wide :class:`MemoryLedger` that
attributes every accounted HBM byte to a **component**

- ``kv_pool`` — :class:`~hetu_tpu.serve.kv_cache.KVCachePool` pages, by
  class (``active | shared_prefix | export_hold | scratch | free``, the
  exact partition ``KVCachePool.page_classes`` computes) and by owner
  (per-tenant table-page holds, the PR 16 identity);
- ``embed_hbm`` — :class:`~hetu_tpu.embed.tier.TieredEmbedding` resident
  hot rows (rows × dim × 4, the f32 HBM tier);
- ``train_weights`` / ``train_optimizer`` — the train step's pytree
  (every array leaf's ``size × itemsize``);
- ``compile`` — executable + temp bytes per instrumented jit site, from
  ``compiled.memory_analysis()`` (``obs.compile.InstrumentedJit``);

fed through instrumented seams (:func:`note_kv`, :func:`note_embed`,
:func:`note_compile`, :func:`note_train_state`) that follow the obs
overhead contract: with no ledger installed (or telemetry disabled) each
seam is one module-global load and a branch.

The ledger is **exact by construction**: every :meth:`~MemoryLedger.
snapshot` asserts that the per-class KV bytes sum to the pool's array
bytes (``k.nbytes + v.nbytes``) — attribution can never silently drop or
double-count a page.  It carries per-component high-water marks, a
free-list fragmentation gauge, and an alloc/free-balance **leak
watchdog**: the seams post alloc/free *events*, the ledger integrates
the balance and cross-checks it against the pool's own live-sequence
count; a drift sustained for ``leak_grace`` snapshots journals
``mem_leak_suspect`` naming the component — an unledgered free path (or
a skipped free) is named, not inferred from an OOM hours later.

Served at ``/memory`` (``obs.server.telemetry_routes``), fleet-merged at
``/fleet/memory`` (``obs.fleet.FleetAggregator.memory``), reconciled
against ``mem.estimator`` predictions via :meth:`~MemoryLedger.
reconcile` (extending PR 12's ``reconcile`` → ``mem_estimate_drift``),
ingested into the calibration :class:`~hetu_tpu.obs.calibration.
ProfileStore` via ``ingest_memory``, and exposed to the
:class:`~hetu_tpu.exec.controller.RuntimeController` as the
:meth:`~MemoryLedger.memory_pressure` signal its ``memory_pressure``
remediation loop acts on (defrag, then shed).

Snapshots contain no wall-clock state and iterate every map in sorted
order, so same-seed replays produce bitwise-identical snapshots — the
chaos acceptance bar.
"""

from __future__ import annotations

import contextlib
import weakref
from typing import Optional

import numpy as np

from hetu_tpu.obs import journal as _journal
from hetu_tpu.obs import registry as _registry

__all__ = ["MemoryLedger", "get_ledger", "install_ledger", "use",
           "note_kv", "note_embed", "note_compile", "note_train_state",
           "KV_PAGE_CLASSES"]

#: The exact KV page partition (KVCachePool.page_classes): every physical
#: page lands in exactly one class, counts sum to ``num_pages``.
KV_PAGE_CLASSES = ("active", "shared_prefix", "export_hold", "scratch",
                   "free")

# Ledger metric families, built on first publication (never while
# telemetry is disabled — the disabled path must register nothing).
_led_metrics = None


def _led_m() -> dict:
    global _led_metrics
    if _led_metrics is None:
        reg = _registry.get_registry()
        _led_metrics = {
            "component": reg.gauge(
                "hetu_memledger_component_bytes",
                "ledger-attributed resident device bytes by component "
                "(kv_pool, embed_hbm, compile, train_weights, "
                "train_optimizer)", ("component",)),
            "hwm": reg.gauge(
                "hetu_memledger_hwm_bytes",
                "per-component high-water mark of the ledger-attributed "
                "bytes since install (plus the 'total' series)",
                ("component",)),
            "kv_class": reg.gauge(
                "hetu_memledger_kv_class_bytes",
                "KV-pool bytes by page class, summed across tracked "
                "pools — the exact partition (classes sum to the pool "
                "arrays' bytes)", ("klass",)),
            "frag": reg.gauge(
                "hetu_memledger_kv_fragmentation",
                "free-list fragmentation of the worst tracked pool: "
                "1 - longest contiguous free run / free pages (0 = one "
                "contiguous run or an empty free list)"),
            "total": reg.gauge(
                "hetu_memledger_total_bytes",
                "sum of all ledger-attributed component bytes"),
            "pressure": reg.gauge(
                "hetu_memledger_pressure",
                "worst-pool used-page fraction — the ledger-backed "
                "signal the controller's memory_pressure loop acts on"),
            "allocs": reg.counter(
                "hetu_memledger_allocs_total",
                "sequence allocations the instrumented seams posted, by "
                "component", ("component",)),
            "frees": reg.counter(
                "hetu_memledger_frees_total",
                "sequence frees the instrumented seams posted, by "
                "component", ("component",)),
            "leaks": reg.counter(
                "hetu_memledger_leak_suspects_total",
                "mem_leak_suspect verdicts the watchdog journaled, by "
                "component", ("component",)),
        }
    return _led_metrics


def _fragmentation(free_sorted) -> float:
    """1 - longest contiguous run / free count over an ascending free
    list (0.0 when empty or fully contiguous) — the defrag trigger."""
    n = len(free_sorted)
    if n == 0:
        return 0.0
    longest = run = 1
    for a, b in zip(free_sorted, free_sorted[1:]):
        run = run + 1 if b == a + 1 else 1
        if run > longest:
            longest = run
    return 1.0 - longest / n


def _pool_page_bytes(pool) -> int:
    """Device bytes one physical page holds across the pool's arrays (k
    AND v, or whatever the model's cache spec names).  (The ledger reads
    only the spec and ``nbytes`` of the pool's arrays: metadata that an
    array a serving step has consumed still answers, so a snapshot needs
    no engine lock and touches no buffer.)"""
    return pool.num_layers * pool.page_size * pool.spec.bytes_per_token


def _tree_bytes(tree) -> int:
    """size × itemsize over every array leaf of a pytree."""
    import jax
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        size = getattr(leaf, "size", None)
        dtype = getattr(leaf, "dtype", None)
        if size is not None and dtype is not None:
            total += int(size) * int(np.dtype(dtype).itemsize)
    return total


class MemoryLedger:
    """Process-wide device-byte attribution (see module doc).

    State is integrated from the seams (alloc/free events, embed
    residency, compile memory analyses, train-state bytes) plus live
    reads of the tracked pools at snapshot time — so the byte
    attribution is exact by construction, while the event balance
    cross-check catches code paths that mutate a pool without posting.
    Pools are keyed by ARRIVAL ORDER per ledger (``"0"``, ``"1"``, …),
    so a fresh ledger per same-seed replay yields identical keys.
    """

    def __init__(self, *, leak_grace: int = 3):
        if leak_grace < 1:
            raise ValueError(f"leak_grace must be >= 1, got {leak_grace}")
        self.leak_grace = int(leak_grace)
        self._pools: list = []        # weakref.ref, arrival order
        self._pool_index: dict = {}   # id(pool) -> index
        self._kv_events: dict = {}    # index -> {"allocs", "frees"}
        self._embed: dict = {}        # table -> {"rows", "bytes"}
        self._compile: dict = {}      # site -> {"executable_bytes",
        #                                        "temp_bytes", "programs"}
        self._train = {"weights_bytes": 0, "optimizer_bytes": 0}
        self._hwm: dict = {}          # component -> bytes
        self._leak_streak: dict = {}  # component -> drifting snapshots
        self._leak_flagged: set = set()
        self.leak_suspects: list = []
        self.snapshots = 0

    # -- the seams' write side ----------------------------------------------

    def _track(self, pool) -> int:
        idx = self._pool_index.get(id(pool))
        if idx is not None and self._pools[idx]() is pool:
            return idx
        # new pool (or a reused id after gc): next arrival-order slot
        idx = len(self._pools)
        self._pools.append(weakref.ref(pool))
        self._pool_index[id(pool)] = idx
        self._kv_events[idx] = {"allocs": 0, "frees": 0,
                                "peak_used_pages": 0,
                                "peak_shared_pages": 0}
        return idx

    def note_kv(self, pool, *, alloc: int = 0, free: int = 0) -> None:
        """One KV-pool mutation: track the pool, integrate alloc/free
        events (the watchdog's balance), and advance the peak-occupancy
        mark.  Byte attribution itself is read live from the pool at
        snapshot time.  The shared-page count (an O(live pages) scan) is
        taken only when a NEW peak is set — peaks are monotone, so the
        scan runs at most ``num_pages`` times over a pool's lifetime."""
        ev = self._kv_events[self._track(pool)]
        ev["allocs"] += int(alloc)
        ev["frees"] += int(free)
        used = (pool.num_pages - 1) - pool.free_pages
        if used > ev["peak_used_pages"]:
            ev["peak_used_pages"] = int(used)
            ev["peak_shared_pages"] = sum(
                1 for rc in pool._refcount.values() if rc > 1)

    def note_embed(self, table: str, rows: int, nbytes: int) -> None:
        """Resident HBM hot rows of one embedding table (exact: the
        staging protocol's own residency map)."""
        self._embed[str(table)] = {"rows": int(rows), "bytes": int(nbytes)}

    def note_compile(self, site: str, memory: dict) -> None:
        """One compiled program at an instrumented jit site: executable
        bytes ACCUMULATE (every program stays resident in jit's
        cache), temp bytes take the site max (transient workspace of the
        largest program)."""
        ent = self._compile.setdefault(
            str(site), {"executable_bytes": 0, "temp_bytes": 0,
                        "programs": 0})
        ent["executable_bytes"] += int(memory.get("generated_code", 0))
        ent["temp_bytes"] = max(ent["temp_bytes"],
                                int(memory.get("temp", 0)))
        ent["programs"] += 1

    def note_train_state(self, state) -> None:
        """Model weights + optimizer state bytes from the train state's
        pytree (every array leaf's ``size × itemsize``)."""
        self._train = {
            "weights_bytes": _tree_bytes(state.model),
            "optimizer_bytes": _tree_bytes(state.opt_state),
        }

    # -- the read side -------------------------------------------------------

    def _live_pools(self) -> list:
        return [(i, p) for i, r in enumerate(self._pools)
                if (p := r()) is not None]

    def memory_pressure(self) -> float:
        """Worst-pool used-page fraction in [0, 1] (0.0 with no tracked
        pools) — the controller's remediation signal."""
        worst = 0.0
        for _i, pool in self._live_pools():
            cap = pool.num_pages - 1
            if cap > 0:
                worst = max(worst, (cap - pool.free_pages) / cap)
        return worst

    def _watchdog(self, component: str, balance: int, drift: int) -> None:
        if drift != 0:
            streak = self._leak_streak.get(component, 0) + 1
            self._leak_streak[component] = streak
            if streak >= self.leak_grace \
                    and component not in self._leak_flagged:
                self._leak_flagged.add(component)
                suspect = {"component": component, "drift": int(drift),
                           "balance": int(balance)}
                self.leak_suspects.append(suspect)
                _journal.record("mem_leak_suspect", **suspect)
                if _registry.enabled():
                    _led_m()["leaks"].labels(component=component).inc()
        else:
            self._leak_streak[component] = 0
            self._leak_flagged.discard(component)

    def snapshot(self) -> dict:
        """The ``/memory`` payload: per-component bytes, per-pool page
        classes / tenants / fragmentation / event balance, high-water
        marks, and the watchdog's suspects — with the exactness
        invariant ASSERTED (attributed bytes == pool array bytes).
        Deterministic: sorted iteration, integer bytes, no wall clock —
        same-seed replays snapshot bitwise-identically."""
        self.snapshots += 1
        kv_pools: dict = {}
        class_bytes = {c: 0 for c in KV_PAGE_CLASSES}
        kv_total = 0
        frag_worst = 0.0
        for idx, pool in self._live_pools():
            page_bytes = _pool_page_bytes(pool)
            classes = pool.page_classes()
            array_bytes = pool.nbytes
            attributed = sum(classes.values()) * page_bytes
            assert attributed == pool.num_pages * page_bytes \
                == array_bytes, \
                (f"ledger attribution leak on pool {idx}: "
                 f"{sum(classes.values())} classed pages x {page_bytes} "
                 f"= {attributed} bytes != pool arrays' {array_bytes}")
            ev = self._kv_events[idx]
            balance = ev["allocs"] - ev["frees"]
            drift = balance - pool.live_sequences
            frag = _fragmentation(pool._free)
            frag_worst = max(frag_worst, frag)
            cap = pool.num_pages - 1
            used = cap - pool.free_pages
            kv_pools[str(idx)] = {
                "page_bytes": int(page_bytes),
                "bytes_total": int(array_bytes),
                "pages_by_class": {c: int(classes[c])
                                   for c in KV_PAGE_CLASSES},
                "bytes_by_class": {c: int(classes[c] * page_bytes)
                                   for c in KV_PAGE_CLASSES},
                "pages_by_tenant": pool.pages_by_tenant(),
                "used_fraction": used / cap if cap else 0.0,
                "peak_used_pages": int(ev["peak_used_pages"]),
                "peak_shared_pages": int(ev["peak_shared_pages"]),
                "peak_used_fraction": (ev["peak_used_pages"] / cap
                                       if cap else 0.0),
                "fragmentation": frag,
                "allocs": int(ev["allocs"]),
                "frees": int(ev["frees"]),
                "balance": int(balance),
                "live_sequences": int(pool.live_sequences),
                "drift": int(drift),
            }
            for c in KV_PAGE_CLASSES:
                class_bytes[c] += int(classes[c] * page_bytes)
            kv_total += array_bytes
            self._watchdog(f"kv_pool:{idx}", balance, drift)
        components = {
            "compile": sum(e["executable_bytes"] + e["temp_bytes"]
                           for e in self._compile.values()),
            "embed_hbm": sum(e["bytes"] for e in self._embed.values()),
            "kv_pool": int(kv_total),
            "train_optimizer": int(self._train["optimizer_bytes"]),
            "train_weights": int(self._train["weights_bytes"]),
        }
        total = sum(components.values())
        for comp, b in list(components.items()) + [("total", total)]:
            if b > self._hwm.get(comp, 0):
                self._hwm[comp] = int(b)
        pressure = self.memory_pressure()
        if _registry.enabled():
            m = _led_m()
            for comp in sorted(components):
                m["component"].labels(component=comp).set(
                    float(components[comp]))
            for comp in sorted(self._hwm):
                m["hwm"].labels(component=comp).set(
                    float(self._hwm[comp]))
            for c in KV_PAGE_CLASSES:
                m["kv_class"].labels(klass=c).set(float(class_bytes[c]))
            m["frag"].set(frag_worst)
            m["total"].set(float(total))
            m["pressure"].set(pressure)
            for idx, _pool in self._live_pools():
                ev = self._kv_events[idx]
                comp = f"kv_pool:{idx}"
                m["allocs"].labels(component=comp).set_total(
                    float(ev["allocs"]))
                m["frees"].labels(component=comp).set_total(
                    float(ev["frees"]))
        return {
            "installed": True,
            "snapshots": int(self.snapshots),
            "total_bytes": int(total),
            "components": {c: int(components[c])
                           for c in sorted(components)},
            "hwm_bytes": {c: int(self._hwm[c])
                          for c in sorted(self._hwm)},
            "kv_class_bytes": {c: int(class_bytes[c])
                               for c in KV_PAGE_CLASSES},
            "kv_pools": kv_pools,
            "embed": {t: dict(self._embed[t])
                      for t in sorted(self._embed)},
            "compile_sites": {s: dict(self._compile[s])
                              for s in sorted(self._compile)},
            "train": {k: int(v) for k, v in sorted(self._train.items())},
            "fragmentation": frag_worst,
            "pressure": pressure,
            "leak_suspects": [dict(s) for s in self.leak_suspects],
        }

    def reconcile(self, predicted_bytes: float, *,
                  component: str = "kv_pool", band: Optional[float] = None,
                  model_sig: str = "") -> dict:
        """Reconcile a planner/estimator byte prediction against the
        LEDGER-measured bytes of ``component`` — the same closing move
        (gauge + ``mem_estimate_drift`` outside the band + a calibration
        ``mem`` record) PR 12's :func:`hetu_tpu.mem.estimator.reconcile`
        runs against XLA's ``memory_analysis``, with the ledger as the
        measured side."""
        from hetu_tpu.mem import estimator as _estimator
        snap = self.snapshot()
        measured = snap["components"].get(component, 0)
        kw: dict = {"model_sig": model_sig}
        if band is not None:
            kw["band"] = float(band)
        out = _estimator.reconcile(float(predicted_bytes),
                                   float(measured), **kw)
        out["component"] = component
        out["measured_bytes"] = int(measured)
        return out


# --------------------------------------------------- process-wide seams

_active: Optional[MemoryLedger] = None


def get_ledger() -> Optional[MemoryLedger]:
    return _active


def install_ledger(ledger: Optional[MemoryLedger]
                   ) -> Optional[MemoryLedger]:
    """Install ``ledger`` process-wide (None uninstalls): the sink the
    instrumented seams post to and the object ``/memory`` serves."""
    global _active
    _active = ledger
    return ledger


@contextlib.contextmanager
def use(ledger: MemoryLedger):
    """Install for the block, restore the previous ledger on exit."""
    global _active
    prev = _active
    _active = ledger
    try:
        yield ledger
    finally:
        _active = prev


def note_kv(pool, *, alloc: int = 0, free: int = 0) -> None:
    """The KV-pool mutator seam (alloc/free/retain/release/CoW/defrag/
    export-hold call sites): one module-global load and a branch when no
    ledger is installed or telemetry is disabled."""
    led = _active
    if led is None or not _registry.enabled():
        return
    led.note_kv(pool, alloc=alloc, free=free)


def note_embed(embedding) -> None:
    """The TieredEmbedding.stage seam: resident-row bytes of the HBM
    tier (rows × dim × 4 — the f32 device cache).  Residency is only
    computed past the one-load-and-branch guard."""
    led = _active
    if led is None or not _registry.enabled():
        return
    h = embedding._handle
    rows = int((h.id_of >= 0).sum())
    led.note_embed(embedding.name, rows, rows * int(embedding.dim) * 4)


def note_compile(site: str, memory: dict) -> None:
    """The InstrumentedJit._compile seam: one program's
    ``memory_analysis`` bytes."""
    led = _active
    if led is None or not _registry.enabled():
        return
    if memory:
        led.note_compile(site, memory)


def note_train_state(state) -> None:
    """The Trainer seam (init + state rebind): weights/optimizer bytes
    from the state pytree — walked only past the guard."""
    led = _active
    if led is None or not _registry.enabled():
        return
    led.note_train_state(state)
