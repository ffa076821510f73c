"""XLA compilation telemetry: count every compile, attribute its cost.

A recompile on the serving hot path is the difference between a 5 ms
decode step and a multi-second stall — and until now it was invisible:
the jit caches were XLA's own, so "the engine got slow" could not be
told apart from "the engine is recompiling every step".  This module
makes the compile boundary an instrumented seam:

- :class:`InstrumentedJit` wraps an already-``jax.jit``-ed callable and
  dispatches every call through it, so jit's own C++ cache checks the
  call's signature: a call that cache serves costs the seam two reads of
  the cache's size and walks no argument tree.  A call after which that
  size has changed went through jit's Python path (a trace and a
  compile, or a miss that found its program in jit's other caches);
  only such a call is keyed here, by the signature below, and a
  signature not seen before is a compile: counted
  (``hetu_compile_total``, exact per site), timed (the call's wall) and,
  on the serving sites, sized by the ``memory_analysis()`` of the
  executable jit holds for it (lowered again on the same arguments: a
  cache hit, no second compile).
  ``hetu_compile_slow_calls_total`` counts the calls that took that path,
  so in a warmed program it stands at the site's compiles.
- :func:`watch` is the same seam for ``Trainer.step`` (donation and
  sharding strategies dispatch as they are): no ``memory_analysis()``,
  and with telemetry disabled the wrapper is one global load + branch —
  the ``Trainer.step`` overhead contract.
- every compile is journaled (kind ``compile``; kind ``recompile`` from
  the second program per site onward, carrying the shape DELTA against
  the previous signature — the "what changed" a 3 am page needs).  Events
  of the serving sites (``aot: true``: the first call's wall, which
  holds the trace and the compile and queues the execution without
  waiting for it) bill the goodput ``compile`` bucket via the same
  journal-ingest path as ``checkpoint_saved``/``retune``; watch-mode
  events do NOT bill — the step's own meter already bills that call as
  ``useful`` (never double-bill a second).
- a process-wide **recompile-storm** detector keeps a rolling window of
  distinct-shape compiles; ``hetu_compile_recent`` gauges the count and
  ``hetu_compile_storm`` flips to 1 while it exceeds the threshold
  (``HETU_TPU_COMPILE_STORM_N`` within ``HETU_TPU_COMPILE_STORM_S``) —
  the classic unbucketed-prompt-length failure shows up as a gauge, not
  a bench round.

Instrumented sites: the ``ServingEngine`` step functions
(``serve.prefill_step`` / ``serve.paged_decode`` / ``serve.sample``),
``Trainer`` (``train.step`` / ``train.eval`` / ``train.scan``, watch),
and the autotune sweeps (each measured candidate reports its compiles
under ``tune.<kernel>`` via the sweep's journal record).

Signatures key on what jit's own cache keys on for the shapes that
matter here: the pytree structure plus each array leaf's
``(shape, dtype)`` (non-array leaves key by type — a traced Python
scalar's VALUE does not retrigger compilation, its type does).
Tracer-stage calls (an instrumented function inlined inside an outer
trace, e.g. ``scan_steps``) put nothing in jit's C++ cache and pass
straight through uncounted: the outer program owns that compile.  A
callable with no such cache (not a ``jax.jit``), or a jit whose C++
cache holds nothing (a program it keeps off its fast path), takes the
Python path on every call and keeps counting.
"""

from __future__ import annotations

import collections
import os
import threading
import time
from typing import Callable, Optional

from hetu_tpu.obs import journal as _journal
from hetu_tpu.obs import memledger as _memledger
from hetu_tpu.obs import registry as _registry
from hetu_tpu.obs import tracing as _tracing

__all__ = ["InstrumentedJit", "watch", "instrument", "shape_signature",
           "signature_str", "StormDetector", "get_storm", "configure_storm",
           "compile_report"]

ENV_STORM_N = "HETU_TPU_COMPILE_STORM_N"
ENV_STORM_S = "HETU_TPU_COMPILE_STORM_S"

_compile_metrics = None


def _compile_m() -> dict:
    global _compile_metrics
    if _compile_metrics is None:
        reg = _registry.get_registry()
        _compile_metrics = {
            "compiles": reg.counter(
                "hetu_compile_total",
                "XLA program compilations by instrumented site (one per "
                "distinct shape signature, keyed on every call that jit's "
                "C++ cache did not serve, so this is exact)", ("site",)),
            "slow_calls": reg.counter(
                "hetu_compile_slow_calls_total",
                "calls at an instrumented site that took the Python path "
                "(a trace, a compile, a miss of jit's C++ cache or a "
                "degraded site); in a warmed program it stands at the "
                "site's compiles", ("site",)),
            "seconds": reg.histogram(
                "hetu_compile_seconds",
                "compile wall time per program (the first call's wall: "
                "trace, compile and the dispatch)"),
            "memory": reg.gauge(
                "hetu_compile_memory_bytes",
                "memory_analysis() of the most recently compiled program "
                "per site (temp/argument/output/alias/generated_code)",
                ("site", "kind")),
            "recent": reg.gauge(
                "hetu_compile_recent",
                "distinct-shape compiles inside the rolling storm window "
                "(all sites)"),
            "storm": reg.gauge(
                "hetu_compile_storm",
                "1 while distinct-shape compiles in the window exceed the "
                "storm threshold, else 0 (see HETU_TPU_COMPILE_STORM_*)"),
        }
    return _compile_metrics


# ------------------------------------------------------------- signatures

def _sig_from_leaves(treedef, leaves) -> tuple:
    sig = []
    for x in leaves:
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is not None and dtype is not None:
            sig.append((tuple(shape), str(dtype)))
        else:
            sig.append(("py", type(x).__name__))
    return (treedef, tuple(sig))


def shape_signature(args: tuple, kwargs: dict) -> tuple:
    """Hashable key over the call's avals: pytree structure + per-leaf
    ``(shape, dtype)`` for arrays, type name otherwise.  Matches what
    retriggers an XLA compile for shape-polymorphic callers (value
    changes of traced scalars do not; shape/dtype/structure changes
    do)."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    return _sig_from_leaves(treedef, leaves)


def signature_str(sig: tuple) -> str:
    """Human/journal form: ``f32[8,16] i32[4] py:int ...``."""
    parts = []
    for ent in sig[1]:
        if ent[0] == "py":
            parts.append(f"py:{ent[1]}")
        else:
            shape, dtype = ent
            parts.append(f"{dtype}[{','.join(str(d) for d in shape)}]")
    return " ".join(parts)


def _sig_delta(old: tuple, new: tuple) -> str:
    """What changed between two signatures — the triggering shape delta
    journaled on a recompile."""
    if old is None:
        return "first compile"
    if old[0] != new[0]:
        return "pytree structure changed"
    diffs = []
    for i, (a, b) in enumerate(zip(old[1], new[1])):
        if a != b:
            diffs.append(f"leaf {i}: {_leaf_str(a)} -> {_leaf_str(b)}")
    return "; ".join(diffs) if diffs else "unchanged signature"


def _leaf_str(ent: tuple) -> str:
    if ent[0] == "py":
        return f"py:{ent[1]}"
    shape, dtype = ent
    return f"{dtype}[{','.join(str(d) for d in shape)}]"


def _classify_call(args: tuple, kwargs: dict):
    """One flatten serving both checks of a call that took the Python
    path: returns ``(is_tracer_call, signature)``."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    if any(isinstance(x, jax.core.Tracer) for x in leaves):
        return True, None
    return False, _sig_from_leaves(treedef, leaves)


# ----------------------------------------------------------- storm window

class StormDetector:
    """Process-wide rolling window of compile events.  ``note()`` is
    called once per distinct-shape compile (any site); while the window
    holds more than ``threshold`` compiles, ``hetu_compile_storm`` reads
    1 and a ``compile_storm`` journal event marks each crossing."""

    def __init__(self, *, threshold: int = 8, window_s: float = 60.0,
                 clock: Callable[[], float] = time.monotonic):
        self.threshold = int(threshold)
        self.window_s = float(window_s)
        self.clock = clock
        self._events: collections.deque = collections.deque()
        self._storming = False
        self._lock = threading.Lock()

    @classmethod
    def from_env(cls) -> "StormDetector":
        return cls(threshold=int(os.environ.get(ENV_STORM_N, "8")),
                   window_s=float(os.environ.get(ENV_STORM_S, "60")))

    def note(self, site: str) -> int:
        """Record one compile; returns the current window count."""
        now = self.clock()
        with self._lock:
            self._events.append(now)
            self._trim(now)
            n = len(self._events)
            storming = n > self.threshold
            if storming and not self._storming:
                _journal.record("compile_storm", site=site, recent=n,
                                threshold=self.threshold,
                                window_s=self.window_s)
            self._storming = storming
            if _registry.enabled():
                m = _compile_m()
                m["recent"].set(n)
                m["storm"].set(1.0 if storming else 0.0)
            return n

    def recent(self) -> int:
        with self._lock:
            self._trim(self.clock())
            return len(self._events)

    def _trim(self, now: float) -> None:
        while self._events and now - self._events[0] > self.window_s:
            self._events.popleft()


_storm: Optional[StormDetector] = None
_storm_lock = threading.Lock()


def get_storm() -> StormDetector:
    global _storm
    if _storm is None:
        with _storm_lock:
            if _storm is None:
                _storm = StormDetector.from_env()
    return _storm


def configure_storm(detector: Optional[StormDetector]) -> StormDetector:
    """Install a detector (tests inject clock/threshold); None resets to
    the environment-configured default on next use."""
    global _storm
    _storm = detector
    return get_storm()


# -------------------------------------------------------------- the seam

class _Program:
    """One compiled program at an instrumented site."""

    __slots__ = ("sig", "compile_s", "memory", "aot")

    def __init__(self, sig, compile_s, memory, aot):
        self.sig = sig
        self.compile_s = compile_s
        self.memory = memory          # {kind: bytes} or {}
        self.aot = aot                # memory taken from its executable


def _memory_analysis(compiled) -> dict:
    """``memory_analysis()`` byte sizes by kind.  ``alias`` is the bytes
    of donated inputs that the program writes in place (the serving
    steps' K/V pool: argument and output are one buffer).  An executable
    deserialized from a warm persistent compilation cache may report 0
    there (``exec/profiler.py`` ``audit_donation``), so a check of the
    aliasing compiles fresh."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for kind in ("temp", "argument", "output", "alias", "generated_code"):
        v = getattr(ma, f"{kind}_size_in_bytes", None)
        if v is not None:
            out[kind] = int(v)
    return out


class InstrumentedJit:
    """The compile-counting seam around one jitted callable (module
    docstring).  Every call goes to the wrapped function; a call after
    which jit's C++ cache is empty or has changed size is a slow call,
    keyed by its signature here.  ``aot=True`` (serving): a new
    program's ``memory_analysis()`` comes from the executable jit holds,
    and its first call's wall bills goodput; ``aot=False`` (training):
    signature and first-call wall alone, and a pass-through while
    telemetry is off.  Attribute access falls through to the wrapped
    function (``.lower`` for the profiler, etc.).  If the executable
    cannot be had (a callable with no ``.lower``, an argument the
    lowering rejects), the instance degrades to watch mode permanently
    and keeps counting."""

    def __init__(self, fn: Callable, *, site: str, aot: bool = True,
                 clock: Callable[[], float] = time.perf_counter):
        self._fn = fn
        self.site = str(site)
        self.aot = bool(aot)
        self.clock = clock
        self.programs: dict = {}      # sig -> _Program
        self.slow_calls = 0
        self._last_sig = None
        # the size of jit's own C++ cache: a call that cache served
        # leaves it as it was, so reading it twice is the whole per-call
        # check (None: not a jit, every call is keyed)
        self._cache_size = getattr(fn, "_cache_size", None)
        self._lock = threading.RLock()

    # the watch-mode contract: with telemetry off this is the wrapped
    # call plus one global load + branch
    def __call__(self, *args, **kwargs):
        if not self.aot and not _registry.enabled():
            return self._fn(*args, **kwargs)
        size = self._cache_size
        n = size() if size is not None else 0
        t0 = self.clock()
        out = self._fn(*args, **kwargs)
        if not n or size() != n:
            self._slow(args, kwargs, self.clock() - t0)
        return out

    def _slow(self, args, kwargs, wall_s):
        """A call that took the Python path: key it, and record a program
        the site has not had.  Donated arguments are consumed by now;
        their shapes, dtypes and shardings are what is read."""
        is_tracer, sig = _classify_call(args, kwargs)
        if is_tracer:
            # inlined inside an outer trace (scan_steps, a strategy's
            # pjit): the OUTER program owns this compile
            return
        with self._lock:
            self.slow_calls += 1
            known = sig in self.programs
        if _registry.enabled():
            _compile_m()["slow_calls"].labels(site=self.site).inc()
        if known:
            return
        compiled = None
        # while the tracer records, the compile becomes a ``compile.xla``
        # span — the namespace the span lint enforces — so a recompile
        # stall is visible on the stitched timeline too; it ran inside
        # the call that just returned, so the span starts where that did
        with _tracing.get_tracer().span("compile.xla", site=self.site,
                                        aot=self.aot) as sp:
            if sp is not None:
                sp.start -= wall_s
            if self.aot:
                try:
                    # jit's caches hold this program's lowering and
                    # executable: no second trace or compile
                    compiled = self._fn.lower(*args, **kwargs).compile()
                except Exception:
                    # no executable to be had (not a jit, an argument the
                    # lowering rejects): degrade to watch mode, keep
                    # counting
                    self.aot = False
        memory = _memory_analysis(compiled) if compiled is not None else {}
        with self._lock:
            self.programs[sig] = _Program(sig, wall_s, memory,
                                          compiled is not None)
            prev, self._last_sig = self._last_sig, sig
            n = len(self.programs)
        if _registry.enabled():
            m = _compile_m()
            m["compiles"].labels(site=self.site).inc()
            m["seconds"].observe(wall_s)
            for kind, nbytes in memory.items():
                m["memory"].labels(site=self.site, kind=kind).set(nbytes)
        # memory-ledger seam: this program's executable/temp bytes join
        # the per-site compile attribution
        _memledger.note_compile(self.site, memory)
        # aot: the first call's wall, whose execution is queued and not
        # waited for (goodput bills it); watch-mode: the step's own meter
        # bills that call as useful — ingest skips them
        _journal.record(
            "recompile" if n > 1 else "compile",
            site=self.site, programs=n, sig=signature_str(sig),
            duration_s=round(wall_s, 6), aot=compiled is not None,
            **({"delta": _sig_delta(prev, sig)} if n > 1 else {}))
        get_storm().note(self.site)

    # -- introspection ------------------------------------------------------

    @property
    def compile_count(self) -> int:
        """Programs compiled at this site — the counting seam."""
        return len(self.programs)

    def report(self) -> dict:
        """Per-program compile cost keyed by shape signature."""
        with self._lock:
            return {signature_str(p.sig): {
                        "compile_s": p.compile_s,
                        "memory_bytes": dict(p.memory), "aot": p.aot}
                    for p in self.programs.values()}

    def __getattr__(self, name):
        return getattr(self._fn, name)


def instrument(fn: Callable, *, site: str) -> InstrumentedJit:
    """Counting seam that also sizes each program (serving step
    functions)."""
    return InstrumentedJit(fn, site=site, aot=True)


def watch(fn: Callable, *, site: str) -> InstrumentedJit:
    """Count-only seam (training steps — donation and sharding
    strategies keep dispatching through the original jit)."""
    return InstrumentedJit(fn, site=site, aot=False)


def compile_report(*watchers: InstrumentedJit) -> dict:
    """One JSON-able report over several sites (``/compile``-style
    payloads; the engine's ``stats()`` embeds it)."""
    return {w.site: {"programs": w.compile_count,
                     "slow_calls": w.slow_calls,
                     "by_signature": w.report()} for w in watchers}
