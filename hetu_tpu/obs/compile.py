"""XLA compilation telemetry: count every compile, attribute its cost.

A recompile on the serving hot path is the difference between a 5 ms
decode step and a multi-second stall — and until now it was invisible:
the jit caches were XLA's own, so "the engine got slow" could not be
told apart from "the engine is recompiling every step".  This module
makes the compile boundary an instrumented seam:

- :class:`InstrumentedJit` wraps an already-``jax.jit``-ed callable and
  **owns the program cache**: per distinct shape signature it lowers
  and compiles ONCE through the AOT path (``fn.lower(...).compile()``)
  and dispatches the cached executable thereafter.  Because the cache
  is ours, the compile count is exact by construction — the seam the
  acceptance test asserts ``hetu_compile_total`` against — and each
  program's compile wall time and ``memory_analysis()`` byte sizes are
  recorded per shape signature.
- :func:`watch` is the light-touch form for seams where the AOT path is
  too invasive (``Trainer.step`` under donation/sharding strategies):
  same signature tracking and counting, but the wrapped jit keeps
  dispatching (the first call per signature is timed as the compile,
  execution included).  With telemetry disabled the wrapper is one
  global load + branch — the ``Trainer.step`` overhead contract.
- every compile is journaled (kind ``compile``; kind ``recompile`` from
  the second program per site onward, carrying the shape DELTA against
  the previous signature — the "what changed" a 3 am page needs).  AOT
  events (``aot: true`` — pure lower+compile wall, no execution) bill
  the goodput ``compile`` bucket via the same journal-ingest path as
  ``checkpoint_saved``/``retune``; watch-mode events do NOT bill — their
  first-call wall includes the step's execution, which the step's own
  meter already bills as ``useful`` (never double-bill a second).
- a process-wide **recompile-storm** detector keeps a rolling window of
  distinct-shape compiles; ``hetu_compile_recent`` gauges the count and
  ``hetu_compile_storm`` flips to 1 while it exceeds the threshold
  (``HETU_TPU_COMPILE_STORM_N`` within ``HETU_TPU_COMPILE_STORM_S``) —
  the classic unbucketed-prompt-length failure shows up as a gauge, not
  a bench round.

Instrumented sites: the ``ServingEngine`` step functions
(``serve.prefill_step`` / ``serve.paged_decode`` / ``serve.sample``,
AOT), ``Trainer`` (``train.step`` / ``train.eval`` / ``train.scan``,
watch), and the autotune sweeps (each measured candidate reports its
compiles under ``tune.<kernel>`` via the sweep's journal record).

Signatures key on what jit's own cache keys on for the shapes that
matter here: the pytree structure plus each array leaf's
``(shape, dtype)`` (non-array leaves key by type — a traced Python
scalar's VALUE does not retrigger compilation, its type does).
Tracer-stage calls (an instrumented function inlined inside an outer
trace, e.g. ``scan_steps``) pass straight through uncounted: the outer
program owns that compile.
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
                "distinct shape signature; the instrumented cache IS the "
                "program cache, so this is exact)", ("site",)),
            "seconds": reg.histogram(
                "hetu_compile_seconds",
                "compile wall time per program (lower+compile on the AOT "
                "sites; first-call wall on watch-only sites)"),
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


def _is_tracer_call(args: tuple, kwargs: dict) -> bool:
    import jax
    return any(isinstance(x, jax.core.Tracer)
               for x in jax.tree_util.tree_leaves((args, kwargs)))


def _classify_call(args: tuple, kwargs: dict):
    """One flatten serving both per-call checks: returns
    ``(is_tracer_call, signature)`` — a large model's parameter tree is
    walked once per dispatch, not twice (the hot-path contract)."""
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

    __slots__ = ("sig", "compiled", "compile_s", "memory", "calls")

    def __init__(self, sig, compiled, compile_s, memory):
        self.sig = sig
        self.compiled = compiled      # None on watch-only sites
        self.compile_s = compile_s
        self.memory = memory          # {kind: bytes} or {}
        self.calls = 0


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
    """The compile-counting seam around one jitted callable.

    ``aot=True`` (serving): own the program cache — lower+compile once
    per signature, dispatch the cached executable after.  ``aot=False``
    (training): the wrapped jit keeps dispatching; we only track
    signatures and time the first call per signature.  Attribute access
    falls through to the wrapped function (``.lower`` for the profiler,
    etc.).  If the AOT path is unavailable for a call (an argument the
    lowering rejects), the instance degrades to watch mode permanently
    and keeps counting."""

    def __init__(self, fn: Callable, *, site: str, aot: bool = True,
                 clock: Callable[[], float] = time.perf_counter):
        self._fn = fn
        self.site = str(site)
        self.aot = bool(aot)
        self.clock = clock
        self.programs: dict = {}      # sig -> _Program
        self._last_sig = None
        self._lock = threading.RLock()

    # the watch-mode contract: with telemetry off this is the wrapped
    # call plus one global load + branch (AOT keeps its own cache so the
    # executable identity stays stable across an enable/disable flip)
    def __call__(self, *args, **kwargs):
        if not self.aot and not _registry.enabled():
            return self._fn(*args, **kwargs)
        is_tracer, sig = _classify_call(args, kwargs)
        if is_tracer:
            # inlined inside an outer trace (scan_steps, a strategy's
            # pjit): the OUTER program owns this compile
            return self._fn(*args, **kwargs)
        with self._lock:
            prog = self.programs.get(sig)
        if prog is not None:
            prog.calls += 1
            if prog.compiled is not None:
                return prog.compiled(*args, **kwargs)
            return self._fn(*args, **kwargs)
        return self._compile(sig, args, kwargs)

    def _compile(self, sig, args, kwargs):
        # while the tracer records, the compile itself becomes a
        # ``compile.xla`` span — the namespace the span lint enforces —
        # so a recompile stall is visible on the stitched timeline too
        tracer = _tracing.get_tracer()
        compiled = None
        t0 = self.clock()
        if self.aot:
            try:
                with tracer.span("compile.xla", site=self.site, aot=True):
                    lowered = self._fn.lower(*args, **kwargs)
                    compiled = lowered.compile()
            except Exception:
                # lowering rejected the call (unhashable static, version
                # skew): degrade to watch mode, never lose the count
                self.aot = False
                compiled = None
        if compiled is not None:
            compile_s = self.clock() - t0
            out = compiled(*args, **kwargs)
        else:
            with tracer.span("compile.xla", site=self.site, aot=False):
                out = self._fn(*args, **kwargs)
            compile_s = self.clock() - t0   # first-call wall, exec incl.
        memory = _memory_analysis(compiled) if compiled is not None else {}
        with self._lock:
            prog = _Program(sig, compiled, compile_s, memory)
            prog.calls = 1
            self.programs[sig] = prog
            prev, self._last_sig = self._last_sig, sig
            n = len(self.programs)
        if _registry.enabled():
            m = _compile_m()
            m["compiles"].labels(site=self.site).inc()
            m["seconds"].observe(compile_s)
            for kind, nbytes in memory.items():
                m["memory"].labels(site=self.site, kind=kind).set(nbytes)
        # memory-ledger seam: this program's executable/temp bytes join
        # the per-site compile attribution
        _memledger.note_compile(self.site, memory)
        # aot: the duration is pure lower+compile wall (goodput bills
        # it); watch-mode durations include the first call's execution,
        # which the step's own meter bills as useful — ingest skips them
        _journal.record(
            "recompile" if n > 1 else "compile",
            site=self.site, programs=n, sig=signature_str(sig),
            duration_s=round(compile_s, 6), aot=compiled is not None,
            **({"delta": _sig_delta(prev, sig)} if n > 1 else {}))
        get_storm().note(self.site)
        return out

    # -- introspection ------------------------------------------------------

    @property
    def compile_count(self) -> int:
        """Programs compiled at this site — the counting seam."""
        return len(self.programs)

    def report(self) -> dict:
        """Per-program compile cost keyed by shape signature."""
        with self._lock:
            return {signature_str(p.sig): {
                        "compile_s": p.compile_s, "calls": p.calls,
                        "memory_bytes": dict(p.memory),
                        "aot": p.compiled is not None}
                    for p in self.programs.values()}

    def __getattr__(self, name):
        return getattr(self._fn, name)


def instrument(fn: Callable, *, site: str) -> InstrumentedJit:
    """AOT-counting seam (serving step functions)."""
    return InstrumentedJit(fn, site=site, aot=True)


def watch(fn: Callable, *, site: str) -> InstrumentedJit:
    """Count-only seam (training steps — donation and sharding
    strategies keep dispatching through the original jit)."""
    return InstrumentedJit(fn, site=site, aot=False)


def compile_report(*watchers: InstrumentedJit) -> dict:
    """One JSON-able report over several sites (``/compile``-style
    payloads; the engine's ``stats()`` embeds it)."""
    return {w.site: {"programs": w.compile_count, **{"by_signature":
            w.report()}} for w in watchers}
