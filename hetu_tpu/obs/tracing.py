"""Cross-layer structured tracing spans.

The reference's timer subexecutor attributes wall time to graph nodes
inside one executor; what it cannot do is follow one *training step*
across runtime layers — driver → ``Trainer.step`` → PS RPCs → checkpoint
writes.  These spans do: each carries ``trace_id``/``span_id``/
``parent_id``, parentage propagates through a ``contextvars`` context
variable (a PS RPC issued inside a step span becomes its child; worker
threads that should inherit parentage run under
``contextvars.copy_context()``), and the
collected spans export as Chrome trace-event JSON.

Recording is opt-in, two ways: ``tracer.start()`` / ``with
tracer.collect():`` by hand, or a live JAX profiler session
(``jax.profiler.start_trace`` .. ``stop_trace``) — attaching the profiler
is the switch.  While a session is live each span also enters a
``jax.profiler.TraceAnnotation`` of its own name (its parent's name as
metadata), so it lands in the ``.xplane.pb`` on its host thread's line, on
the clock of the device's ``XLA Ops`` events: the one way onto a device
timeline.  When off — the production default — ``span()`` is one flag
load, one ``is_enabled()`` (an atomic load) and a shared no-op context.
The clock and the id sequence are injectable/deterministic so tests can
assert exact span trees and timings.
"""

from __future__ import annotations

import contextlib
import contextvars
import gzip
import itertools
import json
import threading
import time
from typing import Callable, Optional

from jax.profiler import TraceAnnotation as _Annotation

from hetu_tpu.obs import registry as _registry

__all__ = ["Span", "Tracer", "get_tracer", "span", "current_span",
           "span_pid", "spans_to_chrome_events"]

# Chrome trace-event pid reserved for runtime spans: far away from XProf's
# device/host pids so a merged trace shows them as their own process row.
# In a stitched FLEET trace (obs.fleet) each worker's spans render at
# pid = SPAN_PID + rank — the same offset scheme generalized, so worker 3
# overrunning everyone else's step span is one glance at four rows.
SPAN_PID = 88888


def span_pid(worker=None) -> int:
    """Chrome-trace pid for one process's runtime spans: the reserved
    base for a standalone process, ``SPAN_PID + rank`` for gang worker
    ``rank`` in a stitched fleet timeline."""
    return SPAN_PID if worker is None else SPAN_PID + int(worker)


def spans_to_chrome_events(span_dicts, *, worker=None,
                           label: Optional[str] = None) -> list:
    """Serialized span dicts (see :meth:`Tracer.span_dicts`) → complete
    (``ph: X``) Chrome trace events plus a process_name metadata event.
    Lives here — not in the aggregator — so the pid-offset scheme has
    one owner; ``obs.fleet`` calls this per worker and concatenates."""
    pid = span_pid(worker)
    if label is None:
        label = ("hetu-tpu runtime spans" if worker is None
                 else f"hetu-tpu runtime spans (worker {worker})")
    events = [{"ph": "M", "name": "process_name", "pid": pid,
               "args": {"name": label}}]
    for sp in span_dicts:
        start = sp["start"]
        end = sp.get("end")
        events.append({
            "ph": "X", "name": sp["name"], "pid": pid,
            "tid": 1 if sp.get("parent_id") is None else 2,
            "ts": start * 1e6,
            "dur": ((end - start) if end is not None else 0.0) * 1e6,
            "args": {"trace_id": sp["trace_id"], "span_id": sp["span_id"],
                     "parent_id": sp.get("parent_id"),
                     **{k: str(v) for k, v in sp.get("attrs", {}).items()}},
        })
    return events

_current: contextvars.ContextVar = contextvars.ContextVar(
    "hetu_obs_span", default=None)
# what ``span()`` hands out while nothing records: enters to None, and is
# shared, so the off path builds no object
_NO_SPAN = contextlib.nullcontext()


class Span:
    """One timed operation.  ``end()`` is idempotent; attributes set
    after creation ride along into the Chrome ``args``."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "start",
                 "end_time", "attrs", "_tracer", "_token")

    def __init__(self, tracer: "Tracer", name: str, trace_id: str,
                 span_id: str, parent_id: Optional[str], start: float,
                 attrs: dict):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.start = start
        self.end_time: Optional[float] = None
        self.attrs = attrs
        self._tracer = tracer
        self._token = None

    @property
    def duration(self) -> Optional[float]:
        if self.end_time is None:
            return None
        return self.end_time - self.start

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def end(self) -> None:
        if self.end_time is None:
            self.end_time = self._tracer.clock()
            self._tracer._record(self)


class Tracer:
    """Span collector with deterministic ids and an injectable clock.

    ``clock`` returns seconds (monotonic by convention); ids are drawn
    from a plain counter, so two identical runs produce identical span
    trees — the property the chaos suite asserts.  Thread-safe: spans
    started on worker threads (the shard router's parallel pulls) land in
    the same buffer, parented by whatever span was current when the
    thread's context was copied.
    """

    def __init__(self, clock: Optional[Callable[[], float]] = None):
        self.clock = clock if clock is not None else time.perf_counter
        self._started = False
        self._spans: list = []
        self._external: list = []   # pre-built span dicts (reqtrace folds)
        self._lock = threading.Lock()
        self._ids = itertools.count(1)

    # -- lifecycle ----------------------------------------------------------

    @property
    def recording(self) -> bool:
        """Started by hand, or a JAX profiler session is live."""
        return self._started or _Annotation.is_enabled()

    def start(self) -> None:
        self._started = True

    def stop(self) -> None:
        self._started = False

    def reset(self) -> None:
        with self._lock:
            self._spans = []
            self._external = []
        self._ids = itertools.count(1)

    @contextlib.contextmanager
    def collect(self):
        """Record spans for the block; yields the tracer."""
        self.start()
        try:
            yield self
        finally:
            self.stop()

    # -- span API -----------------------------------------------------------

    def span(self, name: str, **attrs):
        """Open a child span of the context-current span.  When the tracer
        is not recording (or telemetry is disabled) this is a no-op that
        yields None — the production fast path.  Under a live profiler
        session the span is also a ``TraceAnnotation`` in the profile."""
        profiled = _Annotation.is_enabled()
        if not ((self._started or profiled) and _registry.enabled()):
            return _NO_SPAN
        return self._open(name, attrs, profiled)

    @contextlib.contextmanager
    def _open(self, name: str, attrs: dict, profiled: bool):
        parent = _current.get()
        sid = f"{next(self._ids):08x}"
        if parent is not None:
            trace_id, parent_id = parent.trace_id, parent.span_id
        else:
            trace_id, parent_id = f"t{sid}", None
        if not profiled:
            note = contextlib.nullcontext()
        elif parent is None:
            note = _Annotation(name)
        else:
            note = _Annotation(name, parent=parent.name)
        sp = Span(self, name, trace_id, sid, parent_id, self.clock(), attrs)
        token = _current.set(sp)
        try:
            with note:
                yield sp
        finally:
            _current.reset(token)
            # a span that outlives the recording it began under would be
            # kept with some of its children missing: it is dropped
            if self.recording:
                sp.end()

    def _record(self, sp: Span) -> None:
        with self._lock:
            self._spans.append(sp)

    def record_external(self, span_dicts) -> int:
        """Fold externally-built, already-complete span dicts (the
        :meth:`span_dicts` schema) into this tracer — the seam the
        serving request timelines (``obs.reqtrace``) use so finished
        request traces ride the fleet snapshot and every export path
        exactly like runtime spans.  Only records while the tracer is
        recording (and telemetry enabled); returns the number folded."""
        if not (self.recording and _registry.enabled()):
            return 0
        folded = [dict(sp) for sp in span_dicts]
        with self._lock:
            self._external.extend(folded)
        return len(folded)

    @property
    def spans(self) -> list:
        """Finished spans in end order."""
        with self._lock:
            return list(self._spans)

    # -- export -------------------------------------------------------------

    def span_dicts(self) -> list:
        """Finished spans as plain JSON-serializable dicts — the form a
        fleet telemetry snapshot publishes so rank 0 can stitch every
        worker's timeline (:func:`spans_to_chrome_events`)."""
        own = [{"name": sp.name, "trace_id": sp.trace_id,
                "span_id": sp.span_id, "parent_id": sp.parent_id,
                "start": sp.start, "end": sp.end_time,
                "attrs": {k: str(v) for k, v in sp.attrs.items()}}
               for sp in self.spans]
        with self._lock:
            return own + list(self._external)

    def to_chrome_events(self, worker=None) -> list:
        """Complete (``ph: X``) trace events plus a process_name metadata
        event, timestamps in microseconds — the traceEvents schema XProf
        emits, so the two merge by list concatenation.  ``worker`` offsets
        the pid (``SPAN_PID + rank``) for stitched fleet timelines."""
        return spans_to_chrome_events(self.span_dicts(), worker=worker)

    def export_chrome(self, path: str) -> str:
        """Write ``{"traceEvents": [...]}`` (gzipped when the path ends in
        ``.gz``); loadable by chrome://tracing / Perfetto."""
        payload = json.dumps({"traceEvents": self.to_chrome_events()})
        if path.endswith(".gz"):
            with gzip.open(path, "wt") as f:
                f.write(payload)
        else:
            with open(path, "w") as f:
                f.write(payload)
        return path


_default = Tracer()


def get_tracer() -> Tracer:
    return _default


def span(name: str, **attrs):
    """Module-level shorthand: a span on the default tracer."""
    return _default.span(name, **attrs)


def current_span() -> Optional[Span]:
    return _current.get()
