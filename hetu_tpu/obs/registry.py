"""Process-wide metrics registry: labeled counters, gauges, histograms.

The always-on telemetry layer the reference stack lacks a TPU-native
equivalent of: Hetu ships per-node timer subexecutors and an op-level
profiler (SURVEY §5.1) — offline tools — while the HET cache-enabled PS
(VLDB'22) lives or dies by hit-rate and staleness telemetry in
*production*.  This registry is the scrapeable surface for all of it:

- ``Counter`` / ``Gauge`` / ``Histogram`` families, optionally labeled;
  children are cached per label-value tuple, so the hot path is one dict
  hit plus a guarded add.
- ``snapshot()`` flattens every sample into a ``{sample_key: value}``
  dict (histograms expand into ``_bucket``/``_sum``/``_count`` samples);
  ``delta(new, old)`` subtracts monotonic samples and passes gauges
  through — the form chaos tests assert exact values on.
- ``render_prometheus()`` emits text exposition format 0.0.4 (scraped by
  the ``obs.server`` ``/metrics`` endpoint).
- ``export_jsonl()`` appends one timestamped snapshot line per call.

Disabling (``obs.disable()`` or ``HETU_OBS=0``) turns every mutator into
an immediate return — one module-global load and branch — so the
instrumented production seams (PS RPCs, ``Trainer.step``, checkpoint
writes) cost nothing measurable when telemetry is off.  Counters count
*events*, so under a seeded ``FaultPlan`` two runs produce identical
snapshots (latency histograms share bucket *counts* only when the
workload is deterministic; their ``_sum`` is wall time and is not).
"""

from __future__ import annotations

import json
import math
import os
import re
import threading
import time
from typing import Iterable, Optional, Sequence

__all__ = ["MetricsRegistry", "Counter", "Gauge", "Histogram",
           "get_registry", "enabled", "enable", "disable",
           "DEFAULT_BUCKETS"]

# Master switch.  Checked by every mutator (and by the instrumentation
# sites before they do any timing work), so disabled telemetry is one
# global load + branch on the hot paths.
_ENABLED = os.environ.get("HETU_OBS", "1") not in ("0", "false", "off")


def enabled() -> bool:
    return _ENABLED


def enable() -> None:
    global _ENABLED
    _ENABLED = True


def disable() -> None:
    global _ENABLED
    _ENABLED = False


# Latency-oriented default buckets (seconds): 100 µs .. 10 s, roughly
# log-spaced, matching the spread from a cache-hit RPC to a jit compile.
DEFAULT_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                   0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0)

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")


def _escape_label(v: str) -> str:
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _fmt(v: float) -> str:
    """Prometheus sample value: integers render bare, +Inf/NaN spelled."""
    if math.isinf(v):
        return "+Inf" if v > 0 else "-Inf"
    if math.isnan(v):
        return "NaN"
    if float(v).is_integer() and abs(v) < 2**53:
        return str(int(v))
    return repr(float(v))


def _sample_key(name: str, labelnames: Sequence[str],
                labelvalues: Sequence[str]) -> str:
    if not labelnames:
        return name
    inner = ",".join(f'{k}="{_escape_label(v)}"'
                     for k, v in zip(labelnames, labelvalues))
    return f"{name}{{{inner}}}"


class _Child:
    """One (family, label-values) time series."""

    __slots__ = ("_lock", "_labelvalues")

    def __init__(self, labelvalues: tuple):
        self._lock = threading.Lock()
        self._labelvalues = labelvalues


class Counter(_Child):
    """Monotonic counter.  ``set_total`` mirrors an external cumulative
    source (the C cache engine's hit/miss counters) without losing
    counter semantics in the exposition."""

    __slots__ = ("_value",)

    def __init__(self, labelvalues: tuple = ()):
        super().__init__(labelvalues)
        self._value = 0.0

    def inc(self, amount: float = 1.0) -> None:
        if not _ENABLED:
            return
        if amount < 0:
            raise ValueError("counters only go up; use a Gauge")
        with self._lock:
            self._value += amount

    def set_total(self, total: float) -> None:
        """Adopt an externally-maintained cumulative total (must be
        monotonic from the source's side; values below the current one
        are kept — the source restarted, the series must not go back)."""
        if not _ENABLED:
            return
        with self._lock:
            if total > self._value:
                self._value = float(total)

    @property
    def value(self) -> float:
        return self._value


class Gauge(_Child):
    __slots__ = ("_value",)

    def __init__(self, labelvalues: tuple = ()):
        super().__init__(labelvalues)
        self._value = 0.0

    def set(self, value: float) -> None:
        if not _ENABLED:
            return
        with self._lock:
            self._value = float(value)

    def inc(self, amount: float = 1.0) -> None:
        if not _ENABLED:
            return
        with self._lock:
            self._value += amount

    def dec(self, amount: float = 1.0) -> None:
        self.inc(-amount)

    @property
    def value(self) -> float:
        return self._value


class Histogram(_Child):
    """Fixed-bucket histogram (cumulative ``le`` buckets, Prometheus
    style).  Bucket bounds are frozen at family creation."""

    __slots__ = ("_bounds", "_counts", "_sum", "_count")

    def __init__(self, labelvalues: tuple = (),
                 buckets: Sequence[float] = DEFAULT_BUCKETS):
        super().__init__(labelvalues)
        self._bounds = tuple(buckets)
        self._counts = [0] * (len(self._bounds) + 1)  # +1 for +Inf
        self._sum = 0.0
        self._count = 0

    def observe(self, value: float) -> None:
        if not _ENABLED:
            return
        v = float(value)
        i = 0
        for i, b in enumerate(self._bounds):  # noqa: B007
            if v <= b:
                break
        else:
            i = len(self._bounds)
        with self._lock:
            self._counts[i] += 1
            self._sum += v
            self._count += 1

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    def cumulative(self) -> list:
        """[(le_bound, cumulative_count)] including the +Inf bucket."""
        out, acc = [], 0
        with self._lock:
            for b, c in zip(self._bounds, self._counts):
                acc += c
                out.append((b, acc))
            out.append((math.inf, acc + self._counts[-1]))
        return out

    @staticmethod
    def quantile_from_cumulative(cum_before, cum_after, q: float):
        """Quantile from the delta of two :meth:`cumulative` snapshots.
        Prometheus-style linear interpolation inside the winning bucket.
        Edge semantics are pinned down (this backs the serve ``/stats``
        SLO summary, so "whatever falls out" is not acceptable):

        - an EMPTY delta (nothing observed) returns ``nan`` — never a
          number a dashboard could mistake for a latency;
        - the +Inf bucket reports its lower edge (the largest finite
          bound, or 0.0 for a bucketless histogram) — deterministic,
          never +Inf itself;
        - a single-bucket histogram degenerates to interpolation inside
          that one bucket, its upper bound at q=1.

        The single quantile implementation in the tree: the serving
        ``/stats`` summary calls through here."""
        delta = [(le, a - b)
                 for (le, a), (_, b) in zip(cum_after, cum_before)]
        total = delta[-1][1]
        if total <= 0:
            return math.nan
        rank = q * total
        prev_le, prev_c = 0.0, 0
        for le, c in delta:
            if c >= rank:
                if le == math.inf:
                    return prev_le
                if c == prev_c:
                    return le
                return prev_le + (le - prev_le) * (rank - prev_c) / (c - prev_c)
            prev_le, prev_c = (le if le != math.inf else prev_le), c
        return delta[-1][0]

    def quantile(self, q: float, since=None):
        """Quantile over everything observed since ``since`` (a
        :meth:`cumulative` snapshot taken earlier; default: since the
        histogram was created)."""
        cum = self.cumulative()
        if since is None:
            since = [(le, 0) for le, _c in cum]
        return self.quantile_from_cumulative(since, cum, q)


_CHILD_TYPES = {"counter": Counter, "gauge": Gauge, "histogram": Histogram}


class _Family:
    """A named metric with a fixed label schema; children per label-value
    tuple.  An unlabeled family proxies its single child's mutators, so
    ``reg.counter("x").inc()`` works without a ``labels()`` hop."""

    def __init__(self, name: str, kind: str, help: str,
                 labelnames: Sequence[str],
                 buckets: Optional[Sequence[float]] = None):
        if not _NAME_RE.match(name):
            raise ValueError(f"invalid metric name {name!r}")
        for ln in labelnames:
            if not _LABEL_RE.match(ln):
                raise ValueError(f"invalid label name {ln!r}")
        self.name = name
        self.kind = kind
        self.help = help
        self.labelnames = tuple(labelnames)
        self.buckets = tuple(buckets) if buckets is not None else None
        self._children: dict = {}
        self._lock = threading.Lock()
        if not self.labelnames:
            self.labels()  # materialize the single child eagerly

    def _resolve(self, values, kv) -> tuple:
        if kv:
            if values:
                raise ValueError("pass label values positionally or by "
                                 "name, not both")
            if set(kv) != set(self.labelnames):
                raise ValueError(
                    f"{self.name}: expected labels {self.labelnames}, "
                    f"got {tuple(kv)}")
            values = tuple(str(kv[ln]) for ln in self.labelnames)
        else:
            values = tuple(str(v) for v in values)
        if len(values) != len(self.labelnames):
            raise ValueError(
                f"{self.name}: expected labels {self.labelnames}, "
                f"got {values}")
        return values

    def labels(self, *values, **kv) -> _Child:
        values = self._resolve(values, kv)
        child = self._children.get(values)
        if child is None:
            with self._lock:
                child = self._children.get(values)
                if child is None:
                    if self.kind == "histogram":
                        child = Histogram(values, self.buckets)
                    else:
                        child = _CHILD_TYPES[self.kind](values)
                    self._children[values] = child
        return child

    def remove(self, *values, **kv) -> bool:
        """Drop the child time series for these label values — elastic
        membership support: a worker that left the gang should disappear
        from scrapes and snapshots instead of freezing at its last value.
        Returns True when a child existed.  A later ``labels()`` call
        with the same values starts a fresh series from zero (correct
        for a *rejoining* member's gauges; do not use this on counters
        whose continuity matters)."""
        values = self._resolve(values, kv)
        with self._lock:
            return self._children.pop(values, None) is not None

    # unlabeled convenience proxies
    def inc(self, amount: float = 1.0) -> None:
        self.labels().inc(amount)

    def set(self, value: float) -> None:
        self.labels().set(value)

    def set_total(self, total: float) -> None:
        self.labels().set_total(total)

    def dec(self, amount: float = 1.0) -> None:
        self.labels().dec(amount)

    def observe(self, value: float) -> None:
        self.labels().observe(value)

    @property
    def value(self) -> float:
        return self.labels().value


class MetricsRegistry:
    """Thread-safe collection of metric families.

    ``counter``/``gauge``/``histogram`` are idempotent: asking for an
    existing name returns the existing family (and raises if the kind or
    label schema disagrees), so instrumentation sites can declare their
    metrics lazily without coordinating.
    """

    def __init__(self):
        self._families: dict = {}
        self._lock = threading.Lock()

    def _family(self, name: str, kind: str, help: str,
                labelnames: Sequence[str],
                buckets: Optional[Sequence[float]] = None) -> _Family:
        fam = self._families.get(name)
        if fam is None:
            with self._lock:
                fam = self._families.get(name)
                if fam is None:
                    fam = _Family(name, kind, help, labelnames, buckets)
                    self._families[name] = fam
                    return fam
        if fam.kind != kind or fam.labelnames != tuple(labelnames):
            raise ValueError(
                f"metric {name!r} already registered as {fam.kind} with "
                f"labels {fam.labelnames}; cannot re-register as {kind} "
                f"with labels {tuple(labelnames)}")
        return fam

    def counter(self, name: str, help: str = "",
                labelnames: Sequence[str] = ()) -> _Family:
        return self._family(name, "counter", help, labelnames)

    def gauge(self, name: str, help: str = "",
              labelnames: Sequence[str] = ()) -> _Family:
        return self._family(name, "gauge", help, labelnames)

    def histogram(self, name: str, help: str = "",
                  labelnames: Sequence[str] = (),
                  buckets: Sequence[float] = DEFAULT_BUCKETS) -> _Family:
        return self._family(name, "histogram", help, labelnames, buckets)

    def clear(self) -> None:
        """Drop every family (tests; production registries only grow)."""
        with self._lock:
            self._families.clear()

    # -- read side ----------------------------------------------------------

    def snapshot(self) -> dict:
        """Flat ``{sample_key: value}`` over every sample, in registration
        order.  Histogram children expand the same way the text exposition
        does: ``name_bucket{le=...}``, ``name_sum``, ``name_count``."""
        out: dict = {}
        for fam in list(self._families.values()):
            with fam._lock:  # vs. concurrent labels() child creation
                children = sorted(fam._children.items())
            for values, child in children:
                if fam.kind == "histogram":
                    for le, acc in child.cumulative():
                        key = _sample_key(
                            fam.name + "_bucket",
                            fam.labelnames + ("le",),
                            values + (_fmt(le),))
                        out[key] = float(acc)
                    out[_sample_key(fam.name + "_sum", fam.labelnames,
                                    values)] = child.sum
                    out[_sample_key(fam.name + "_count", fam.labelnames,
                                    values)] = float(child.count)
                else:
                    out[_sample_key(fam.name, fam.labelnames,
                                    values)] = child.value
        return out

    def dump(self) -> dict:
        """Structured, JSON-serializable export of every family — schema
        (kind, help, label names, histogram bucket bounds) plus raw child
        state (per-bucket counts, not cumulative).  This is the form one
        process can hand another for re-aggregation: ``obs.fleet``
        publishes it in worker snapshots and merges it back under a
        ``worker`` label, which the flat :meth:`snapshot` sample keys
        could only support by re-parsing."""
        fams = []
        for fam in list(self._families.values()):
            with fam._lock:  # vs. concurrent labels() child creation
                children = sorted(fam._children.items())
            ent = {"name": fam.name, "kind": fam.kind, "help": fam.help,
                   "labelnames": list(fam.labelnames)}
            if fam.kind == "histogram":
                ent["buckets"] = list(fam.buckets)
            kids = []
            for values, child in children:
                if fam.kind == "histogram":
                    with child._lock:
                        kids.append({"labels": list(values),
                                     "counts": list(child._counts),
                                     "sum": child._sum,
                                     "count": child._count})
                else:
                    kids.append({"labels": list(values),
                                 "value": child.value})
            ent["children"] = kids
            fams.append(ent)
        return {"families": fams}

    def delta(self, new: dict, old: dict) -> dict:
        """Difference of two :meth:`snapshot` dicts: monotonic samples
        (counters, histogram buckets/sums/counts) subtract, gauges pass
        through at their new value.  Samples absent from ``old`` count
        from zero."""
        gauge_names = {f.name for f in self._families.values()
                       if f.kind == "gauge"}
        out = {}
        for key, val in new.items():
            base = key.split("{", 1)[0]
            if base in gauge_names:
                out[key] = val
            else:
                out[key] = val - old.get(key, 0.0)
        return out

    def render_prometheus(self) -> str:
        """Text exposition format 0.0.4 (``/metrics``)."""
        lines = []
        for fam in list(self._families.values()):
            if fam.help:
                help_text = fam.help.replace("\\", "\\\\").replace(
                    "\n", "\\n")
                lines.append(f"# HELP {fam.name} {help_text}")
            lines.append(f"# TYPE {fam.name} {fam.kind}")
            with fam._lock:  # vs. concurrent labels() child creation
                children = sorted(fam._children.items())
            for values, child in children:
                if fam.kind == "histogram":
                    for le, acc in child.cumulative():
                        lines.append(
                            f"{_sample_key(fam.name + '_bucket', fam.labelnames + ('le',), values + (_fmt(le),))}"
                            f" {acc}")
                    lines.append(
                        f"{_sample_key(fam.name + '_sum', fam.labelnames, values)} {_fmt(child.sum)}")
                    lines.append(
                        f"{_sample_key(fam.name + '_count', fam.labelnames, values)} {child.count}")
                else:
                    lines.append(
                        f"{_sample_key(fam.name, fam.labelnames, values)} "
                        f"{_fmt(child.value)}")
        return "\n".join(lines) + "\n"

    def export_jsonl(self, path_or_file, extra: Optional[dict] = None) -> dict:
        """Append one JSON line — ``{"ts": ..., "metrics": snapshot()}``
        plus ``extra`` keys — to ``path_or_file``; returns the record.
        Call on a cadence for a poor-man's on-disk time series."""
        rec = {"ts": time.time(), "metrics": self.snapshot()}
        if extra:
            rec.update(extra)
        line = json.dumps(rec) + "\n"
        if hasattr(path_or_file, "write"):
            path_or_file.write(line)
        else:
            with open(path_or_file, "a") as f:
                f.write(line)
        return rec


# The process-wide default registry every instrumentation seam writes to.
_default = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    return _default
