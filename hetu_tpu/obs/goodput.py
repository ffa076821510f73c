"""Online goodput & MFU accounting: efficiency as a scrape.

This module makes efficiency continuous: every unit of step wall time
is classified into one of the :data:`BUCKETS`, the classification is
exact (buckets sum to total accounted time by construction), and a
rolling MFU gauge is computed from the flops per step its caller states
(:func:`transformer_train_flops` gives a transformer's) over
:data:`PEAK_BF16`; the benchmark counts for itself in
``benchmark/counts.py`` and ``benchmark/peaks.py``.

Buckets (``hetu_goodput_seconds_total{bucket=...}``):

==================  ====================================================
``useful``          first-time execution of a committed step
``straggler_wait``  time spent waiting on the slowest contributor at a
                    partial-reduce cut (attributed per worker:
                    ``hetu_goodput_straggler_wait_seconds_total{worker=}``)
``rollback``        steps rejected by the anomaly guard + the rollback
                    restore itself
``rescale``         re-execution of already-committed steps after a gang
                    rescale rewound the lineage, plus barrier time
``checkpoint``      synchronous checkpoint writes (async writes hide
                    under ``useful`` and are journaled, not re-billed)
``retune``          kernel autotune sweeps (``hetu_tune_retunes_total``'s
                    wall cost, when the tuner reports it)
``compile``         XLA program compilation wall time, billed from the
                    ``obs.compile`` serving seams' journal events
                    (kinds ``compile``/``recompile`` with ``aot: true``)
                    exactly like ``checkpoint_saved``/``retune`` — one
                    billing path; watch-mode events are not billed
                    (their wall is inside a step already billed useful)
==================  ====================================================

Classification inputs are the things the runtime already records:
``Trainer.step``'s duration and ``skipped`` flag, the partial-reduce
cut's ``waited``/straggler rank, journal kinds (``checkpoint_saved``
carries ``duration_s``), and repeated step ids after a
``gang_rescale``.  :class:`GoodputMeter` is unit-agnostic — wall
seconds in production, step-clock units under the deterministic
:class:`~hetu_tpu.exec.gang.ElasticGang` simulation, which is what lets
the chaos acceptance assert the buckets sum EXACTLY to total time.

A process-wide meter is installed with :func:`install_meter`;
:func:`record_step` / :func:`record_event` are single-global-load-and-
branch no-ops when none is (the ``Trainer.step`` seam contract).
"""

from __future__ import annotations

import collections
import threading
from typing import Optional

from hetu_tpu.obs import registry as _registry

__all__ = ["BUCKETS", "GoodputMeter", "install_meter", "get_meter",
           "record_step", "record_event", "transformer_train_flops",
           "PEAK_BF16", "peak_flops"]

BUCKETS = ("useful", "straggler_wait", "rollback", "rescale",
           "checkpoint", "retune", "compile")

# ------------------------------------------------------------ flops model
# What the online MFU gauge divides by, and a transformer's count for it.

PEAK_BF16 = {
    # chip kind (jax.devices()[0].device_kind) -> peak bf16 FLOP/s.  The
    # v5e reports itself as "TPU v5 lite" (197 TFLOP/s: Google Cloud
    # documentation, "TPU v5e").
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v4": 275e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
}


def transformer_train_flops(L, h, V, batch, seq, ratio=4):
    """Forward+backward matmul FLOPs per step (2 flops per MAC, bwd = 2x fwd)."""
    per_layer_fwd = (
        6 * seq * h * h      # qkv projection
        + 2 * seq * h * h    # attention out projection
        + 4 * seq * seq * h  # QK^T and PV
        + 4 * ratio * seq * h * h  # MLP in+out
    )
    heads_fwd = 2 * seq * (h * h + h * V)  # mlm transform + tied decoder
    fwd = L * per_layer_fwd + heads_fwd
    return 3 * fwd * batch


def peak_flops(device_kind: Optional[str] = None) -> float:
    """Peak bf16 FLOP/s for ``device_kind`` (default: the first visible
    jax device).  A TPU kind that is not in :data:`PEAK_BF16` is an error,
    not a guess: an MFU normalized against another chip's peak is wrong
    without saying so.  Non-TPU hosts read 1e12, the CPU test-suite
    convention where MFU is only a smoke signal."""
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    if device_kind in PEAK_BF16:
        return PEAK_BF16[device_kind]
    if "TPU" in str(device_kind).upper():
        raise KeyError(
            f"unknown TPU device kind {device_kind!r}: add its peak bf16 "
            f"FLOP/s to hetu_tpu.obs.goodput.PEAK_BF16 (known: "
            f"{sorted(PEAK_BF16)}) or pass peak= explicitly")
    return 1e12


# ------------------------------------------------------------- the meter

class GoodputMeter:
    """Exact time-bucket accounting + rolling MFU.

    ``record_step`` splits one step's duration: the ``waited`` portion
    goes to ``straggler_wait`` (attributed to ``straggler``'s rank when
    given), the remainder to ``rollback`` (``skipped=True``), ``rescale``
    (a step id already committed once — post-rescale replay), or
    ``useful``.  ``record_event`` bills non-step time (rollback restores,
    synchronous checkpoint writes, retunes, rescale barriers).  By
    construction ``sum(totals.values()) == `` everything ever recorded,
    so the chaos acceptance can assert the partition is exact.

    MFU: after :meth:`set_flops_model`, each *useful* step contributes
    ``(flops, duration)`` to a rolling window; the gauge is
    ``sum(flops) / sum(duration) / peak`` over that window (and the
    cumulative value rides ``fractions()``).  Thread-safe; all gauges are
    lazily registered and no-ops while telemetry is disabled.
    """

    def __init__(self, *, registry: Optional[_registry.MetricsRegistry] = None,
                 window: int = 64):
        self._reg = registry
        self.totals = {b: 0.0 for b in BUCKETS}
        self.by_worker: dict = {}          # rank -> straggler_wait total
        # replay detection is a high-water mark, not a seen-set: step ids
        # are monotonic except after a rescale rewind, so `step <= max`
        # IS "already committed once" — and it stays O(1) memory over a
        # process-lifetime meter, where a set would grow one entry per
        # step forever
        self._max_step: Optional[int] = None
        self._win = collections.deque(maxlen=int(window))
        self._flops_per_step: Optional[float] = None
        self._peak: Optional[float] = None
        self._useful_flops = 0.0
        self._lock = threading.Lock()
        self._m = None

    def _metrics(self):
        if self._m is None:
            reg = self._reg if self._reg is not None \
                else _registry.get_registry()
            self._m = {
                "seconds": reg.counter(
                    "hetu_goodput_seconds_total",
                    "accounted step/driver time by goodput bucket "
                    "(useful, straggler_wait, rollback, rescale, "
                    "checkpoint, retune, compile); buckets partition the "
                    "total exactly", ("bucket",)),
                "fraction": reg.gauge(
                    "hetu_goodput_fraction",
                    "share of accounted time per goodput bucket "
                    "(useful's share IS the goodput)", ("bucket",)),
                "wait_by_worker": reg.counter(
                    "hetu_goodput_straggler_wait_seconds_total",
                    "straggler wait attributed to the slowest "
                    "contributor's rank at each partial-reduce cut",
                    ("worker",)),
                "mfu": reg.gauge(
                    "hetu_goodput_mfu",
                    "rolling model-flops utilization over the recent "
                    "useful steps (flops model set by the driver; 0 "
                    "until then)"),
            }
        return self._m

    def set_flops_model(self, flops_per_step: float,
                        peak: Optional[float] = None) -> None:
        """Attach the per-step flops model (e.g.
        :func:`transformer_train_flops` for the running config) and the
        peak FLOP/s to normalize by (default: :func:`peak_flops` of the
        visible device)."""
        self._flops_per_step = float(flops_per_step)
        self._peak = float(peak) if peak is not None else peak_flops()

    # -- recording ----------------------------------------------------------

    def record_step(self, duration: float, *, step: Optional[int] = None,
                    waited: float = 0.0, straggler: Optional[int] = None,
                    skipped: bool = False) -> None:
        """Account one executed step of ``duration`` time units."""
        duration = float(duration)
        wait = min(max(float(waited), 0.0), duration)
        rest = duration - wait
        with self._lock:
            enabled = _registry.enabled()
            m = self._metrics() if enabled else None
            if wait > 0:
                self.totals["straggler_wait"] += wait
                if enabled:
                    m["seconds"].labels(bucket="straggler_wait").inc(wait)
                if straggler is not None:
                    w = int(straggler)
                    self.by_worker[w] = self.by_worker.get(w, 0.0) + wait
                    if enabled:
                        m["wait_by_worker"].labels(worker=str(w)).inc(wait)
            if skipped:
                bucket = "rollback"
            elif step is not None and self._max_step is not None \
                    and step <= self._max_step:
                bucket = "rescale"  # replaying work a rescale rewound
            else:
                bucket = "useful"
                if step is not None:
                    self._max_step = step
                if self._flops_per_step is not None and duration > 0:
                    self._useful_flops += self._flops_per_step
                    self._win.append((self._flops_per_step, duration))
            self.totals[bucket] += rest
            if enabled:
                m["seconds"].labels(bucket=bucket).inc(rest)
            self._publish_gauges(enabled)

    def record_event(self, bucket: str, duration: float) -> None:
        """Bill non-step driver time (a rollback restore, a synchronous
        checkpoint write, a rescale barrier, an autotune sweep)."""
        if bucket not in BUCKETS:
            raise ValueError(f"unknown goodput bucket {bucket!r}; "
                             f"one of {BUCKETS}")
        duration = max(float(duration), 0.0)
        with self._lock:
            self.totals[bucket] += duration
            enabled = _registry.enabled()
            if enabled:
                self._metrics()["seconds"].labels(bucket=bucket).inc(duration)
            self._publish_gauges(enabled)

    def ingest(self, events, since_seq: int = 0) -> int:
        """Fold journal events into the buckets — ``checkpoint_saved``
        (its ``duration_s`` bills ``checkpoint``), ``retune``, and the
        ``obs.compile`` serving seams' ``compile``/``recompile`` records
        (``aot: true``, billing ``compile``), each carrying
        ``duration_s``.  Watch-mode compile events (``aot: false``) are
        deliberately NOT billed: their first-call wall is the training
        step's, and the step's own ``record_step`` already billed that
        second as ``useful`` — billing it again would break the
        exact-partition invariant (the same never-double-bill rule the
        autotune sweep follows).  Returns the new cursor (max seq seen), for
        incremental polls against ``/journal?since=``."""
        last = int(since_seq)
        billed = {"checkpoint_saved": "checkpoint", "retune": "retune",
                  "compile": "compile", "recompile": "compile"}
        for e in events:
            seq = int(e.get("seq", 0))
            if seq <= since_seq:
                continue
            last = max(last, seq)
            bucket = billed.get(e.get("kind"))
            if bucket == "compile" and not e.get("aot"):
                continue
            if bucket is not None:
                self.record_event(bucket, float(e.get("duration_s", 0.0)))
        return last

    # -- read side ----------------------------------------------------------

    def _publish_gauges(self, enabled: bool) -> None:
        # callers hold self._lock
        if not enabled:
            return
        m = self._metrics()
        total = sum(self.totals.values())
        for b in BUCKETS:
            m["fraction"].labels(bucket=b).set(
                self.totals[b] / total if total > 0 else 0.0)
        m["mfu"].set(self._rolling_mfu())

    def _rolling_mfu(self) -> float:
        if self._peak is None or not self._win:
            return 0.0
        flops = sum(f for f, _d in self._win)
        secs = sum(d for _f, d in self._win)
        return flops / secs / self._peak if secs > 0 else 0.0

    def total(self) -> float:
        """Total accounted time — equals ``sum(totals.values())``
        exactly (the partition invariant the chaos tests assert)."""
        with self._lock:
            return sum(self.totals.values())

    def fractions(self) -> dict:
        with self._lock:
            total = sum(self.totals.values())
            return {b: (self.totals[b] / total if total > 0 else 0.0)
                    for b in BUCKETS}

    def mfu(self) -> float:
        """Rolling MFU over the recent useful-step window."""
        with self._lock:
            return self._rolling_mfu()

    def snapshot(self) -> dict:
        """One JSON-able report: totals, fractions, per-worker straggler
        wait, rolling + cumulative MFU."""
        with self._lock:
            total = sum(self.totals.values())
            cum_mfu = (self._useful_flops / total / self._peak
                       if self._peak and total > 0 else 0.0)
            return {"totals": dict(self.totals), "total": total,
                    "fractions": {b: (self.totals[b] / total
                                      if total > 0 else 0.0)
                                  for b in BUCKETS},
                    "straggler_wait_by_worker": dict(self.by_worker),
                    "mfu_rolling": self._rolling_mfu(),
                    "mfu_cumulative": cum_mfu}


# ------------------------------------------------ process-wide installation

_meter: Optional[GoodputMeter] = None


def install_meter(meter: Optional[GoodputMeter]) -> Optional[GoodputMeter]:
    """Install ``meter`` as the process-wide sink for :func:`record_step`
    (None uninstalls).  Returns the meter."""
    global _meter
    _meter = meter
    return meter


def get_meter() -> Optional[GoodputMeter]:
    return _meter


def record_step(duration: float, *, step: Optional[int] = None,
                waited: float = 0.0, straggler: Optional[int] = None,
                skipped: bool = False) -> None:
    """Emit to the installed meter; no-op (one global load + branch) when
    none is installed or telemetry is disabled — the ``Trainer.step``
    hot-path contract."""
    m = _meter
    if m is None or not _registry.enabled():
        return
    m.record_step(duration, step=step, waited=waited, straggler=straggler,
                  skipped=skipped)


def record_event(bucket: str, duration: float) -> None:
    """Emit a non-step bucket charge to the installed meter; no-op when
    none is installed or telemetry is disabled."""
    m = _meter
    if m is None or not _registry.enabled():
        return
    m.record_event(bucket, duration)
