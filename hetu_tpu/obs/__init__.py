"""Unified runtime telemetry: metrics registry, tracing spans, event
journal, and the ``/metrics``+``/healthz`` scrape endpoint.

The reference stack's observability is offline (timer subexecutors,
per-op re-execution profiling — SURVEY §5.1); the HET architecture it
headlines (cache-enabled PS, VLDB'22) is operated on *live* cache-hit
and staleness telemetry.  This package is the always-on layer the
production seams write to:

- :mod:`~hetu_tpu.obs.registry` — thread-safe process-wide
  ``MetricsRegistry`` (labeled counters/gauges/histograms, ``snapshot``
  deltas, Prometheus text exposition, JSONL export);
- :mod:`~hetu_tpu.obs.tracing` — cross-layer spans (trace/span/parent
  ids, context propagation, deterministic clock) exporting Chrome
  trace-event JSON mergeable with XProf traces;
- :mod:`~hetu_tpu.obs.journal` — append-only JSONL resilience event
  journal with monotonic sequence numbers;
- :mod:`~hetu_tpu.obs.server` — stdlib-HTTP ``/metrics`` / ``/healthz``
  endpoint (the ``exec/graphboard.py`` server pattern);
- :mod:`~hetu_tpu.obs.fleet` — the cross-worker plane: per-worker atomic
  snapshot publication into the gang dir, rank-0 aggregation under a
  ``worker`` label, merged journals, stitched traces, and the
  ``/fleet/*`` endpoints;
- :mod:`~hetu_tpu.obs.goodput` — online goodput buckets (useful /
  straggler-wait / rollback / rescale / checkpoint / retune / compile)
  and a rolling MFU gauge from one per-config flops model;
- :mod:`~hetu_tpu.obs.reqtrace` — request-scope serving timelines: one
  exact stage decomposition + span tree per request, kept in a bounded
  ring with slowest-N exemplar retention, queryable via
  ``/trace/<request_id>`` and stitchable with the fleet traces;
- :mod:`~hetu_tpu.obs.slo` — the serving SLO engine: per-request
  TTFT/TPOT/queue-age grading against env-configurable targets,
  short+long-window burn rates, and the ``/slo`` shed-pressure gauge
  (``/fleet/slo`` aggregates it);
- :mod:`~hetu_tpu.obs.compile` — XLA compilation telemetry: exact
  compile counting at the jit seams (serving step fns and
  ``Trainer.step``, keyed only where jit's C++ cache misses),
  per-shape-signature compile cost and
  ``memory_analysis`` bytes, ``recompile`` journal events carrying the
  triggering shape delta, and a recompile-storm gauge;
- :mod:`~hetu_tpu.obs.calibration` — the performance calibration
  plane: a versioned CRC+signed ``ProfileStore`` of calibration
  records ingested from the signals above, a fit layer emitting
  measured ``TimeCostModel``/``MemoryCostModel`` constants (consumed
  via ``dp_search(calibration=)`` / ``plan_memory(calibration=)``),
  and a perf-regression sentinel journaling ``perf_regression`` and
  flipping a ``/healthz`` red flag (``/calibration`` +
  ``/fleet/calibration``).

Instrumented seams: ``embed.net.RemoteEmbeddingTable._rpc`` (latency,
bytes, redials, errors), the HET caches (hit/miss), ``Trainer.step``
(latency, examples/s, grad-norm), ``exec.checkpoint`` (write duration/
bytes/CRC + journal), ``exec.resilience`` (journal events), and
``launch.simulate_workers`` (heartbeat-age straggler gauges).  All of it
is disabled in one switch — ``obs.disable()`` or ``HETU_OBS=0`` — and
the disabled path is a single global load + branch per seam.
"""

from hetu_tpu.obs.calibration import (Calibration, CalibrationKey,
                                      FittedConstant, ProfileStore,
                                      RegressionSentinel, fit_calibration,
                                      get_store, install_store)
from hetu_tpu.obs.compile import (InstrumentedJit, StormDetector,
                                  compile_report, instrument, watch)
from hetu_tpu.obs.divergence import (DivergenceDetector, FingerprintBoard,
                                     compare_fleet)
from hetu_tpu.obs.numerics import (FlightRecorder, first_nonfinite,
                                   fingerprint, group_stats,
                                   host_fingerprint, host_fingerprint_ints,
                                   host_group_stats, install_recorder,
                                   loss_provenance, tree_fingerprints)
from hetu_tpu.obs.fleet import (FleetAggregator, SnapshotPublisher,
                                fleet_routes, serve_fleet)
from hetu_tpu.obs.goodput import GoodputMeter
from hetu_tpu.obs.routing import record_routing
from hetu_tpu.obs.reqtrace import ReqTraceBuffer, RequestTimeline
from hetu_tpu.obs.slo import SLOEngine, SLOTargets
from hetu_tpu.obs.journal import (EventJournal, get_journal, record,
                                  set_journal, use)
from hetu_tpu.obs.registry import (DEFAULT_BUCKETS, Counter, Gauge,
                                   Histogram, MetricsRegistry, disable,
                                   enable, enabled, get_registry)
from hetu_tpu.obs.server import (Routes, RoutedHTTPServer, TelemetryServer,
                                 serve, telemetry_routes)
from hetu_tpu.obs.tracing import (Span, Tracer, current_span, get_tracer,
                                  span)

__all__ = [
    "record_routing",
    "MetricsRegistry", "Counter", "Gauge", "Histogram", "DEFAULT_BUCKETS",
    "get_registry", "enabled", "enable", "disable",
    "Tracer", "Span", "get_tracer", "span", "current_span",
    "EventJournal", "get_journal", "set_journal", "use", "record",
    "TelemetryServer", "serve", "Routes", "RoutedHTTPServer",
    "telemetry_routes",
    "SnapshotPublisher", "FleetAggregator", "fleet_routes", "serve_fleet",
    "GoodputMeter",
    "RequestTimeline", "ReqTraceBuffer",
    "SLOEngine", "SLOTargets",
    "InstrumentedJit", "StormDetector", "instrument", "watch",
    "compile_report",
    "FlightRecorder", "install_recorder", "fingerprint", "group_stats",
    "tree_fingerprints", "host_fingerprint", "host_fingerprint_ints",
    "host_group_stats", "first_nonfinite", "loss_provenance",
    "DivergenceDetector", "FingerprintBoard", "compare_fleet",
    "ProfileStore", "CalibrationKey", "Calibration", "FittedConstant",
    "RegressionSentinel", "fit_calibration", "install_store", "get_store",
]
