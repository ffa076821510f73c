"""SLO decomposition engine: per-request stage accounting, burn rates,
and the shed-pressure signal.

``obs.goodput`` made *training* efficiency a scrape by classifying every
unit of step time into buckets that sum to total by construction.  This
module applies the same discipline per serving request: every resolved
:class:`~hetu_tpu.obs.reqtrace.RequestTimeline` is decomposed into the
``queue``/``prefill``/``decode``/``emit`` stages (exact partition — see
reqtrace), graded against the TTFT / TPOT / queue-age targets, and
folded into rolling short+long violation windows from which burn rates
and a shed-pressure gauge are derived.

Targets (:class:`SLOTargets`) come from the constructor or environment:

=========================  ============================================
``HETU_TPU_SLO_TTFT``      time-to-first-token target, seconds
``HETU_TPU_SLO_TPOT``      time-per-output-token target, seconds
                           (decode stage / decode tokens)
``HETU_TPU_SLO_QUEUE``     queue-age target, seconds (admission wait;
                           expiries count against it by definition)
``HETU_TPU_SLO_OBJECTIVE`` the SLO fraction (default 0.99: 1% of
                           requests may violate before the budget is
                           spent)
=========================  ============================================

**Burn rate** is the SRE multi-window form: over a window, ``burn =
violating_fraction / (1 - objective)`` — 1.0 means the error budget is
being consumed exactly at the sustainable rate, N means N× too fast.
Both a short window (default 60 s — fast detection) and a long window
(default 600 s — deduced sustained damage) are kept per target; the
**shed-pressure** gauge is ``clip(max_target min(short, long) /
shed_burn, 0, 1)`` — both windows must burn (the short window alone
spikes on one slow request; the long window alone lags), which is the
standard guard against paging on noise.  1.0 means "shed now"; the
future multi-replica router reads this gauge for placement and
admission decisions, and ``/slo`` (per process) and ``/fleet/slo``
(aggregated) publish it.

**Per-tenant grading** (multi-tenant front door): every timeline whose
attrs carry a ``tenant`` id is additionally folded into per-(tenant,
class) violation windows, yielding :meth:`SLOEngine.tenant_shed_pressure`
— the scoped signal the controller's shed actuator uses to shed the
tenant *causing* the burn instead of everyone.  The aggregate windows
above are untouched (single-tenant runs produce bit-identical burn
state and ``/slo`` payloads); the tenants section appears in
:meth:`SLOEngine.summary` and the ``hetu_tenant_shed_pressure`` gauge
only once a non-default tenant has been observed.

Everything is clock-injectable (the serving engine passes its own
clock), so deterministic tests drive the windows exactly.  All metrics
are lazily registered and no-ops while telemetry is disabled.
"""

from __future__ import annotations

import collections
import dataclasses
import os
import threading
import time
from typing import Callable, Optional

from hetu_tpu.obs import registry as _registry
from hetu_tpu.obs.reqtrace import STAGES, RequestTimeline

__all__ = ["SLOTargets", "SLOEngine"]

_ENV = {"ttft_s": "HETU_TPU_SLO_TTFT", "tpot_s": "HETU_TPU_SLO_TPOT",
        "queue_age_s": "HETU_TPU_SLO_QUEUE",
        "objective": "HETU_TPU_SLO_OBJECTIVE"}


@dataclasses.dataclass(frozen=True)
class SLOTargets:
    """The serving SLO: latency targets plus the objective fraction."""

    ttft_s: float = 0.5
    tpot_s: float = 0.1
    queue_age_s: float = 0.25
    objective: float = 0.99

    def __post_init__(self):
        if not 0.0 < self.objective < 1.0:
            raise ValueError(
                f"objective must be in (0, 1), got {self.objective}")
        for f in ("ttft_s", "tpot_s", "queue_age_s"):
            if getattr(self, f) <= 0:
                raise ValueError(f"{f} must be positive")

    @classmethod
    def from_env(cls, **overrides) -> "SLOTargets":
        """Targets from the environment (``HETU_TPU_SLO_*``), explicit
        ``overrides`` winning — the production wiring, so a fleet's SLO
        is deployment config, not code."""
        kw = {}
        for field, env in _ENV.items():
            raw = os.environ.get(env)
            if raw is not None:
                kw[field] = float(raw)
        kw.update(overrides)
        return cls(**kw)


#: the graded dimensions, each with its own violation window pair
TARGETS = ("ttft", "tpot", "queue_age")


class _Window:
    """Rolling (timestamp, violated) record over a fixed horizon."""

    __slots__ = ("horizon", "events")

    def __init__(self, horizon: float):
        self.horizon = float(horizon)
        self.events: collections.deque = collections.deque()

    def add(self, now: float, violated: bool) -> None:
        self.events.append((now, bool(violated)))
        self.trim(now)

    def trim(self, now: float) -> None:
        while self.events and now - self.events[0][0] > self.horizon:
            self.events.popleft()

    def fraction(self, now: float) -> float:
        self.trim(now)
        if not self.events:
            return 0.0
        return sum(1 for _, v in self.events if v) / len(self.events)


class SLOEngine:
    """Grades resolved request timelines against the targets and keeps
    the burn-rate / shed-pressure state.  One per serving engine; writes
    to the process registry (``hetu_slo_*``)."""

    def __init__(self, targets: Optional[SLOTargets] = None, *,
                 clock: Callable[[], float] = time.monotonic,
                 short_window_s: float = 60.0, long_window_s: float = 600.0,
                 shed_burn: float = 2.0,
                 registry: Optional[_registry.MetricsRegistry] = None):
        self.targets = targets if targets is not None \
            else SLOTargets.from_env()
        self.clock = clock
        self.short_window_s = float(short_window_s)
        self.long_window_s = float(long_window_s)
        # the burn rate at which shed pressure saturates to 1.0 (burning
        # the error budget `shed_burn`x too fast on BOTH windows)
        self.shed_burn = float(shed_burn)
        self._windows = {t: (_Window(short_window_s), _Window(long_window_s))
                         for t in TARGETS}
        self.stage_totals = dict.fromkeys(STAGES, 0.0)
        self.requests = 0
        self.violations = dict.fromkeys(TARGETS, 0)
        # per-tenant scoped burn state: tenant id -> {target: (short,
        # long)} window pairs, plus class / request / violation rosters.
        # Populated lazily from timeline attrs; a pre-tenant deployment
        # only ever materializes the "default" row.
        self._tenant_windows: dict = {}
        self._tenant_class: dict = {}
        self._tenant_requests: dict = {}
        self._tenant_violations: dict = {}
        self._reg = registry
        self._m = None
        self._lock = threading.Lock()

    def _metrics(self):
        if self._m is None:
            reg = self._reg if self._reg is not None \
                else _registry.get_registry()
            self._m = {
                "stage": reg.counter(
                    "hetu_slo_stage_seconds_total",
                    "request wall time by stage (queue, prefill, decode, "
                    "emit); per request the stages partition wall time "
                    "exactly, so this is total request-seconds by where "
                    "they went", ("stage",)),
                "requests": reg.counter(
                    "hetu_slo_requests_total",
                    "requests graded against the SLO targets, by verdict",
                    ("verdict",)),
                "violations": reg.counter(
                    "hetu_slo_violations_total",
                    "per-target SLO violations (one request can violate "
                    "several targets)", ("target",)),
                "burn": reg.gauge(
                    "hetu_slo_burn_rate",
                    "error-budget burn rate per target and window "
                    "(violating fraction / (1 - objective); 1.0 = "
                    "sustainable)", ("target", "window")),
                "shed": reg.gauge(
                    "hetu_slo_shed_pressure",
                    "admission shed signal in [0, 1]: max over targets of "
                    "min(short, long) burn, normalized by the shed burn "
                    "threshold — the router/admission input"),
                "tenant_shed": reg.gauge(
                    "hetu_tenant_shed_pressure",
                    "per-(tenant, class) admission shed signal in [0, 1] "
                    "— the controller's scoped-shed input; published only "
                    "once a non-default tenant has been observed",
                    ("tenant", "klass")),
            }
        return self._m

    # -- grading ------------------------------------------------------------

    def grade(self, tl: RequestTimeline) -> dict:
        """The per-request verdict WITHOUT recording it (pure): stage
        split, derived latencies, and per-target violation flags."""
        stages = tl.stage_seconds()
        ttft = stages["queue"] + stages["prefill"]
        decode_tokens = max(tl.decode_count() - 1, 0)
        tpot = (stages["decode"] / decode_tokens) if decode_tokens else 0.0
        t = self.targets
        violated = {
            # a never-admitted expiry spent its whole life in the queue:
            # it violates queue_age by definition even if the deadline
            # was short.  A RUNNING-stage expiry does not — charging it
            # here would point the burn rates at admission when the
            # regression is decode.
            "queue_age": (stages["queue"] > t.queue_age_s
                          or (tl.outcome == "expired"
                              and tl.admitted_at is None)),
            "ttft": tl.first_token_at is not None and ttft > t.ttft_s,
            "tpot": tpot > t.tpot_s,
        }
        return {"stages_s": stages, "ttft_s": ttft, "tpot_s": tpot,
                "violated": violated}

    def observe(self, tl: RequestTimeline) -> dict:
        """Grade one resolved timeline and fold it into the counters and
        burn windows; returns the grade."""
        g = self.grade(tl)
        now = self.clock()
        with self._lock:
            enabled = _registry.enabled()
            m = self._metrics() if enabled else None
            self.requests += 1
            any_violation = False
            for stage, dt in g["stages_s"].items():
                self.stage_totals[stage] += dt
                if enabled and dt:
                    m["stage"].labels(stage=stage).inc(dt)
            tid = str(tl.attrs.get("tenant", "default"))
            tw = self._tenant_windows.get(tid)
            if tw is None:
                tw = {t: (_Window(self.short_window_s),
                          _Window(self.long_window_s)) for t in TARGETS}
                self._tenant_windows[tid] = tw
                self._tenant_class[tid] = str(
                    tl.attrs.get("tenant_class", "latency"))
                self._tenant_requests[tid] = 0
                self._tenant_violations[tid] = dict.fromkeys(TARGETS, 0)
            self._tenant_requests[tid] += 1
            for target in TARGETS:
                v = bool(g["violated"][target])
                any_violation |= v
                if v:
                    self.violations[target] += 1
                    self._tenant_violations[tid][target] += 1
                    if enabled:
                        m["violations"].labels(target=target).inc()
                for w in self._windows[target]:
                    w.add(now, v)
                for w in tw[target]:
                    w.add(now, v)
            if enabled:
                m["requests"].labels(
                    verdict="violated" if any_violation else "ok").inc()
                self._publish(now, m)
        return g

    # -- burn / shed --------------------------------------------------------

    def _budget(self) -> float:
        return 1.0 - self.targets.objective

    def burn_rates(self, now: Optional[float] = None) -> dict:
        """``{target: {"short": rate, "long": rate}}`` at ``now``."""
        now = self.clock() if now is None else now
        budget = self._budget()
        with self._lock:
            return {t: {"short": short.fraction(now) / budget,
                        "long": long.fraction(now) / budget}
                    for t, (short, long) in self._windows.items()}

    def shed_pressure(self, now: Optional[float] = None) -> float:
        """max over targets of min(short, long) burn, normalized by
        ``shed_burn`` and clipped to [0, 1]."""
        rates = self.burn_rates(now)
        worst = max((min(r["short"], r["long"]) for r in rates.values()),
                    default=0.0)
        return min(max(worst / self.shed_burn, 0.0), 1.0)

    def _pressure_of(self, windows: dict, now: float) -> float:
        # caller holds self._lock
        budget = self._budget()
        worst = max((min(short.fraction(now), long.fraction(now)) / budget
                     for short, long in windows.values()), default=0.0)
        return min(max(worst / self.shed_burn, 0.0), 1.0)

    def tenant_shed_pressure(self, tenant_id: str,
                             now: Optional[float] = None) -> float:
        """The scoped shed signal: :meth:`shed_pressure` computed over
        ONE tenant's violation windows (0.0 for a never-observed
        tenant).  The controller's surgical actuator reads this so a
        flooding tenant's burn cannot shed a victim."""
        now = self.clock() if now is None else now
        with self._lock:
            tw = self._tenant_windows.get(str(tenant_id))
            return self._pressure_of(tw, now) if tw is not None else 0.0

    def observed_tenants(self) -> dict:
        """Tenants seen so far (id -> priority class)."""
        with self._lock:
            return dict(self._tenant_class)

    @property
    def multi_tenant(self) -> bool:
        """True once any non-default tenant has been graded — the
        monotone switch the controller uses to pick the scoped shed
        policy over the legacy global one."""
        with self._lock:
            return any(tid != "default" for tid in self._tenant_windows)

    def _publish(self, now: float, m: dict) -> None:
        # caller holds self._lock; recompute without re-locking
        budget = self._budget()
        worst = 0.0
        for target, (short, long) in self._windows.items():
            s, l_ = short.fraction(now) / budget, long.fraction(now) / budget
            m["burn"].labels(target=target, window="short").set(s)
            m["burn"].labels(target=target, window="long").set(l_)
            worst = max(worst, min(s, l_))
        m["shed"].set(min(max(worst / self.shed_burn, 0.0), 1.0))
        # the per-tenant gauge only once real multi-tenant traffic
        # exists — a pre-tenant deployment's metric surface is unchanged
        if any(tid != "default" for tid in self._tenant_windows):
            for tid, tw in self._tenant_windows.items():
                m["tenant_shed"].labels(
                    tenant=tid, klass=self._tenant_class[tid]).set(
                        self._pressure_of(tw, now))

    # -- read side ----------------------------------------------------------

    def stage_summary(self) -> dict:
        """Total + per-request-mean + fractional split per stage: the
        benchmark's ``serve.queue_share`` reads it (a regression shows
        up as a stage's share moving, not just a ratio)."""
        with self._lock:
            total = sum(self.stage_totals.values())
            n = self.requests
            return {s: {"total_s": self.stage_totals[s],
                        "mean_s": self.stage_totals[s] / n if n else 0.0,
                        "fraction": (self.stage_totals[s] / total
                                     if total > 0 else 0.0)}
                    for s in STAGES}

    def summary(self) -> dict:
        """The ``/slo`` payload."""
        now = self.clock()
        rates = self.burn_rates(now)
        with self._lock:
            total = sum(self.stage_totals.values())
            body = {
                "targets": dataclasses.asdict(self.targets),
                "windows_s": {"short": self.short_window_s,
                              "long": self.long_window_s},
                "requests": self.requests,
                "violations": dict(self.violations),
                "stages": {s: {"total_s": self.stage_totals[s],
                               "fraction": (self.stage_totals[s] / total
                                            if total > 0 else 0.0)}
                           for s in STAGES},
                "burn_rates": rates,
            }
        worst = max((min(r["short"], r["long"]) for r in rates.values()),
                    default=0.0)
        body["shed_pressure"] = min(max(worst / self.shed_burn, 0.0), 1.0)
        with self._lock:
            if any(tid != "default" for tid in self._tenant_windows):
                budget = self._budget()
                body["tenants"] = {
                    tid: {"class": self._tenant_class[tid],
                          "requests": self._tenant_requests[tid],
                          "violations": dict(self._tenant_violations[tid]),
                          "burn_rates": {
                              t: {"short": short.fraction(now) / budget,
                                  "long": long.fraction(now) / budget}
                              for t, (short, long) in tw.items()},
                          "shed_pressure": self._pressure_of(tw, now)}
                    for tid, tw in sorted(self._tenant_windows.items())}
        return body
