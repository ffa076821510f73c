"""Closed-loop remediation: a controller that acts on the telemetry plane.

PRs 6-10 built every signal a human SRE would watch — straggler-lag
EWMAs (``WorkerLagEWMA``), SLO burn rates and the shed-pressure gauge
(``obs.slo``), compile-storm gauges (``obs.compile.StormDetector``),
replica-divergence verdicts (``obs.divergence``) — but they only
rendered on ``/fleet`` endpoints: a dying run still died.  This module
closes the loop.  A rank-0 :class:`RuntimeController` consumes those
signals and drives the *existing* actuators through journaled,
seeded-replayable ``remediation`` events:

1. **Partial-reduce deadline auto-tuning** — each committed gang step,
   the per-worker arrival-lag EWMAs propose a deadline that covers the
   healthy ``cover_fraction`` of the gang with ``headroom`` slack:
   tighten when the gang is healthy, relax under injected pareto
   stalls.  The proposal is clamped by the policy's own
   :meth:`~hetu_tpu.exec.partial.PartialReduceConfig.clamp` bounds and
   hysteresis-damped (relative deadband + a ``cooldown_steps`` refractory
   period), so the deadline never oscillates.  Tuned cuts journal
   ``deadline_source="controller"`` on their ``partial_step`` events, so
   replays distinguish tuned from configured cuts.

2. **Divergence quarantine** — a fresh ``replica_divergence`` verdict
   (the PR-10 detector naming step/worker/shard) evicts the divergent
   replica's lease (:meth:`~hetu_tpu.exec.gang.ElasticGang.quarantine`:
   the rank stops renewing and its *suspect* shard storage is dropped),
   the gang ``rescale()``s, and the restore recovers that rank's shard
   from its ring neighbor's replica (``shard_restore``) — a completed
   run instead of a lost one.

3. **Admission shedding** — sustained SLO burn (the shed-pressure gauge
   at or above ``shed_on`` for ``sustain_ticks`` consecutive scheduler
   ticks) engages :meth:`~hetu_tpu.serve.batcher.ContinuousBatcher.
   set_shed`: ``submit`` rejects with a distinguishable ``/infer`` error
   (``AdmissionShed``, counted ``hetu_serve_shed_total{reason=
   controller}``) until pressure stays at or below ``shed_off`` for
   ``sustain_ticks`` ticks.

4. **Compile-storm bucket freeze** — while the recompile-storm gauge is
   up, serving prompt-bucket *growth* freezes: a prompt whose prefill
   bucket has not been compiled yet is rejected (reason
   ``bucket_freeze``) instead of adding fuel to the storm; already-warm
   buckets keep serving.  The freeze lifts when the gauge clears.

Every decision — acted or not — is a ``remediation`` journal event
carrying ``action`` / ``signal`` / ``dry_run`` plus the decision's
numbers, so chaos acceptance stays bitwise: inject the seeded fault
distribution, assert the controller's action sequence and the recovered
goodput across same-seed runs.  **Dry-run mode**
(``ControllerConfig(dry_run=True)``) journals identical ``would_act``
decisions while actuating nothing — the deadline decisions evolve
against an internal shadow value, so the decision stream is the same
pure function of the signals the active controller would see — the
audit trail a production rollout needs before flipping the switch.

The seams match the obs conventions: :func:`maybe_gang_step` /
:func:`maybe_serve_tick` / :func:`maybe_after_train_step` are one
global load + branch when no controller is installed (the
``Trainer.step`` overhead contract).  A controller is attached
explicitly (``ElasticGang(controller=...)`` /
``ServingEngine(controller=...)``) or installed process-wide with
:func:`install` / :func:`use` — the installed one also backs the
``/controller`` endpoint (``obs/server.py``) and its ``hetu_ctrl_*``
metrics ride the PR-8 fleet snapshots into ``/fleet/controller``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import os
import weakref
from typing import Optional

from hetu_tpu.obs import compile as _obs_compile
from hetu_tpu.obs import journal as _obs_journal
from hetu_tpu.obs import registry as _obs

__all__ = ["ControllerConfig", "RuntimeController", "get_controller",
           "install", "use", "maybe_gang_step", "maybe_serve_tick",
           "maybe_after_train_step", "controller_smoke"]

_ENV_PREFIX = "HETU_TPU_CTRL_"


@dataclasses.dataclass(frozen=True)
class ControllerConfig:
    """The remediation policy — every knob of the four control loops.

    Deadline tuning: ``proposed = clamp(headroom * lag[q])`` where
    ``lag[q]`` is the ``cover_fraction`` order statistic of the
    per-worker arrival-lag EWMAs (cover the healthy majority, let the
    tail fold late — covering the *worst* straggler would re-derive the
    full barrier partial reduce exists to break).  The controller acts
    only when the proposal moves more than ``hysteresis`` of the larger
    of (current, proposed) and at least ``cooldown_steps`` after its
    last retune — the two dampers that make oscillation impossible.

    Shedding: engage at shed-pressure >= ``shed_on`` sustained for
    ``sustain_ticks`` scheduler ticks; release at <= ``shed_off``
    sustained equally long (the on/off gap is the third hysteresis
    band).  ``dry_run`` journals every decision as ``would_act`` and
    touches nothing.
    """

    enabled: bool = True
    dry_run: bool = False
    # 1: partial-reduce deadline auto-tuning
    tune_deadline: bool = True
    headroom: float = 1.5
    cover_fraction: float = 0.75
    hysteresis: float = 0.25
    cooldown_steps: int = 4
    # 2: divergence quarantine
    quarantine: bool = True
    # 2b: serving flap quarantine (PR 20): a replica declared lost this
    # many times (a hang/recover cycle that keeps repeating) is
    # quarantined on its FailoverMonitor — never restored on heartbeat
    # recovery — instead of oscillating in and out of the placement
    # ranking.  Gated by the same ``quarantine`` switch as loop 2.
    replica_flap_threshold: int = 2
    # 3: SLO-burn admission shedding
    shed: bool = True
    shed_on: float = 0.9
    shed_off: float = 0.25
    sustain_ticks: int = 3
    # 3b: tenant-scoped shedding (multi-tenant engines only): batch-
    # class tenants engage at shed_on * batch_shed_factor — throughput
    # traffic is the first to go under sustained burn, latency-class
    # tenants shed only on their OWN burn at the full threshold
    batch_shed_factor: float = 0.5
    # 4: compile-storm bucket freeze
    freeze_buckets: bool = True
    # 5: ledger-backed memory pressure (PR 17): when the installed
    # MemoryLedger's worst-pool occupancy holds at or above ``mem_on``
    # for ``sustain_ticks`` ticks, defrag the engine's pool and shed
    # admission; release at or below ``mem_off`` sustained equally long
    # (the same hysteresis discipline as the SLO shed loop)
    mem_pressure: bool = True
    mem_on: float = 0.92
    mem_off: float = 0.75

    def __post_init__(self):
        if self.headroom <= 0:
            raise ValueError(f"headroom must be > 0, got {self.headroom}")
        if not 0.0 < self.cover_fraction <= 1.0:
            raise ValueError(f"cover_fraction must be in (0, 1], got "
                             f"{self.cover_fraction}")
        if self.hysteresis < 0:
            raise ValueError(f"hysteresis must be >= 0, got "
                             f"{self.hysteresis}")
        if self.cooldown_steps < 0:
            raise ValueError(f"cooldown_steps must be >= 0, got "
                             f"{self.cooldown_steps}")
        if not 0.0 <= self.shed_off <= self.shed_on:
            raise ValueError(
                f"need 0 <= shed_off <= shed_on (the hysteresis band), "
                f"got shed_off={self.shed_off} shed_on={self.shed_on}")
        if not 0.0 < self.shed_on <= 1.0:
            raise ValueError(f"shed_on is a shed-pressure fraction in "
                             f"(0, 1], got {self.shed_on}")
        if self.sustain_ticks < 1:
            raise ValueError(f"sustain_ticks must be >= 1, got "
                             f"{self.sustain_ticks}")
        if self.replica_flap_threshold < 1:
            raise ValueError(f"replica_flap_threshold must be >= 1, got "
                             f"{self.replica_flap_threshold}")
        if not 0.0 < self.batch_shed_factor <= 1.0:
            raise ValueError(f"batch_shed_factor scales shed_on for "
                             f"batch-class tenants, must be in (0, 1], "
                             f"got {self.batch_shed_factor}")
        if not 0.0 <= self.mem_off <= self.mem_on:
            raise ValueError(
                f"need 0 <= mem_off <= mem_on (the hysteresis band), "
                f"got mem_off={self.mem_off} mem_on={self.mem_on}")
        if not 0.0 < self.mem_on <= 1.0:
            raise ValueError(f"mem_on is a used-page fraction in (0, 1], "
                             f"got {self.mem_on}")

    @classmethod
    def from_env(cls, **overrides) -> "ControllerConfig":
        """Policy from the environment (``HETU_TPU_CTRL_*``), explicit
        ``overrides`` winning — deployment config, not code.  Booleans
        parse 1/true/yes (case-insensitive)."""
        spec = {"enabled": bool, "dry_run": bool, "tune_deadline": bool,
                "headroom": float, "cover_fraction": float,
                "hysteresis": float, "cooldown_steps": int,
                "quarantine": bool, "replica_flap_threshold": int,
                "shed": bool, "shed_on": float,
                "shed_off": float, "sustain_ticks": int,
                "batch_shed_factor": float, "freeze_buckets": bool,
                "mem_pressure": bool, "mem_on": float, "mem_off": float}
        kw = {}
        for field, typ in spec.items():
            raw = os.environ.get(_ENV_PREFIX + field.upper())
            if raw is None:
                continue
            if typ is bool:
                kw[field] = raw.strip().lower() in ("1", "true", "yes")
            else:
                kw[field] = typ(raw)
        kw.update(overrides)
        return cls(**kw)


# ------------------------------------------------------------- telemetry

def _ctrl_families(reg) -> dict:
    """The ``hetu_ctrl_*`` families on ``reg`` (idempotent: identical
    re-registration returns the existing family)."""
    return {
            "actions": reg.counter(
                "hetu_ctrl_actions_total",
                "remediation actions the controller APPLIED, by action "
                "(deadline_retune, quarantine, admission_shed, "
                "admission_release, bucket_freeze, bucket_unfreeze)",
                ("action",)),
            "would_act": reg.counter(
                "hetu_ctrl_would_act_total",
                "remediation decisions a DRY-RUN controller journaled "
                "without actuating, by action — the rollout audit trail",
                ("action",)),
            "deadline": reg.gauge(
                "hetu_ctrl_deadline_seconds",
                "the controller's current partial-reduce deadline "
                "(step-clock units in the in-process gang, wall seconds "
                "over a GradientBoard); tracks the shadow value in dry "
                "run"),
            "shed_active": reg.gauge(
                "hetu_ctrl_shed_active",
                "1 while controller admission shedding is engaged "
                "(sustained SLO burn), else 0"),
            "freeze_active": reg.gauge(
                "hetu_ctrl_freeze_active",
                "1 while serving prompt-bucket growth is frozen (compile "
                "storm), else 0"),
            "mem_active": reg.gauge(
                "hetu_ctrl_mem_pressure_active",
                "1 while the ledger-backed memory-pressure remediation "
                "is latched (sustained pool occupancy), else 0"),
        }


class RuntimeController:
    """The rank-0 signals → actuators loop.

    Stateless about the systems it controls beyond what determinism
    needs: a shadow deadline (so dry-run decisions evolve identically to
    an active controller's), the divergence-event cursor, the shed/freeze
    latches and their sustain streaks.  Every method is driven by the
    controlled system's own clock/step, so a seeded replay reproduces the
    decision sequence bitwise."""

    def __init__(self, config: Optional[ControllerConfig] = None, *,
                 registry: Optional[_obs.MetricsRegistry] = None,
                 history: int = 512, planner=None):
        self.config = config if config is not None else ControllerConfig()
        # unified-deployment replanning (hetu_tpu/plan.PlanApplier): an
        # attached planner turns remediation into planning — a
        # quarantine decision re-plans against the surviving fleet, a
        # sustained-SLO-burn shed engage re-plans the serving tier.
        # Dry-run flows through: the planner journals the identical
        # decision and actuates nothing.  None = legacy behavior.
        self.planner = planner
        # metrics land on the process registry by default; a private one
        # (controller_smoke, tests) keeps hetu_ctrl_* series unpolluted
        self._reg = registry
        self._metrics = None
        # decision history: journal-field form, bounded to the newest
        # `history` entries (the journal is the unbounded record; a
        # long-lived controller must not grow — or ship on every
        # /controller scrape — weeks of decision dicts)
        self.history = int(history)
        self.actions: list = []
        self.actions_total = 0
        # deadline-tuning state: the shadow deadline the decisions are
        # made against (== the actuated deadline when not dry_run)
        self._deadline: Optional[float] = None
        self._last_retune_step: Optional[int] = None
        # quarantine state (_quarantined holds CURRENT-generation ranks:
        # a rescale renumbers survivors, so it resets per generation)
        self._div_cursor = 0
        self._quarantined: set = set()
        self._quarantine_gen: Optional[int] = None
        # serve state is PER ENGINE: one installed controller may drive
        # several ServingEngines, and engine A's latch must never be
        # released (or its sustain streak polluted) by engine B's ticks.
        # Weak keys: a departed engine needs no release.
        self._serve_state: "weakref.WeakKeyDictionary" = \
            weakref.WeakKeyDictionary()
        # serving flap-quarantine state is PER FailoverMonitor (one
        # installed controller may watch several fleets): monitor -> the
        # set of replicas already decided.  The latch IS the hysteresis
        # — one quarantine decision per replica, in dry run too, so the
        # decision stream matches an active controller's even though a
        # dry-run replica keeps recovering and failing.
        self._fleet_state: "weakref.WeakKeyDictionary" = \
            weakref.WeakKeyDictionary()

    # -- the decision record --------------------------------------------------

    def _m(self) -> dict:
        if self._metrics is None:
            self._metrics = _ctrl_families(
                self._reg if self._reg is not None
                else _obs.get_registry())
        return self._metrics

    def _act(self, action: str, signal: str, **fields) -> dict:
        rec = {"action": action, "signal": signal,
               "dry_run": bool(self.config.dry_run), **fields}
        self.actions.append(rec)
        self.actions_total += 1
        if len(self.actions) > self.history:
            del self.actions[:len(self.actions) - self.history]
        if _obs.enabled():
            m = self._m()
            key = "would_act" if self.config.dry_run else "actions"
            m[key].labels(action=action).inc()
        _obs_journal.record("remediation", action=action, signal=signal,
                            dry_run=bool(self.config.dry_run), **fields)
        return rec

    # -- loop 1+2: the training gang -----------------------------------------

    def after_step(self, gang, step: int, metrics: Optional[dict] = None
                   ) -> None:
        """One committed :class:`~hetu_tpu.exec.gang.ElasticGang` step:
        consume fresh divergence verdicts, then re-evaluate the
        partial-reduce deadline.  Called by the gang's post-commit seam
        (after the step's checkpoint save, so a quarantine's storage drop
        is not immediately rewritten)."""
        if not self.config.enabled:
            return
        self._maybe_quarantine(gang, step)
        if gang.partial is not None and gang.reducer is not None:
            self._maybe_retune(step, gang.partial, gang.reducer.lags.lag,
                               actuate=gang.set_partial_deadline)

    def _maybe_quarantine(self, gang, step: int) -> None:
        det = getattr(gang, "divergence", None)
        if det is None or not self.config.quarantine:
            return
        if gang.generation != self._quarantine_gen:
            # a rescale densely renumbered the survivors (or we just
            # attached to a gang that may have rescaled before we were
            # watching): rank ids decided under the old numbering are
            # stale — reset the quarantined set so a reused index is not
            # masked, and skip findings recorded before the current
            # generation (the detector's generation_cursor), whose
            # worker fields name ranks that no longer exist as such
            self._quarantine_gen = gang.generation
            self._quarantined = set()
            self._div_cursor = max(self._div_cursor,
                                   det.generation_cursor)
        events = det.events
        while self._div_cursor < len(events):
            f = events[self._div_cursor]
            self._div_cursor += 1
            w = int(f["worker"])
            # already decided, already dead, or the LAST live worker —
            # remediation must never leave nothing to rescale.  In dry
            # run the gang never actually evicts, so earlier would-act
            # quarantines count as shadow evictions: the decision stream
            # stays the one an active controller would produce (it would
            # not quarantine both workers of a 2-gang either).
            shadow = (len(self._quarantined) if self.config.dry_run
                      else 0)
            if w in self._quarantined or not gang.can_quarantine(w) \
                    or gang.live_world - shadow < 2:
                continue
            self._quarantined.add(w)
            self._act("quarantine", "replica_divergence", step=int(step),
                      worker=w, shard=f["shard"],
                      divergent_step=int(f["step"]))
            if not self.config.dry_run:
                gang.quarantine(w)
            if self.planner is not None \
                    and getattr(gang, "planner", None) is None:
                # re-plan against the post-eviction world now (a gang
                # with its OWN attached planner re-plans at the rescale
                # instead — never both, one decision per trigger).  In
                # dry run the eviction never happened, so the surviving
                # world is computed from the shadow-quarantine count:
                # the decision stream matches an active controller's.
                survivors = gang.live_world - (len(self._quarantined)
                                               if self.config.dry_run
                                               else 0)
                self.planner.replan_for_gang(
                    gang, trigger="quarantine",
                    dry_run=self.config.dry_run, train_world=survivors)

    def _maybe_retune(self, step: int, config, lags: dict,
                      actuate) -> None:
        """The shared deadline-tuning core (in-process gang and
        per-process :class:`~hetu_tpu.exec.resilience.ResilientTrainer`
        paths): propose from the lag EWMAs, clamp, damp, act.
        ``actuate(new_deadline)`` applies it; ``config`` is the current
        :class:`~hetu_tpu.exec.partial.PartialReduceConfig`."""
        if not self.config.tune_deadline or not lags:
            return
        if self._deadline is None:
            self._deadline = float(config.deadline)
            if _obs.enabled():
                self._m()["deadline"].set(self._deadline)
        vals = sorted(float(v) for v in lags.values())
        idx = min(len(vals) - 1,
                  max(0, math.ceil(self.config.cover_fraction
                                   * len(vals)) - 1))
        proposed = config.clamp(self.config.headroom * vals[idx])
        cur = self._deadline
        if self._last_retune_step is not None and \
                step - self._last_retune_step < self.config.cooldown_steps:
            return
        if math.isfinite(cur):
            if abs(proposed - cur) <= \
                    self.config.hysteresis * max(cur, proposed):
                return
        elif not math.isfinite(proposed):
            # inf -> inf (full-barrier config, unbounded clamp): the
            # hysteresis band is inf-poisoned AND there is no change —
            # any FINITE proposal against an inf deadline always acts
            return
        self._deadline = proposed
        self._last_retune_step = int(step)
        self._act("deadline_retune", "worker_lag_ewma", step=int(step),
                  # inf (the synchronous-barrier start) has no strict-
                  # JSON form: the journal carries null, the `new` side
                  # is always the finite clamped proposal
                  old=round(cur, 6) if math.isfinite(cur) else None,
                  new=round(proposed, 6),
                  covered_lag=round(vals[idx], 6))
        if not self.config.dry_run:
            actuate(proposed)
        if _obs.enabled():
            self._m()["deadline"].set(proposed)

    def after_train_step(self, trainer, step: int,
                         metrics: Optional[dict] = None) -> None:
        """The per-process form: a
        :class:`~hetu_tpu.exec.resilience.ResilientTrainer` carrying a
        :class:`~hetu_tpu.exec.partial.PartialReducer` (the multi-process
        ``GradientBoard`` gangs) gets the same deadline loop — the
        reducer's lag EWMAs (fed by ``GradientBoard.collect`` or the
        harness) propose, and acting replaces ``reducer.config`` so the
        next ``collect(deadline_s=reducer.config.deadline)`` runs the
        tuned cut."""
        if not self.config.enabled:
            return
        red = getattr(trainer, "partial", None)
        if red is None:
            return

        def actuate(new):
            red.config = dataclasses.replace(
                red.config, deadline=float(new),
                deadline_source="controller")

        self._maybe_retune(step, red.config, red.lags.lag, actuate=actuate)

    def on_replica_lost(self, monitor, replica: int,
                        lost_count: int) -> None:
        """Serving-fleet flap quarantine (the
        :class:`~hetu_tpu.serve.fleet.failover.FailoverMonitor` seam):
        the monitor reports every ``replica_lost`` declaration with the
        replica's cumulative loss count; at ``replica_flap_threshold``
        the replica is quarantined — never restored on heartbeat
        recovery — so a hang/recover cycle that keeps repeating stops
        oscillating the placement ranking.  One decision per replica per
        monitor; dry run journals the identical ``quarantine_replica``
        decision and leaves the monitor's restore behavior untouched."""
        if not self.config.enabled or not self.config.quarantine:
            return
        if int(lost_count) < self.config.replica_flap_threshold:
            return
        decided = self._fleet_state.get(monitor)
        if decided is None:
            decided = set()
            self._fleet_state[monitor] = decided
        if replica in decided:
            return
        decided.add(replica)
        self._act("quarantine_replica", "replica_flap",
                  replica=int(replica), lost=int(lost_count))
        if not self.config.dry_run:
            monitor.quarantine(replica)

    # -- loop 3+4: the serving engine ----------------------------------------

    def on_serve_tick(self, engine) -> None:
        """One :class:`~hetu_tpu.serve.engine.ServingEngine` scheduler
        tick: latch/release the compile-storm bucket freeze and the
        SLO-burn admission shed.  Driven by the engine's injectable
        clock, so deterministic tests replay the decisions exactly."""
        if not self.config.enabled:
            return
        if self.config.freeze_buckets:
            self._maybe_freeze(engine)
        if self.config.shed:
            self._maybe_shed(engine)
        if self.config.mem_pressure:
            self._maybe_mem(engine)

    def _serve_st(self, engine) -> dict:
        st = self._serve_state.get(engine)
        if st is None:
            st = {"shed_active": False, "freeze_active": False,
                  "shed_streak": 0, "ok_streak": 0,
                  # memory-pressure latch (PR 17): mem_shed remembers
                  # whether THIS loop engaged the batcher's shed, so a
                  # release never unlatches the SLO loop's shed
                  "mem_active": False, "mem_shed": False,
                  "mem_streak": 0, "mem_ok_streak": 0,
                  # tenant-scoped latches (multi-tenant engines):
                  # tid -> {"active", "shed_streak", "ok_streak"}
                  "tenants": {}}
            self._serve_state[engine] = st
        return st

    @property
    def shed_active(self) -> bool:
        """Any driven engine currently latched shedding (global or
        tenant-scoped)."""
        return any(st["shed_active"]
                   or any(t["active"] for t in st["tenants"].values())
                   for st in self._serve_state.values())

    @property
    def freeze_active(self) -> bool:
        """Any driven engine currently latched frozen."""
        return any(st["freeze_active"]
                   for st in self._serve_state.values())

    @property
    def mem_pressure_active(self) -> bool:
        """Any driven engine currently latched on ledger memory
        pressure."""
        return any(st["mem_active"]
                   for st in self._serve_state.values())

    def _maybe_freeze(self, engine) -> None:
        st = self._serve_st(engine)
        storm = _obs_compile.get_storm()
        recent = storm.recent()
        storming = recent > storm.threshold
        if storming and not st["freeze_active"]:
            warm = sorted(engine._prefill_buckets)
            if not warm:
                # nothing is warm yet (e.g. a training-side storm hit a
                # freshly started engine): freezing "growth" would be a
                # total outage, strictly worse than compiling — defer
                # until the engine has served at least one bucket
                return
            st["freeze_active"] = True
            self._act("bucket_freeze", "compile_storm", recent=int(recent),
                      threshold=int(storm.threshold), warm_buckets=warm)
            if not self.config.dry_run:
                engine.freeze_bucket_growth = True
        elif not storming and st["freeze_active"]:
            st["freeze_active"] = False
            self._act("bucket_unfreeze", "compile_storm",
                      recent=int(recent), threshold=int(storm.threshold))
            if not self.config.dry_run:
                engine.freeze_bucket_growth = False
        if _obs.enabled():
            self._m()["freeze_active"].set(1.0 if self.freeze_active
                                           else 0.0)

    def _maybe_shed(self, engine) -> None:
        st = self._serve_st(engine)
        if getattr(engine.slo, "multi_tenant", False):
            # the scoped policy: per-tenant burn drives per-tenant
            # latches, so a flooding tenant's aggregate burn can never
            # close a victim's door.  The switch is monotone (tenant
            # windows never un-observe), so a replay flips policies at
            # the same tick.  Engines that only ever see the default
            # tenant stay on the legacy global path below, bit for bit.
            if st["shed_active"]:
                # a burn latched the GLOBAL door before the first tenant
                # was observed (a tenant request in flight at engage time
                # flips multi_tenant when it resolves).  The scoped loop
                # only ever manages per-tenant latches, and this path
                # never runs the global release again — left alone the
                # legacy latch strands every tenant shut forever.  Hand
                # the latch over: release it here (memory pressure may
                # still be holding the shared batcher latch, same rule
                # as the release below) and let the scoped streaks
                # re-engage per tenant if the burn is real.
                st["shed_active"] = False
                st["shed_streak"] = 0
                st["ok_streak"] = 0
                self._act("admission_release", "tenant_policy_switch")
                if not self.config.dry_run and not st["mem_shed"]:
                    engine.batcher.clear_shed()
            self._maybe_shed_tenants(engine, st)
            return
        pressure = float(engine.slo.shed_pressure())
        if pressure >= self.config.shed_on:
            st["shed_streak"] += 1
            st["ok_streak"] = 0
        elif pressure <= self.config.shed_off:
            st["ok_streak"] += 1
            st["shed_streak"] = 0
        else:
            # inside the hysteresis band: sustain nothing, hold the latch
            st["shed_streak"] = 0
            st["ok_streak"] = 0
        if not st["shed_active"] \
                and st["shed_streak"] >= self.config.sustain_ticks:
            st["shed_active"] = True
            self._act("admission_shed", "slo_burn",
                      pressure=round(pressure, 6),
                      sustained_ticks=int(st["shed_streak"]))
            if not self.config.dry_run:
                engine.batcher.set_shed(
                    "controller shed: sustained SLO burn (shed pressure "
                    f"{pressure:.3f} >= {self.config.shed_on})")
            if self.planner is not None:
                # sustained SLO burn: the serving tier is under-planned
                # — re-plan it (the decision journals now; the plan's
                # structural axes apply at the next fleet construction)
                self.planner.replan_for_engine(
                    engine, trigger="slo_burn",
                    dry_run=self.config.dry_run)
        elif st["shed_active"] \
                and st["ok_streak"] >= self.config.sustain_ticks:
            st["shed_active"] = False
            self._act("admission_release", "slo_burn",
                      pressure=round(pressure, 6),
                      sustained_ticks=int(st["ok_streak"]))
            # the memory loop shares the batcher's global shed latch:
            # only clear it when memory pressure is not also holding it
            if not self.config.dry_run and not st["mem_shed"]:
                engine.batcher.clear_shed()
        if _obs.enabled():
            self._m()["shed_active"].set(1.0 if self.shed_active else 0.0)

    def _maybe_shed_tenants(self, engine, st: dict) -> None:
        """The scoped shed loop: one streak/hysteresis machine per
        observed (tenant, class), same sustain discipline as the global
        path, engaging :meth:`~hetu_tpu.serve.batcher.ContinuousBatcher.
        set_tenant_shed` instead of the global latch.  Batch-class
        tenants engage at ``shed_on * batch_shed_factor`` (and release
        at the equally scaled ``shed_off``): under sustained burn the
        throughput tier is shed FIRST, and a latency-class tenant is
        shed only when its OWN windows burn at the full threshold."""
        cfg = self.config
        observed = engine.slo.observed_tenants()
        for tid in sorted(observed):
            klass = observed[tid]
            ts = st["tenants"].get(tid)
            if ts is None:
                ts = {"active": False, "shed_streak": 0, "ok_streak": 0}
                st["tenants"][tid] = ts
            factor = cfg.batch_shed_factor if klass == "batch" else 1.0
            on = cfg.shed_on * factor
            off = cfg.shed_off * factor
            pressure = float(engine.slo.tenant_shed_pressure(tid))
            if pressure >= on:
                ts["shed_streak"] += 1
                ts["ok_streak"] = 0
            elif pressure <= off:
                ts["ok_streak"] += 1
                ts["shed_streak"] = 0
            else:
                ts["shed_streak"] = 0
                ts["ok_streak"] = 0
            if not ts["active"] \
                    and ts["shed_streak"] >= cfg.sustain_ticks:
                ts["active"] = True
                reason = (f"controller shed: sustained SLO burn by "
                          f"tenant {tid} ({klass}-class, shed pressure "
                          f"{pressure:.3f} >= {on:g})")
                self._act("admission_shed", "slo_burn", tenant=tid,
                          klass=klass, pressure=round(pressure, 6),
                          sustained_ticks=int(ts["shed_streak"]))
                _obs_journal.record("tenant_shed", tenant=tid,
                                    engaged=True, reason="slo_burn",
                                    klass=klass,
                                    pressure=round(pressure, 6))
                if not cfg.dry_run:
                    engine.batcher.set_tenant_shed(tid, reason)
            elif ts["active"] and ts["ok_streak"] >= cfg.sustain_ticks:
                ts["active"] = False
                self._act("admission_release", "slo_burn", tenant=tid,
                          klass=klass, pressure=round(pressure, 6),
                          sustained_ticks=int(ts["ok_streak"]))
                _obs_journal.record("tenant_shed", tenant=tid,
                                    engaged=False, reason="slo_burn",
                                    klass=klass,
                                    pressure=round(pressure, 6))
                if not cfg.dry_run:
                    engine.batcher.clear_tenant_shed(tid)
        if _obs.enabled():
            self._m()["shed_active"].set(1.0 if self.shed_active else 0.0)

    def _maybe_mem(self, engine) -> None:
        """The ledger-backed memory loop: the installed
        :class:`~hetu_tpu.obs.memledger.MemoryLedger`'s worst-pool
        occupancy sustained at or above ``mem_on`` for ``sustain_ticks``
        ticks first defrags the engine's KV pool (reclaiming
        fragmentation is free capacity), then sheds admission if
        occupancy alone keeps the pool pinned; releases at or below
        ``mem_off`` sustained equally long.  No ledger installed means
        no signal — the loop is inert, not guessing."""
        from hetu_tpu.obs import memledger as _memledger
        led = _memledger.get_ledger()
        if led is None:
            return
        st = self._serve_st(engine)
        cfg = self.config
        pressure = float(led.memory_pressure())
        if pressure >= cfg.mem_on:
            st["mem_streak"] += 1
            st["mem_ok_streak"] = 0
        elif pressure <= cfg.mem_off:
            st["mem_ok_streak"] += 1
            st["mem_streak"] = 0
        else:
            st["mem_streak"] = 0
            st["mem_ok_streak"] = 0
        if not st["mem_active"] \
                and st["mem_streak"] >= cfg.sustain_ticks:
            st["mem_active"] = True
            moved = 0
            if not cfg.dry_run:
                moved = int(engine.pool.defrag())
            still = float(led.memory_pressure())
            action = ("memory_shed" if still >= cfg.mem_on
                      else "memory_defrag")
            self._act(action, "memory_pressure",
                      pressure=round(pressure, 6),
                      moved_pages=moved,
                      sustained_ticks=int(st["mem_streak"]))
            _obs_journal.record("memory_pressure",
                                pressure=round(pressure, 6),
                                component="kv_pool", action=action)
            if action == "memory_shed" and not cfg.dry_run:
                st["mem_shed"] = True
                engine.batcher.set_shed(
                    "controller shed: sustained memory pressure "
                    f"({pressure:.3f} >= {cfg.mem_on})")
        elif st["mem_active"] \
                and st["mem_ok_streak"] >= cfg.sustain_ticks:
            st["mem_active"] = False
            self._act("memory_release", "memory_pressure",
                      pressure=round(pressure, 6),
                      sustained_ticks=int(st["mem_ok_streak"]))
            _obs_journal.record("memory_pressure",
                                pressure=round(pressure, 6),
                                component="kv_pool",
                                action="memory_release")
            if st["mem_shed"]:
                st["mem_shed"] = False
                # the SLO loop shares this latch: leave it held if that
                # loop is still latched shedding
                if not cfg.dry_run and not st["shed_active"]:
                    engine.batcher.clear_shed()
        if _obs.enabled():
            self._m()["mem_active"].set(
                1.0 if self.mem_pressure_active else 0.0)

    def release(self) -> None:
        """Release every latch this controller actuated (admission shed,
        bucket freeze) on every engine it drove, and reset the sustain
        streaks — a departing controller must not strand an engine
        rejecting traffic with nobody left to unlatch it.  Called by
        :func:`use` on scope exit; long-lived installed controllers
        should call it when decommissioned.  Idempotent."""
        for eng in list(self._serve_state):
            st = self._serve_state[eng]
            if st["shed_active"]:
                st["shed_active"] = False
                self._act("admission_release", "controller_detach")
                if getattr(eng.batcher, "shedding", False):
                    eng.batcher.clear_shed()
            for tid, ts in st["tenants"].items():
                if ts["active"]:
                    ts["active"] = False
                    self._act("admission_release", "controller_detach",
                              tenant=tid)
                    _obs_journal.record("tenant_shed", tenant=tid,
                                        engaged=False,
                                        reason="controller_detach")
                    if eng.batcher.tenant_shed_reason(tid) is not None:
                        eng.batcher.clear_tenant_shed(tid)
                ts["shed_streak"] = 0
                ts["ok_streak"] = 0
            if st["freeze_active"]:
                st["freeze_active"] = False
                self._act("bucket_unfreeze", "controller_detach")
                if getattr(eng, "freeze_bucket_growth", False):
                    eng.freeze_bucket_growth = False
            if st["mem_active"]:
                st["mem_active"] = False
                self._act("memory_release", "controller_detach")
                _obs_journal.record("memory_pressure", pressure=0.0,
                                    component="kv_pool",
                                    action="memory_release")
                if st["mem_shed"]:
                    st["mem_shed"] = False
                    if getattr(eng.batcher, "shedding", False):
                        eng.batcher.clear_shed()
            st["shed_streak"] = 0
            st["ok_streak"] = 0
            st["mem_streak"] = 0
            st["mem_ok_streak"] = 0
        if _obs.enabled():
            m = self._m()
            m["shed_active"].set(0.0)
            m["freeze_active"].set(0.0)
            m["mem_active"].set(0.0)

    # -- read side -------------------------------------------------------------

    def summary(self) -> dict:
        """The ``/controller`` payload: policy, live latches, the tuned
        deadline (shadow value in dry run), and the newest ``history``
        decisions in journal-field form (``actions_total`` counts every
        decision ever made; the journal is the unbounded record)."""
        return {
            "installed": True,
            "config": dataclasses.asdict(self.config),
            "dry_run": bool(self.config.dry_run),
            # an inf deadline (the full-barrier start) has no strict-
            # JSON form; the payload carries null until a retune
            "deadline": (self._deadline
                         if self._deadline is None
                         or math.isfinite(self._deadline) else None),
            "shed_active": bool(self.shed_active),
            "tenant_shed_active": sorted(
                {tid for st in self._serve_state.values()
                 for tid, ts in st["tenants"].items() if ts["active"]}),
            "freeze_active": bool(self.freeze_active),
            "mem_pressure_active": bool(self.mem_pressure_active),
            "quarantined": sorted(self._quarantined),
            "actions_total": int(self.actions_total),
            "actions": list(self.actions),
        }


# --------------------------------------------------- process-wide seams

_active: Optional[RuntimeController] = None


def get_controller() -> Optional[RuntimeController]:
    return _active


def install(controller: Optional[RuntimeController]
            ) -> Optional[RuntimeController]:
    """Install ``controller`` process-wide (None uninstalls): the
    fallback the gang/serve/trainer seams consult when no controller was
    attached explicitly, and the object ``/controller`` serves."""
    global _active
    _active = controller
    return controller


@contextlib.contextmanager
def use(controller: RuntimeController):
    """Install for the block, restore the previous controller on exit —
    releasing any latch the scoped controller actuated (once it is
    uninstalled, nothing would ever unlatch a shed/frozen engine)."""
    global _active
    prev = _active
    _active = controller
    try:
        yield controller
    finally:
        _active = prev
        controller.release()


def maybe_gang_step(gang, step: int, metrics: Optional[dict] = None) -> None:
    """The :class:`~hetu_tpu.exec.gang.ElasticGang` post-commit seam:
    one attribute + one global load and a branch when no controller is
    attached or installed — the obs overhead contract."""
    c = gang.controller if gang.controller is not None else _active
    if c is None:
        return
    c.after_step(gang, step, metrics)


def maybe_serve_tick(engine) -> None:
    """The :class:`~hetu_tpu.serve.engine.ServingEngine` per-tick seam
    (same disabled-cost contract as :func:`maybe_gang_step`)."""
    c = engine.controller if engine.controller is not None else _active
    if c is None:
        return
    c.on_serve_tick(engine)


def maybe_after_train_step(trainer, step: int,
                           metrics: Optional[dict] = None) -> None:
    """The :class:`~hetu_tpu.exec.resilience.ResilientTrainer` post-step
    seam: one global load + branch when no controller is installed."""
    c = _active
    if c is None:
        return
    c.after_train_step(trainer, step, metrics)


# ------------------------------------------------------------ the smoke

def controller_smoke(steps: int = 16, seed: int = 0) -> dict:
    """Seeded 2-worker in-process deadline-retune smoke — the closed
    loop end to end on a tiny MLP gang: healthy early steps tighten the
    deadline toward its clamp floor, an injected mid-run stall relaxes
    it back.  Deterministic (two calls return identical dicts); run by
    the tier-1 controller smoke test.  Journals into a private journal
    and meters into a private registry, so it never pollutes the
    caller's event stream or the process ``hetu_ctrl_*`` series."""
    import tempfile

    import numpy as np

    from hetu_tpu.core import set_random_seed
    from hetu_tpu.exec import faults as _faults
    from hetu_tpu.exec.executor import Trainer
    from hetu_tpu.exec.gang import ElasticGang
    from hetu_tpu.exec.partial import PartialReduceConfig
    from hetu_tpu.models import MLP
    from hetu_tpu.optim import SGDOptimizer
    from hetu_tpu.ops import softmax_cross_entropy_sparse

    set_random_seed(seed)
    model = MLP((8, 16, 3))

    def loss_fn(model, batch, key):
        logits = model(batch["x"])
        return softmax_cross_entropy_sparse(logits, batch["y"]).mean(), {}

    trainer = Trainer(model, SGDOptimizer(0.1), loss_fn, donate=False)
    rng = np.random.default_rng(seed)
    data = []
    for _ in range(steps):
        x = rng.standard_normal((16, 8)).astype(np.float32)
        data.append({"x": x, "y": (x[:, 0] > 0).astype(np.int32)})
    cfg = PartialReduceConfig(deadline=4.0, tau=4, min_deadline=0.5,
                              max_deadline=8.0)
    ctrl = RuntimeController(
        ControllerConfig(cooldown_steps=2, quarantine=False, shed=False,
                         freeze_buckets=False),
        registry=_obs.MetricsRegistry())
    with tempfile.TemporaryDirectory() as d, \
            _obs_journal.use(_obs_journal.EventJournal(clock=lambda: 0.0)):
        gang = ElasticGang(trainer, d, world_size=2,
                           data_fn=lambda s: data[s - 1],
                           global_batch_size=16, seed=seed, save_every=0,
                           partial=cfg, controller=ctrl)
        plan = _faults.FaultPlan(
            [(steps // 2, _faults.Fault("worker_stall", worker=1,
                                        arg=4.0))])
        with _faults.inject(plan):
            gang.run_until(steps)
    by_action: dict = {}
    for a in ctrl.actions:
        by_action[a["action"]] = by_action.get(a["action"], 0) + 1
    return {"actions": len(ctrl.actions), "by_action": by_action,
            "final_deadline": round(float(gang.partial.deadline), 6),
            "deadline_source": gang.partial.deadline_source,
            "clamp": [cfg.min_deadline, cfg.max_deadline]}
