"""Closed-loop remediation: the controller that acts on the telemetry
plane, under deterministic chaos.

The acceptance bar is the ROADMAP capstone's: a 4-worker gang under a
seeded pareto-stall + ``bit_flip`` fault plan auto-tunes its
partial-reduce deadline inside the policy clamp, quarantines the
divergent replica (lease eviction + rescale) and recovers its shard
from the ring neighbor's replica instead of losing the run — and the
controller's action sequence, the journal, and the recovered goodput
buckets are bitwise-identical across two same-seed runs.  A clean run
journals ZERO ``remediation`` events; dry-run mode journals identical
``would_act`` decisions while actuating nothing.  The serving loops
(sustained-SLO-burn shedding, compile-storm bucket freeze) replay the
same way on the engine's injectable clock.
"""

import json
import os
import time
import urllib.request

import numpy as np
import pytest

from hetu_tpu.core import set_random_seed
from hetu_tpu.exec import (ElasticGang, PartialReduceConfig, Trainer,
                           faults)
from hetu_tpu.exec import controller as ctrl_mod
from hetu_tpu.exec.controller import (ControllerConfig, RuntimeController,
                                      controller_smoke)
from hetu_tpu.models import MLP
from hetu_tpu.obs import compile as obs_compile
from hetu_tpu.obs import divergence as obs_divergence
from hetu_tpu.obs import journal as obs_journal
from hetu_tpu.obs import registry as obs_registry
from hetu_tpu.obs.goodput import GoodputMeter
from hetu_tpu.optim import SGDOptimizer
from hetu_tpu.ops import softmax_cross_entropy_sparse

pytestmark = [pytest.mark.controller, pytest.mark.chaos]


# ---------------------------------------------------------------- helpers

def make_trainer():
    set_random_seed(0)
    model = MLP((8, 16, 3))

    def loss_fn(model, batch, key):
        logits = model(batch["x"])
        return softmax_cross_entropy_sparse(logits, batch["y"]).mean(), {}

    return Trainer(model, SGDOptimizer(0.1), loss_fn, donate=False)


def make_data(n=40, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        x = rng.standard_normal((16, 8)).astype(np.float32)
        out.append({"x": x, "y": (x[:, 0] > 0).astype(np.int32)})
    return out


def norm_events(jr):
    """Journal events minus wall-clock noise (the test_gang/test_partial
    normalization: checkpoint durations and tmp-dir prefixes vary, the
    CRCs and every decision field must not)."""
    out = []
    for e in jr.events:
        e = {k: v for k, v in e.items() if k != "ts"}
        if e["kind"] == "checkpoint_saved":
            e.pop("duration_s", None)
            e["path"] = "/".join(e["path"].split(os.sep)[-2:])
        out.append(e)
    return out


@pytest.fixture
def journal():
    j = obs_journal.EventJournal(clock=lambda: 0.0)
    obs_journal.set_journal(j)
    yield j
    obs_journal.set_journal(None)


class VClock:
    """Injectable virtual clock for the serving-loop tests."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def gang_controller_cfg(**kw):
    """The training-side policy the gang tests share (serve loops off)."""
    base = dict(cooldown_steps=3, shed=False, freeze_buckets=False)
    base.update(kw)
    return ControllerConfig(**base)


def build_gang(tmpdir, data, *, ctrl, world=4, deadline=2.0,
               goodput=None, numerics=True):
    tr = make_trainer()
    return ElasticGang(
        tr, str(tmpdir), world_size=world,
        data_fn=lambda s: data[s - 1], global_batch_size=16, seed=0,
        save_every=2,
        partial=PartialReduceConfig(deadline=deadline, tau=4,
                                    min_deadline=0.5, max_deadline=6.0),
        numerics=numerics, goodput=goodput, controller=ctrl)


# THE seeded chaos schedule of the acceptance tests: heavy-tailed pareto
# stalls plus one post-reduce bit flip on rank 2 at step 6.
def chaos_plan():
    stalls = faults.FaultPlan.random(
        7, 14, kinds=("worker_stall",), rate=0.2, n_workers=4,
        stall_steps=("pareto", 1.5, 2.0))
    events = list(stalls._events) + [
        (6, faults.Fault("bit_flip", worker=2, arg=5))]
    return faults.FaultPlan(events)


# ----------------------------------------------------------- the policy

class TestControllerConfig:
    def test_validation(self):
        with pytest.raises(ValueError, match="headroom"):
            ControllerConfig(headroom=0.0)
        with pytest.raises(ValueError, match="cover_fraction"):
            ControllerConfig(cover_fraction=1.5)
        with pytest.raises(ValueError, match="hysteresis"):
            ControllerConfig(hysteresis=-0.1)
        with pytest.raises(ValueError, match="shed_off"):
            ControllerConfig(shed_on=0.2, shed_off=0.5)
        with pytest.raises(ValueError, match="shed_on"):
            # 0 would latch shedding on an idle engine forever
            ControllerConfig(shed_on=0.0, shed_off=0.0)
        with pytest.raises(ValueError, match="sustain_ticks"):
            ControllerConfig(sustain_ticks=0)

    def test_from_env(self, monkeypatch):
        monkeypatch.setenv("HETU_TPU_CTRL_DRY_RUN", "true")
        monkeypatch.setenv("HETU_TPU_CTRL_HEADROOM", "2.5")
        monkeypatch.setenv("HETU_TPU_CTRL_COOLDOWN_STEPS", "7")
        monkeypatch.setenv("HETU_TPU_CTRL_SHED", "0")
        cfg = ControllerConfig.from_env()
        assert cfg.dry_run is True and cfg.headroom == 2.5
        assert cfg.cooldown_steps == 7 and cfg.shed is False
        # explicit overrides win over the environment
        assert ControllerConfig.from_env(headroom=1.0).headroom == 1.0

    def test_partial_config_clamp_and_source(self):
        cfg = PartialReduceConfig(deadline=2.0, min_deadline=0.5,
                                  max_deadline=4.0)
        assert cfg.clamp(0.1) == 0.5
        assert cfg.clamp(9.0) == 4.0
        assert cfg.clamp(1.7) == 1.7
        assert cfg.deadline_source == "static"
        with pytest.raises(ValueError, match="max_deadline"):
            PartialReduceConfig(min_deadline=2.0, max_deadline=1.0)
        with pytest.raises(ValueError, match="deadline_source"):
            PartialReduceConfig(deadline_source="magic")


# ------------------------------------------- deadline retune (tier-1)

class TestDeadlineRetune:
    def test_smoke_is_deterministic_and_tunes_within_clamp(self):
        s1 = controller_smoke()
        s2 = controller_smoke()
        assert s1 == s2, "the 2-worker retune smoke must replay bitwise"
        assert s1["actions"] >= 1
        assert s1["by_action"].get("deadline_retune", 0) >= 1
        lo, hi = s1["clamp"]
        assert lo <= s1["final_deadline"] <= hi
        assert s1["deadline_source"] == "controller"

    def test_partial_step_journal_distinguishes_tuned_cuts(self, tmp_path,
                                                           journal):
        data = make_data()
        ctrl = RuntimeController(gang_controller_cfg(quarantine=False))
        g = build_gang(tmp_path, data, ctrl=ctrl, world=2, numerics=False)
        g.run_until(6)
        steps = journal.of_kind("partial_step")
        assert steps, "partial cuts must journal"
        retunes = [a for a in ctrl.actions
                   if a["action"] == "deadline_retune"]
        assert retunes, "a healthy gang must tighten its deadline"
        first = retunes[0]["step"]
        by_step = {e["step"]: e["deadline_source"] for e in steps}
        # the cut at the retune step itself still ran under the old
        # config (the controller acts post-commit); later cuts are tuned
        assert all(src == "static" for s, src in by_step.items()
                   if s <= first)
        assert all(src == "controller" for s, src in by_step.items()
                   if s > first)
        assert g.partial.deadline_source == "controller"

    def test_clamp_cooldown_and_hysteresis_prevent_oscillation(
            self, tmp_path, journal):
        data = make_data()
        ctrl = RuntimeController(gang_controller_cfg(quarantine=False))
        g = build_gang(tmp_path, data, ctrl=ctrl, world=4)
        plan = faults.FaultPlan.random(
            11, 20, kinds=("worker_stall",), rate=0.3, n_workers=4,
            stall_steps=("pareto", 1.5, 2.0))
        with faults.inject(plan):
            g.run_until(20)
        retunes = [a for a in ctrl.actions
                   if a["action"] == "deadline_retune"]
        assert retunes
        for a in retunes:
            assert 0.5 <= a["new"] <= 6.0, "clamp must hold"
        steps = [a["step"] for a in retunes]
        gaps = [b - a for a, b in zip(steps, steps[1:])]
        assert all(gap >= 3 for gap in gaps), \
            f"cooldown of 3 steps violated: retunes at {steps}"
        # damped: the controller acts on sustained shifts, not per step
        assert len(retunes) <= 20 // 3 + 1

    def test_resilient_trainer_seam_tunes_reducer_config(self, journal):
        """The per-process path: an installed controller retunes a
        ResilientTrainer's PartialReducer deadline from its lag EWMAs
        (the multi-process GradientBoard gangs' loop)."""
        import tempfile

        from hetu_tpu.exec import PartialReducer, ResilientTrainer

        tr = make_trainer()
        red = PartialReducer(PartialReduceConfig(
            deadline=3.0, min_deadline=0.5, max_deadline=6.0))
        # a healthy board: every rank arrives instantly
        for _ in range(4):
            red.lags.observe({0: 0.0, 1: 0.1})
        data = make_data(8)
        ctrl = RuntimeController(gang_controller_cfg(
            cooldown_steps=1, quarantine=False))
        with tempfile.TemporaryDirectory() as d, ctrl_mod.use(ctrl):
            rt = ResilientTrainer(tr, ckpt_dir=d, save_every=0,
                                  partial=red)
            rt.step(data[0])
        retunes = [a for a in ctrl.actions
                   if a["action"] == "deadline_retune"]
        assert retunes and red.config.deadline_source == "controller"
        assert red.config.deadline < 3.0  # tightened toward the floor

    def test_infinite_baseline_deadline_still_tunes(self, tmp_path,
                                                    journal):
        """deadline=inf is the documented synchronous-barrier baseline:
        the inf-poisoned hysteresis band must not dead-band the tuner
        forever, and the inf shadow value must never leak Infinity into
        the strict-JSON surfaces."""
        data = make_data()
        ctrl = RuntimeController(gang_controller_cfg(quarantine=False))
        tr = make_trainer()
        g = ElasticGang(tr, str(tmp_path), world_size=2,
                        data_fn=lambda s: data[s - 1],
                        global_batch_size=16, seed=0, save_every=0,
                        partial=PartialReduceConfig(
                            deadline=float("inf"), tau=4,
                            min_deadline=0.5, max_deadline=6.0),
                        controller=ctrl)
        g.run_until(6)
        retunes = [a for a in ctrl.actions
                   if a["action"] == "deadline_retune"]
        assert retunes, "an inf baseline must still tighten"
        assert retunes[0]["old"] is None  # inf has no strict-JSON form
        assert 0.5 <= retunes[0]["new"] <= 6.0
        assert g.partial.deadline <= 6.0
        json.dumps(ctrl.summary(), allow_nan=False)  # strict-JSON clean

    def test_no_partial_no_retune(self, tmp_path, journal):
        """A synchronous-barrier gang has no deadline to tune: the
        controller must not act (and must not crash)."""
        data = make_data()
        ctrl = RuntimeController(gang_controller_cfg())
        tr = make_trainer()
        g = ElasticGang(tr, str(tmp_path), world_size=2,
                        data_fn=lambda s: data[s - 1],
                        global_batch_size=16, seed=0, save_every=0,
                        controller=ctrl)
        g.run_until(4)
        assert ctrl.actions == []
        assert journal.of_kind("remediation") == []


# ------------------------------------------------ quarantine (tier-1)

class TestQuarantine:
    def run(self, tmpdir, dry=False):
        obs_divergence.reset_detected()
        data = make_data()
        j = obs_journal.EventJournal(clock=lambda: 0.0)
        obs_journal.set_journal(j)
        try:
            ctrl = RuntimeController(gang_controller_cfg(
                dry_run=dry, tune_deadline=False))
            g = build_gang(tmpdir, data, ctrl=ctrl)
            plan = faults.FaultPlan(
                [(6, faults.Fault("bit_flip", worker=2, arg=5))])
            with faults.inject(plan):
                g.run_until(12)
            assert not plan.remaining()
            return g, j, ctrl
        finally:
            obs_journal.set_journal(None)

    def test_divergence_quarantines_and_restores_from_ring(self, tmp_path):
        g, j, ctrl = self.run(tmp_path / "a")
        div, = j.of_kind("replica_divergence")
        assert (div["step"], div["worker"]) == (6, 2)
        rem, = j.of_kind("remediation")
        assert rem["action"] == "quarantine" and rem["worker"] == 2
        assert rem["signal"] == "replica_divergence"
        assert rem["dry_run"] is False
        lost, = j.of_kind("worker_lost")
        assert lost["rank"] == 2
        resc, = j.of_kind("gang_rescale")
        assert (resc["old_world"], resc["new_world"]) == (4, 3)
        # the quarantined replica's storage was dropped: its shard came
        # back from the ring predecessor's replica, not a lost run
        restore, = j.of_kind("shard_restore")
        assert restore["rank"] == 2 and restore["from_rank"] == 1
        assert g.world_size == 3 and g.step_count == 12
        # ordered: verdict -> decision -> eviction -> restore (inside the
        # rescale's manifest compose) -> the committed rescale record
        seqs = [j.of_kind(k)[0]["seq"] for k in
                ("replica_divergence", "remediation", "worker_lost",
                 "shard_restore", "gang_rescale")]
        assert seqs == sorted(seqs)

    def test_completes_at_matched_loss(self, tmp_path):
        g, _j, _c = self.run(tmp_path / "b")
        obs_divergence.reset_detected()
        data = make_data()
        clean = build_gang(tmp_path / "clean", data,
                           ctrl=None, numerics=False)
        clean.run_until(12)
        # the quarantined run must converge like the clean one — the
        # 4->3 rescale changes the reduction slightly, so matched means
        # close, not bitwise
        assert np.isfinite(g.losses_by_step[12])
        assert abs(g.losses_by_step[12] - clean.losses_by_step[12]) < 0.15

    def test_reused_rank_index_after_rescale_still_quarantines(
            self, tmp_path):
        """A rescale densely renumbers survivors, so rank ids recycle:
        a second divergence on the REUSED index (a different physical
        replica) must quarantine too — neither the controller's
        quarantined-set nor the detector's dedupe keys may go stale
        across the generation bump."""
        obs_divergence.reset_detected()
        data = make_data()
        j = obs_journal.EventJournal(clock=lambda: 0.0)
        obs_journal.set_journal(j)
        try:
            ctrl = RuntimeController(gang_controller_cfg(
                tune_deadline=False))
            g = build_gang(tmp_path, data, ctrl=ctrl)
            plan = faults.FaultPlan(
                [(4, faults.Fault("bit_flip", worker=2, arg=5)),
                 # after the 4->3 rescale, new rank 2 is old rank 3
                 (9, faults.Fault("bit_flip", worker=2, arg=9))])
            with faults.inject(plan):
                g.run_until(12)
            assert not plan.remaining()
            quars = [a for a in ctrl.actions
                     if a["action"] == "quarantine"]
            assert [q["worker"] for q in quars] == [2, 2]
            assert g.world_size == 2
            assert len(j.of_kind("gang_rescale")) == 2
        finally:
            obs_journal.set_journal(None)

    def test_never_quarantines_the_last_live_worker(self, tmp_path):
        """Remediation must never make it worse: with one worker already
        dead, quarantining the sole survivor would leave nothing to
        rescale — the controller must decline and let the run degrade
        to world 1 instead of raising GangError."""
        obs_divergence.reset_detected()
        data = make_data()
        j = obs_journal.EventJournal(clock=lambda: 0.0)
        obs_journal.set_journal(j)
        try:
            ctrl = RuntimeController(gang_controller_cfg(
                tune_deadline=False))
            g = build_gang(tmp_path, data, ctrl=ctrl, world=2)
            plan = faults.FaultPlan(
                [(4, faults.Fault("worker_kill", worker=0)),
                 (4, faults.Fault("bit_flip", worker=1, arg=5))])
            with faults.inject(plan):
                g.run_until(8)
            assert g.world_size == 1 and g.step_count == 8
            assert all(a["action"] != "quarantine" for a in ctrl.actions)
        finally:
            obs_journal.set_journal(None)

    def test_stale_pre_attach_findings_are_not_misapplied(self, tmp_path):
        """Divergence findings recorded under a previous generation's
        rank numbering must not be applied to the renumbered gang: a
        controller attached after a rescale skips the backlog (the
        detector's generation_cursor) but still acts on fresh verdicts."""
        obs_divergence.reset_detected()
        data = make_data()
        j = obs_journal.EventJournal(clock=lambda: 0.0)
        obs_journal.set_journal(j)
        try:
            g = build_gang(tmp_path, data, ctrl=None)
            plan = faults.FaultPlan(
                [(3, faults.Fault("bit_flip", worker=1, arg=5)),
                 (4, faults.Fault("worker_kill", worker=0))])
            with faults.inject(plan):
                g.run_until(6)   # verdict on OLD rank 1, then 4->3
            assert g.world_size == 3 and len(g.divergence.events) == 1
            ctrl = RuntimeController(gang_controller_cfg(
                tune_deadline=False))
            g.controller = ctrl
            with faults.inject(faults.FaultPlan(
                    [(8, faults.Fault("bit_flip", worker=1, arg=9))])):
                g.run_until(10)
            quars = [a for a in ctrl.actions
                     if a["action"] == "quarantine"]
            # exactly the FRESH verdict acted on — the stale rank-1
            # finding from generation 0 never quarantined the healthy
            # replica now numbered 1
            assert [(q["worker"], q["divergent_step"]) for q in quars] \
                == [(1, 8)]
            assert g.world_size == 2
        finally:
            obs_journal.set_journal(None)

    def test_dry_run_counts_shadow_evictions(self, tmp_path):
        """Dry run must not overstate what an active controller would
        do: with both workers of a 2-gang diverging, an active
        controller quarantines one and declines the other (last live
        worker) — the would_act stream must decide exactly the same."""
        for tag, dry in (("active", False), ("dry", True)):
            obs_divergence.reset_detected()
            data = make_data()
            j = obs_journal.EventJournal(clock=lambda: 0.0)
            obs_journal.set_journal(j)
            try:
                ctrl = RuntimeController(gang_controller_cfg(
                    dry_run=dry, tune_deadline=False))
                g = build_gang(tmp_path / tag, data, ctrl=ctrl, world=2)
                plan = faults.FaultPlan(
                    [(4, faults.Fault("bit_flip", worker=0, arg=5)),
                     (4, faults.Fault("bit_flip", worker=1, arg=7))])
                with faults.inject(plan):
                    g.run_until(8)
                quars = [a["worker"] for a in ctrl.actions
                         if a["action"] == "quarantine"]
                assert len(quars) == 1, (tag, quars)
            finally:
                obs_journal.set_journal(None)

    def test_dry_run_decides_but_does_not_actuate(self, tmp_path):
        g, j, ctrl = self.run(tmp_path / "d1", dry=True)
        rem, = j.of_kind("remediation")
        assert rem["dry_run"] is True and rem["worker"] == 2
        # nothing actuated: no eviction, no rescale, full gang survives
        assert g.world_size == 4
        assert j.of_kind("worker_lost") == []
        assert j.of_kind("gang_rescale") == []
        assert j.of_kind("shard_restore") == []
        # and two same-seed dry runs decide identically
        _g2, j2, _c2 = self.run(tmp_path / "d2", dry=True)
        assert json.dumps(norm_events(j), sort_keys=True) == \
            json.dumps(norm_events(j2), sort_keys=True)


# ------------------------------------- the chaos acceptance bar (slow)

@pytest.mark.slow
class TestChaosAcceptance:
    def run(self, tmpdir, dry=False):
        obs_divergence.reset_detected()
        data = make_data()
        j = obs_journal.EventJournal(clock=lambda: 0.0)
        obs_journal.set_journal(j)
        try:
            ctrl = RuntimeController(gang_controller_cfg(dry_run=dry))
            meter = GoodputMeter(registry=obs_registry.MetricsRegistry())
            g = build_gang(tmpdir, data, ctrl=ctrl, goodput=meter)
            with faults.inject(chaos_plan()):
                g.run_until(14)
            return g, j, ctrl, meter
        finally:
            obs_journal.set_journal(None)

    def test_controller_acts_and_replays_bitwise(self, tmp_path):
        g1, j1, c1, m1 = self.run(tmp_path / "r1")
        g2, j2, c2, m2 = self.run(tmp_path / "r2")
        # the controller both tuned and quarantined
        kinds = {a["action"] for a in c1.actions}
        assert "deadline_retune" in kinds and "quarantine" in kinds
        quar = [a for a in c1.actions if a["action"] == "quarantine"]
        assert quar[0]["worker"] == 2  # the bit-flipped rank, exactly
        assert any(e["kind"] == "shard_restore" and e["rank"] == 2
                   for e in j1.events)
        # deadline stayed inside the clamp through the whole run
        for a in c1.actions:
            if a["action"] == "deadline_retune":
                assert 0.5 <= a["new"] <= 6.0
        # bitwise acceptance: action sequence, full journal, recovered
        # goodput buckets, final parameters
        assert c1.actions == c2.actions
        assert json.dumps(norm_events(j1), sort_keys=True) == \
            json.dumps(norm_events(j2), sort_keys=True)
        s1, s2 = m1.snapshot(), m2.snapshot()
        assert s1["totals"] == s2["totals"]
        assert s1["straggler_wait_by_worker"] == \
            s2["straggler_wait_by_worker"]
        assert np.array_equal(
            np.asarray(g1.trainer.state.model.layers[0].w),
            np.asarray(g2.trainer.state.model.layers[0].w))
        assert g1.losses_by_step == g2.losses_by_step

    def test_dry_run_journals_identical_would_act(self, tmp_path):
        g1, j1, c1, _m1 = self.run(tmp_path / "d1", dry=True)
        g2, j2, c2, _m2 = self.run(tmp_path / "d2", dry=True)
        assert c1.actions and all(a["dry_run"] for a in c1.actions)
        assert c1.actions == c2.actions
        assert json.dumps(norm_events(j1), sort_keys=True) == \
            json.dumps(norm_events(j2), sort_keys=True)
        # actuated nothing: static deadline, full world, no evictions
        assert g1.partial.deadline_source == "static"
        assert g1.partial.deadline == 2.0
        assert g1.world_size == 4
        assert j1.of_kind("worker_lost") == []

    def test_clean_run_journals_zero_remediation(self, tmp_path):
        obs_divergence.reset_detected()
        data = make_data()
        j = obs_journal.EventJournal(clock=lambda: 0.0)
        obs_journal.set_journal(j)
        try:
            ctrl = RuntimeController(gang_controller_cfg(
                tune_deadline=False))
            g = build_gang(tmp_path, data, ctrl=ctrl)
            g.run_until(10)
            assert j.of_kind("remediation") == []
            assert ctrl.actions == []
            assert g.world_size == 4
        finally:
            obs_journal.set_journal(None)


# --------------------------------------------------- the serving loops

def make_engine(clock, controller=None, queue_depth=64, **kw):
    from hetu_tpu.models.gpt import GPT, GPTConfig
    from hetu_tpu.serve import ServingEngine

    set_random_seed(0)
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=64)
    return ServingEngine(GPT(cfg), num_slots=2, page_size=4, seed=0,
                         clock=clock, controller=controller,
                         queue_depth=queue_depth, **kw)


class TestServeControls:
    def serve_cfg(self, **kw):
        base = dict(sustain_ticks=2, shed_on=0.9, shed_off=0.1,
                    tune_deadline=False, quarantine=False)
        base.update(kw)
        return ControllerConfig(**base)

    def test_sustained_burn_sheds_then_releases(self, journal):
        clk = VClock()
        ctrl = RuntimeController(self.serve_cfg(freeze_buckets=False))
        eng = make_engine(clk, controller=ctrl)
        reg = obs_registry.get_registry()
        s0 = reg.snapshot()
        # one request that ages a full second in the queue violates
        # every default target -> both burn windows light up
        h = eng.submit([1, 2, 3], max_new_tokens=2)
        clk.t += 1.0
        eng.run_until_idle()
        assert h.status == "completed"
        eng.step()
        assert not ctrl.shed_active, "one tick must not shed (sustain=2)"
        eng.step()
        assert ctrl.shed_active and eng.batcher.shedding
        shed_rec = [a for a in ctrl.actions
                    if a["action"] == "admission_shed"]
        assert shed_rec and shed_rec[0]["pressure"] >= 0.9
        # capacity-gated submit rejects with a distinguishable error
        h2 = eng.submit([1, 2, 3], max_new_tokens=2)
        assert h2.status == "rejected"
        assert "controller shed" in h2.error
        d = reg.delta(reg.snapshot(), s0)
        assert d.get('hetu_serve_shed_total'
                     '{reason="controller",tenant="default"}') == 1
        assert [e["reason"] for e in journal.of_kind("shed")] == \
            ["controller"]
        # burn recovers once the windows drain -> release, then serve
        clk.t += 700.0
        eng.step()
        eng.step()
        assert not ctrl.shed_active and not eng.batcher.shedding
        assert any(a["action"] == "admission_release"
                   for a in ctrl.actions)
        h3 = eng.submit([1, 2, 3], max_new_tokens=2)
        eng.run_until_idle()
        assert h3.status == "completed"

    def test_admission_shed_is_public_and_catchable_as_queue_full(self):
        from hetu_tpu.serve import AdmissionQueueFull, AdmissionShed
        assert issubclass(AdmissionShed, AdmissionQueueFull)

    def test_queue_full_is_counted_distinguishably(self, journal):
        clk = VClock()
        eng = make_engine(clk, queue_depth=1)
        reg = obs_registry.get_registry()
        s0 = reg.snapshot()
        eng.submit([1, 2, 3], max_new_tokens=2)
        h2 = eng.submit([1, 2, 3], max_new_tokens=2)
        assert h2.status == "rejected" and "depth limit" in h2.error
        d = reg.delta(reg.snapshot(), s0)
        assert d.get('hetu_serve_shed_total'
                     '{reason="queue_full",tenant="default"}') == 1
        shed, = journal.of_kind("shed")
        assert shed["reason"] == "queue_full"
        eng.run_until_idle()

    def test_compile_storm_freezes_bucket_growth(self, journal):
        clk = VClock()
        obs_compile.configure_storm(
            obs_compile.StormDetector(threshold=2, window_s=50.0,
                                      clock=clk))
        ctrl = RuntimeController(self.serve_cfg(shed=False))
        eng = make_engine(clk, controller=ctrl)
        # warm bucket 8
        h = eng.submit([1, 2, 3], max_new_tokens=2)
        eng.run_until_idle()
        assert h.status == "completed"
        # the seeded fault plan floods the storm detector at the next tick
        plan = faults.FaultPlan(
            [(1, faults.Fault("compile_storm", arg=3))])
        with faults.inject(plan):
            eng.step()
        assert not plan.remaining()
        assert ctrl.freeze_active and eng.freeze_bucket_growth
        assert any(a["action"] == "bucket_freeze" for a in ctrl.actions)
        # a prompt needing a NEW bucket is shed; a warm bucket serves on
        h2 = eng.submit(list(range(1, 11)), max_new_tokens=2)  # bucket 16
        assert h2.status == "rejected" and "frozen" in h2.error
        assert any(e["reason"] == "bucket_freeze"
                   for e in journal.of_kind("shed"))
        h3 = eng.submit([4, 5], max_new_tokens=2)               # bucket 8
        eng.run_until_idle()
        assert h3.status == "completed"
        # the storm clears with its window -> growth unfreezes
        clk.t += 100.0
        eng.step()
        assert not ctrl.freeze_active and not eng.freeze_bucket_growth
        assert any(a["action"] == "bucket_unfreeze" for a in ctrl.actions)
        h4 = eng.submit(list(range(1, 11)), max_new_tokens=2)
        eng.run_until_idle()
        assert h4.status == "completed"

    def test_freeze_defers_until_a_bucket_is_warm(self, journal):
        """A storm hitting a freshly started engine (e.g. training-side
        recompiles tripping the shared detector) must not freeze an
        engine with zero warm buckets — that would shed 100% of traffic,
        a worse outage than compiling."""
        clk = VClock()
        obs_compile.configure_storm(
            obs_compile.StormDetector(threshold=2, window_s=50.0,
                                      clock=clk))
        ctrl = RuntimeController(self.serve_cfg(shed=False))
        eng = make_engine(clk, controller=ctrl)
        for _ in range(3):
            obs_compile.get_storm().note("train.step")
        eng.step()
        assert not ctrl.freeze_active and not eng.freeze_bucket_growth
        h = eng.submit([1, 2, 3], max_new_tokens=2)   # warms bucket 8
        eng.run_until_idle()
        assert h.status == "completed"
        eng.step()   # storm still in-window, now one bucket is warm
        assert ctrl.freeze_active and eng.freeze_bucket_growth
        freeze, = [a for a in ctrl.actions
                   if a["action"] == "bucket_freeze"]
        assert freeze["warm_buckets"] == [8]

    def test_per_engine_latches_one_controller_two_engines(self, journal):
        """One installed controller driving two engines: the idle
        engine's low-pressure ticks must neither release the overloaded
        engine's shed latch nor pollute its sustain streak."""
        clk = VClock()
        ctrl = RuntimeController(self.serve_cfg(freeze_buckets=False))
        hot = make_engine(clk, controller=ctrl)
        idle = make_engine(clk, controller=ctrl)
        h = hot.submit([1, 2, 3], max_new_tokens=2)
        clk.t += 1.0
        hot.run_until_idle()
        assert h.status == "completed"
        # interleave: the idle engine ticks between the hot one's —
        # per-engine streaks mean the hot engine still latches
        for _ in range(3):
            hot.step()
            idle.step()
        assert hot.batcher.shedding and not idle.batcher.shedding
        # many more idle-engine ticks: they must not release HOT's latch
        for _ in range(5):
            idle.step()
        assert hot.batcher.shedding
        assert ctrl.shed_active   # the any-engine aggregate
        # hot engine's own windows drain -> its own ticks release it
        clk.t += 700.0
        hot.step()
        hot.step()
        assert not hot.batcher.shedding and not ctrl.shed_active

    def test_detaching_the_controller_releases_its_latches(self, journal):
        """A controller leaving scope (use() exit / decommission) must
        release the latches it actuated — nothing else would ever call
        clear_shed, stranding the engine rejecting traffic forever."""
        clk = VClock()
        ctrl = RuntimeController(self.serve_cfg(freeze_buckets=False))
        eng = make_engine(clk)
        with ctrl_mod.use(ctrl):
            eng.controller = None   # drive via the installed seam
            h = eng.submit([1, 2, 3], max_new_tokens=2)
            clk.t += 1.0
            eng.run_until_idle()
            eng.step()
            eng.step()
            assert ctrl.shed_active and eng.batcher.shedding
        assert not ctrl.shed_active and not eng.batcher.shedding
        assert any(a["action"] == "admission_release"
                   and a["signal"] == "controller_detach"
                   for a in ctrl.actions)
        h2 = eng.submit([1, 2, 3], max_new_tokens=2)
        eng.run_until_idle()
        assert h.status == "completed" and h2.status == "completed"

    def test_policy_switch_releases_stranded_global_latch(self, journal):
        """Regression: a global shed latch engaged while the engine was
        still single-tenant must be RELEASED when the SLO plane flips
        multi-tenant (a tenant request in flight at engage time flips it
        on completion).  The scoped loop only manages per-tenant
        latches, so without the hand-over the legacy global latch
        strands every tenant shut forever — no release path ever runs
        again."""
        from hetu_tpu.serve.tenant import Tenant, TenantPolicy
        clk = VClock()
        ctrl = RuntimeController(self.serve_cfg(freeze_buckets=False))
        eng = make_engine(clk, controller=ctrl, tenants=TenantPolicy(
            [Tenant(id="acme", klass="latency")]))
        # a default request that ages a full second (the burn) plus a
        # long-running TENANT request still decoding when the latch
        # engages
        h1 = eng.submit([1, 2, 3], max_new_tokens=2)
        h2 = eng.submit([4, 5, 6], max_new_tokens=12, tenant="acme")
        clk.t += 1.0
        for _ in range(50):
            if h1.status == "completed":
                break
            eng.step()
        assert h1.status == "completed" and h2.status is None
        # default-only completions so far: the GLOBAL path latches
        eng.step()
        eng.step()
        assert not eng.slo.multi_tenant
        assert ctrl.shed_active and eng.batcher.shedding
        # the in-flight tenant request resolves -> the SLO plane goes
        # multi-tenant mid-latch
        for _ in range(50):
            if h2.status == "completed":
                break
            eng.step()
        assert h2.status == "completed" and eng.slo.multi_tenant
        eng.step()  # first scoped tick: the stranded latch hands over
        assert not eng.batcher.shedding, \
            "policy switch stranded the global admission latch"
        assert any(a["action"] == "admission_release"
                   and a["signal"] == "tenant_policy_switch"
                   for a in ctrl.actions)
        # the door is open again (scoped latches may re-engage later,
        # per tenant, if the burn is real — that is the scoped loop's
        # own sustain discipline, not a stranded latch)
        h3 = eng.submit([7, 8], max_new_tokens=2, tenant="acme")
        assert h3.status is None or h3.status == "completed"
        eng.run_until_idle()

    def test_detach_releases_tenant_scoped_latches(self, journal):
        """Regression (PR 16 contract): ``release()`` must clear
        tenant-scoped shed latches too, not just the global one — a
        departing controller otherwise strands single tenants shut."""
        from hetu_tpu.serve.tenant import Tenant, TenantPolicy
        clk = VClock()
        ctrl = RuntimeController(self.serve_cfg(freeze_buckets=False))
        eng = make_engine(clk, tenants=TenantPolicy(
            [Tenant(id="flood", klass="latency")]))
        with ctrl_mod.use(ctrl):
            eng.controller = None   # drive via the installed seam
            h = eng.submit([1, 2, 3], max_new_tokens=2, tenant="flood")
            clk.t += 1.0
            eng.run_until_idle()
            assert h.status == "completed" and eng.slo.multi_tenant
            eng.step()
            eng.step()
            assert "flood" in eng.batcher.tenant_sheds
            assert ctrl.shed_active
        assert not ctrl.shed_active
        assert not eng.batcher.tenant_sheds
        assert any(a["action"] == "admission_release"
                   and a["signal"] == "controller_detach"
                   and a.get("tenant") == "flood"
                   for a in ctrl.actions)
        h2 = eng.submit([1, 2, 3], max_new_tokens=2, tenant="flood")
        eng.run_until_idle()
        assert h2.status == "completed"

    def test_dry_run_serve_decisions_actuate_nothing(self, journal):
        clk = VClock()
        ctrl = RuntimeController(self.serve_cfg(freeze_buckets=False,
                                                dry_run=True))
        eng = make_engine(clk, controller=ctrl)
        h = eng.submit([1, 2, 3], max_new_tokens=2)
        clk.t += 1.0
        eng.run_until_idle()
        eng.step()
        eng.step()
        assert h.status == "completed"
        rem = journal.of_kind("remediation")
        assert rem and rem[0]["action"] == "admission_shed" \
            and rem[0]["dry_run"] is True
        # decided, but never latched the batcher
        assert not eng.batcher.shedding
        h2 = eng.submit([1, 2, 3], max_new_tokens=2)
        eng.run_until_idle()
        assert h2.status == "completed"


# -------------------------------------------------- seams and overhead

class TestSeamOverhead:
    def test_disabled_seam_is_one_load_and_branch(self):
        """With no controller attached or installed, the gang/serve/
        trainer seams must cost a couple of attribute loads and a branch
        — bounded absolutely, and touching no telemetry."""
        assert ctrl_mod.get_controller() is None

        class Host:
            controller = None

        host = Host()
        reg = obs_registry.get_registry()
        s0 = reg.snapshot()
        n = 20000
        t0 = time.perf_counter()
        for _ in range(n):
            ctrl_mod.maybe_gang_step(host, 1, None)
            ctrl_mod.maybe_serve_tick(host)
            ctrl_mod.maybe_after_train_step(host, 1, None)
        per = (time.perf_counter() - t0) / (3 * n)
        assert per < 5e-6, f"disabled seam costs {per * 1e6:.2f}us/call"
        # raw snapshot equality, not delta(): delta passes gauges
        # through at their new value, which would flag series other
        # tests already set — the seams must have MUTATED nothing
        assert reg.snapshot() == s0

    def test_use_scopes_the_installed_controller(self):
        c = RuntimeController(ControllerConfig())
        assert ctrl_mod.get_controller() is None
        with ctrl_mod.use(c):
            assert ctrl_mod.get_controller() is c
        assert ctrl_mod.get_controller() is None

    def test_action_history_is_bounded(self, journal):
        """A long-lived controller must not grow (or ship on every
        /controller scrape) weeks of decision dicts: the list holds the
        newest `history`, the total keeps counting, the journal stays
        the unbounded record."""
        c = RuntimeController(ControllerConfig(), history=4,
                              registry=obs_registry.MetricsRegistry())
        for i in range(10):
            c._act("deadline_retune", "worker_lag_ewma", step=i,
                   old=1.0, new=1.0)
        assert len(c.actions) == 4 and c.actions_total == 10
        assert [a["step"] for a in c.actions] == [6, 7, 8, 9]
        assert c.summary()["actions_total"] == 10
        assert len(journal.of_kind("remediation")) == 10

    def test_smoke_meters_into_a_private_registry(self):
        """controller_smoke must not pollute the process hetu_ctrl_*
        series — a live production controller's gauges survive a bench
        smoke running in the same process."""
        def ctrl_series(snap):
            return {k: v for k, v in snap.items()
                    if k.startswith("hetu_ctrl_")}

        reg = obs_registry.get_registry()
        live = RuntimeController(ControllerConfig())
        live._m()["deadline"].set(123.0)
        s0 = ctrl_series(reg.snapshot())
        controller_smoke()
        assert ctrl_series(reg.snapshot()) == s0
        assert s0["hetu_ctrl_deadline_seconds"] == 123.0


# ------------------------------------------------------------ endpoints

class TestEndpoints:
    def get(self, url):
        with urllib.request.urlopen(url, timeout=10) as r:
            return json.loads(r.read())

    def test_controller_endpoint(self, journal):
        from hetu_tpu.obs.server import serve
        ctrl = RuntimeController(gang_controller_cfg())
        ctrl._act("deadline_retune", "worker_lag_ewma", step=1, old=2.0,
                  new=1.0, covered_lag=0.5)
        with ctrl_mod.use(ctrl):
            srv = serve(port=0)
            try:
                body = self.get(f"{srv.url}/controller")
            finally:
                srv.stop()
        assert body["installed"] is True
        assert body["actions"][0]["action"] == "deadline_retune"
        assert body["dry_run"] is False
        uninstalled = None
        srv = serve(port=0)
        try:
            uninstalled = self.get(f"{srv.url}/controller")
        finally:
            srv.stop()
        assert uninstalled == {"installed": False}

    def test_fleet_controller_endpoint(self, tmp_path, journal):
        from hetu_tpu.obs.fleet import SnapshotPublisher, serve_fleet
        ctrl = RuntimeController(gang_controller_cfg())
        ctrl._act("quarantine", "replica_divergence", step=6, worker=2,
                  shard="layers.0", divergent_step=6)
        SnapshotPublisher(str(tmp_path), 0, clock=lambda: 0.0).publish()
        srv = serve_fleet(str(tmp_path), port=0)
        try:
            body = self.get(f"{srv.url}/fleet/controller")
        finally:
            srv.stop()
        assert body["workers"] == 1
        assert body["actions"].get("quarantine", 0) >= 1
        tail = body["remediation"]
        assert tail and tail[-1]["action"] == "quarantine"
        # the event keeps its own worker (the QUARANTINED rank); the
        # publishing rank rides under `publisher`, never clobbering it
        assert tail[-1]["worker"] == 2
        assert tail[-1]["publisher"] == 0


# ------------------------------------------------------ the smoke's summary

class TestControllerSmokeSummary:
    def test_controller_smoke_returns_its_five_json_clean_fields(self):
        s = controller_smoke()
        assert set(s) == {"actions", "by_action", "final_deadline",
                          "deadline_source", "clamp"}
        json.dumps(s)
