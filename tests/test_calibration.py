"""Performance calibration plane (obs.calibration).

Covers: ProfileStore versioning/dedupe, CRC+signature verification and
tamper diagnosis, byte-identical serialization across same-input runs,
the pure two-sided merge (and its two-process acceptance), the
regression sentinel (seeded degraded run journals EXACTLY one
``perf_regression`` naming the metric; clean runs journal zero), the
fit layer's determinism, the calibrated consumers
(``dp_search(calibration=)``, cost-model ctor overrides,
``plan_memory(calibration=)`` / ``MemoryPlanner``), the estimator
reconciliation (``hetu_mem_estimator_error_ratio`` +
``mem_estimate_drift``), the measurement seams (autotune
``record_entry`` → store, ``bench._line`` → store), the
``/calibration`` + ``/healthz`` + ``/fleet/calibration`` surfaces, and
the end-to-end acceptance: an instrumented GPT train step's signals fit
constants that ``dp_search`` ranks plans by — bitwise across same-seed
replays.
"""

import itertools
import json
import multiprocessing
import os
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.core import set_random_seed
from hetu_tpu.obs import calibration as calib
from hetu_tpu.obs import goodput as obs_goodput
from hetu_tpu.obs import registry as obs_registry
from hetu_tpu.obs.calibration import (Calibration, CalibrationKey,
                                      CalibrationStoreError, ProfileStore,
                                      RegressionSentinel, fit_calibration)
from hetu_tpu.obs.goodput import GoodputMeter
from hetu_tpu.obs.journal import EventJournal, use as journal_use

pytestmark = pytest.mark.calib

CPU = "cpu-test"


def _store(**kw):
    kw.setdefault("clock", lambda: 0.0)
    kw.setdefault("registry", obs_registry.MetricsRegistry())
    return ProfileStore(**kw)


KEY = dict(model_sig="gpt-tiny", mesh_sig="dp2", policy="none",
           device_kind=CPU)


# ------------------------------------------------------------- the store

class TestProfileStore:
    def test_versioning_and_baseline(self):
        s = _store()
        r1 = s.put("goodput", {"mfu": 0.5}, **KEY)
        r2 = s.put("goodput", {"mfu": 0.55}, **KEY)
        assert (r1["version"], r2["version"]) == (1, 2)
        h = s.history("goodput", **KEY)
        assert [r["version"] for r in h] == [1, 2]
        assert s.get("goodput", **KEY)["values"]["mfu"] == 0.55

    def test_identical_reingest_is_idempotent(self):
        s = _store()
        s.put("goodput", {"mfu": 0.5}, **KEY)
        again = s.put("goodput", {"mfu": 0.5}, **KEY)
        assert again["version"] == 1
        assert len(s.history("goodput", **KEY)) == 1

    def test_values_cleaned_to_finite_numbers(self):
        s = _store()
        rec = s.put("bench", {"mfu": 0.5, "nan": float("nan"),
                              "inf": float("inf"), "note": "str",
                              "flag": True, "n": 3}, **KEY)
        assert rec["values"] == {"mfu": 0.5, "n": 3.0}

    def test_key_roundtrip(self):
        k = CalibrationKey("kernel", "flash|512x512|d64|c0", "dp4",
                           "full", "TPU v5e")
        assert CalibrationKey.parse(str(k)) == k

    def test_save_load_verify_and_tamper(self, tmp_path):
        p = tmp_path / "calib.json"
        s = _store(path=str(p))
        s.put("goodput", {"mfu": 0.5}, **KEY)  # autosaves
        loaded = ProfileStore.load(str(p), clock=lambda: 0.0,
                                   registry=obs_registry.MetricsRegistry())
        assert loaded.get("goodput", **KEY)["values"]["mfu"] == 0.5
        # flip a byte inside the body: CRC (or signature) must catch it
        raw = p.read_bytes()
        p.write_bytes(raw.replace(b"0.5", b"0.9", 1))
        with pytest.raises(CalibrationStoreError):
            ProfileStore.load(str(p))
        # a missing file is an empty store, not an error
        empty = ProfileStore.load(str(tmp_path / "nope.json"))
        assert empty.records == {}

    def test_to_json_byte_identical_across_runs(self):
        def build():
            s = _store()
            rng = np.random.default_rng(3)
            for i in range(5):
                s.put("goodput", {"mfu": float(rng.uniform(0.4, 0.6)),
                                  "useful_s": float(rng.uniform(5, 10))},
                      **KEY)
                s.put("kernel", {"best_s": float(rng.uniform(1e-3, 2e-3))},
                      model_sig=f"flash|s{i}", device_kind=CPU)
            return s.to_json()

        assert build() == build()

    def test_merge_is_pure_and_keeps_both_writers(self):
        a = _store()
        a.put("goodput", {"mfu": 0.5}, **KEY)
        a.put("goodput", {"mfu": 0.52}, **KEY)
        b = _store()
        b.put("goodput", {"mfu": 0.5}, **KEY)     # same baseline
        b.put("goodput", {"mfu": 0.41}, **KEY)    # divergent v2
        m1 = calib._merge_histories(a.records, b.records)
        m2 = calib._merge_histories(b.records, a.records)
        assert m1 == m2  # order-independent
        key = str(CalibrationKey("goodput", **{
            "model_sig": KEY["model_sig"], "mesh_sig": KEY["mesh_sig"],
            "policy": KEY["policy"], "device_kind": KEY["device_kind"]}))
        vals = [r["values"]["mfu"] for r in m1[key]]
        assert sorted(vals) == [0.41, 0.5, 0.52]     # nothing lost
        assert [r["version"] for r in m1[key]] == [1, 2, 3]
        # record CRCs were recomputed for the renumbered versions
        for r in m1[key]:
            assert r["crc32"] == calib._record_crc(r)

    def test_merge_breaks_version_ties_chronologically(self):
        """Two fresh-process writers both append version 1 of the same
        key: the merge must order the collision by timestamp, so
        history[-1] (what the sentinel calls 'latest') is the LATER
        measurement — not whichever record's JSON happens to sort
        first."""
        early = ProfileStore(clock=lambda: 100.0,
                             registry=obs_registry.MetricsRegistry())
        late = ProfileStore(clock=lambda: 999.0,  # lexicographically
                            registry=obs_registry.MetricsRegistry())
        # "999.0" < "1000.0" as strings would invert a content sort;
        # as floats 999.0 < 1000.0 keeps chronology — use 100 vs 999
        early.put("step", {"step_time_s": 1.0}, **KEY)
        late.put("step", {"step_time_s": 2.0}, **KEY)
        for merged in (calib._merge_histories(early.records, late.records),
                       calib._merge_histories(late.records, early.records)):
            (key,) = merged
            assert [r["ts"] for r in merged[key]] == [100.0, 999.0]
            assert merged[key][-1]["values"]["step_time_s"] == 2.0


def _merge_writer(path, tag, n, q):
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from __graft_entry__ import _force_virtual_cpu_mesh
    _force_virtual_cpu_mesh(1)
    from hetu_tpu.obs import registry as reg
    from hetu_tpu.obs.calibration import ProfileStore
    s = ProfileStore(path, clock=lambda: 0.0,
                     registry=reg.MetricsRegistry())
    for i in range(n):
        s.put("kernel", {"best_s": float(i + 1)},
              model_sig=f"{tag}|sig{i}", device_kind="cpu-test")
    q.put("done")


@pytest.mark.slow
def test_concurrent_two_process_writers_merge_without_loss(tmp_path):
    """Acceptance: two processes putting records concurrently into the
    same store file — every record from BOTH survives the exclusive-lock
    merge, and the published file verifies (CRC + signature intact, no
    torn write)."""
    path = str(tmp_path / "calib.json")
    n = 20
    ctx = multiprocessing.get_context("spawn")
    q = ctx.Queue()
    ps = [ctx.Process(target=_merge_writer, args=(path, tag, n, q))
          for tag in ("alpha", "beta")]
    for p in ps:
        p.start()
    for p in ps:
        assert q.get(timeout=120) == "done"
    for p in ps:
        p.join(30)
        assert p.exitcode == 0
    merged = ProfileStore.load(path)  # verifies CRC + signature
    for tag in ("alpha", "beta"):
        for i in range(n):
            rec = merged.get("kernel", model_sig=f"{tag}|sig{i}",
                             device_kind="cpu-test")
            assert rec is not None and rec["values"]["best_s"] == i + 1
    assert len(merged.records) == 2 * n


# ------------------------------------------------------------- sentinel

class TestSentinel:
    def test_grade_is_deterministic_and_sorted(self):
        sen = RegressionSentinel()
        base = {"mfu": 0.5, "step_time_s": 1.0, "context": 7.0}
        bad = {"mfu": 0.4, "step_time_s": 1.3, "context": 1.0}
        f1, f2 = sen.grade(base, bad), sen.grade(base, bad)
        assert f1 == f2
        assert [f["metric"] for f in f1] == ["mfu", "step_time_s"]
        assert f1[0]["ratio"] == 0.8
        # ungraded context fields never alarm; zero baselines are skipped
        assert sen.grade({"mfu": 0.0}, {"mfu": 0.0}) == []

    def test_degraded_run_journals_exactly_one_event(self):
        """Seeded degraded run: baseline put, then a slowed run whose one
        graded metric crosses its threshold — EXACTLY one
        ``perf_regression``, naming that metric; and the event stream is
        bitwise-identical across same-seed replays."""
        def run(slowdown):
            s = _store()
            j = EventJournal(clock=lambda: 0.0)
            rng = np.random.default_rng(11)
            base = float(rng.uniform(0.9, 1.1))
            with journal_use(j):
                s.put("step", {"step_time_s": base}, **KEY)
                s.put("step", {"step_time_s": base * slowdown}, **KEY)
            return s, [e for e in j.events
                       if e["kind"] == "perf_regression"]

        s, events = run(1.5)
        assert len(events) == 1
        assert events[0]["metric"] == "step_time_s"
        assert events[0]["ratio"] == 1.5
        assert events[0]["key"] == str(CalibrationKey("step", **{
            "model_sig": KEY["model_sig"], "mesh_sig": KEY["mesh_sig"],
            "policy": KEY["policy"], "device_kind": KEY["device_kind"]}))
        _, replay = run(1.5)
        assert replay == events  # deterministic, bitwise
        # the active-regression view recomputes the same finding
        regs = s.regressions()
        assert len(regs) == 1 and regs[0]["metric"] == "step_time_s"

    def test_clean_run_journals_zero_events(self):
        s = _store()
        j = EventJournal(clock=lambda: 0.0)
        with journal_use(j):
            s.put("step", {"step_time_s": 1.0}, **KEY)
            s.put("step", {"step_time_s": 1.05}, **KEY)  # inside +15%
        assert [e for e in j.events if e["kind"] == "perf_regression"] == []
        assert s.regressions() == []

    def test_recovery_clears_the_active_regression(self):
        s = _store()
        s.put("step", {"step_time_s": 1.0}, **KEY)
        s.put("step", {"step_time_s": 2.0}, **KEY)
        assert s.regressions()
        s.put("step", {"step_time_s": 1.02}, **KEY)
        assert s.regressions() == []

    def test_regression_metrics_counted(self):
        reg = obs_registry.MetricsRegistry()
        s = _store(registry=reg)
        s.put("goodput", {"mfu_rolling": 0.5}, **KEY)
        s.put("goodput", {"mfu_rolling": 0.3}, **KEY)
        snap = reg.snapshot()
        assert snap['hetu_calib_records_total{kind="goodput"}'] == 2.0
        assert snap[
            'hetu_calib_regressions_total{metric="mfu_rolling"}'] == 1.0
        assert snap["hetu_calib_regressed"] == 1.0


# ------------------------------------------------------------ fit layer

class TestFit:
    def _seeded_store(self):
        s = _store()
        rng = np.random.default_rng(5)
        for _ in range(4):
            useful = float(rng.uniform(8, 10))
            wait = float(rng.uniform(0.5, 1.5))
            s.put("goodput", {"mfu_rolling": float(rng.uniform(0.5, 0.6)),
                              "mfu_cumulative": 0.0, "useful_s": useful,
                              "straggler_wait_s": wait},
                  grade=False, **KEY)
        s.put("compile", {"temp_bytes": 4.0e9, "compile_s": 1.0,
                          "programs": 1.0}, grade=False, **KEY)
        s.put("mem", {"predicted_bytes": 5e9, "xla_bytes": 4e9,
                      "ratio": 1.25}, grade=False, **KEY)
        return s

    def test_fit_constants_and_residuals(self):
        cal = fit_calibration(self._seeded_store(), n_layers=8, **KEY)
        mfu = cal.constant("mfu")
        assert mfu is not None and 0.5 < mfu.value < 0.6 and mfu.n == 4
        assert len(mfu.residuals) == 4
        # residuals are deviations from the fit: they re-center on it
        assert any(r != 0 for r in mfu.residuals)
        ov = cal.constant("dp_overlap")
        assert ov is not None and 0.8 < ov.value < 1.0
        assert cal.get("bytes_per_layer") == 5.0e8
        assert cal.mem_error_ratio == 1.25

    def test_fit_is_bitwise_deterministic(self):
        c1 = fit_calibration(self._seeded_store(), n_layers=8, **KEY)
        c2 = fit_calibration(self._seeded_store(), n_layers=8, **KEY)
        assert c1.to_json() == c2.to_json()

    def test_empty_store_fits_nothing(self):
        cal = fit_calibration(_store(), **KEY)
        assert cal.constants == ()
        assert cal.mfu is None and cal.dp_overlap is None

    def test_manual_calibration(self):
        cal = Calibration.of(mfu=0.55, dp_overlap=0.9)
        assert cal.mfu == 0.55 and cal.get("dp_overlap") == 0.9
        assert cal.get("missing", 7) == 7


# ------------------------------------------------- calibrated consumers

class TestConsumers:
    def test_time_cost_model_calibration_and_overrides(self):
        from hetu_tpu.parallel.autoparallel import (ClusterSpec,
                                                    TimeCostModel)
        cl = ClusterSpec(n_devices=1)
        assert TimeCostModel(cl).mfu == 0.4                 # legacy default
        cal = Calibration.of(mfu=0.55, dp_overlap=0.92)
        tm = TimeCostModel(cl, calibration=cal)
        assert (tm.mfu, tm.dp_overlap) == (0.55, 0.92)
        # explicit keyword wins over the calibration
        assert TimeCostModel(cl, mfu=0.5, calibration=cal).mfu == 0.5
        # out-of-range fitted values are rejected, defaults kept
        assert TimeCostModel(
            cl, calibration=Calibration.of(mfu=0.0)).mfu == 0.4

    def test_memory_cost_model_byte_overrides(self):
        from hetu_tpu.parallel.autoparallel import (ClusterSpec, LayerSpec,
                                                    MemoryCostModel,
                                                    ParallelChoice)
        cl = ClusterSpec(n_devices=1)
        layer = LayerSpec("l", params=1e6, flops_per_sample=1.0,
                          activation_per_sample=0.0)
        base = MemoryCostModel(cl).layer_bytes(layer, ParallelChoice(), 1)
        assert base == 1e6 * (2.0 + 12.0 + 2.0)
        halved = MemoryCostModel(cl, bytes_state=6.0).layer_bytes(
            layer, ParallelChoice(), 1)
        assert halved == 1e6 * (2.0 + 6.0 + 2.0)
        via_cal = MemoryCostModel(
            cl, calibration=Calibration.of(bytes_state=6.0))
        assert via_cal.layer_bytes(layer, ParallelChoice(), 1) == halved

    def test_dp_search_ranks_by_measured_mfu(self):
        from hetu_tpu.parallel.autoparallel import (
            ClusterSpec, dp_search, transformer_layer_spec)
        specs = [transformer_layer_spec(64, 32, name=f"l{i}")
                 for i in range(2)]
        cl = ClusterSpec(n_devices=1, hbm_bytes=16e9)
        t_guess = dp_search(specs, cl, global_batch=4).time
        cal = Calibration.of(mfu=0.8)
        t_measured = dp_search(specs, cl, global_batch=4,
                               calibration=cal).time
        # single device: the plan time is pure compute, ∝ 1/mfu
        assert t_measured == pytest.approx(t_guess * 0.4 / 0.8)

    def test_plan_memory_corrects_by_measured_ratio(self):
        import dataclasses
        from hetu_tpu import mem
        from hetu_tpu.models.gpt import GPT, GPTConfig
        tiny = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                         num_heads=2, max_seq_len=32, remat="none")

        def build(policy):
            set_random_seed(0)
            return GPT(dataclasses.replace(tiny, remat=policy))

        def batch(mb):
            rng = np.random.default_rng(0)
            return jnp.array(rng.integers(0, tiny.vocab_size,
                                          (mb, tiny.max_seq_len)))

        loss = lambda m, b: m.loss(b, training=False)  # noqa: E731
        raw = mem.plan_memory(loss, build, batch, 1e12,
                              policies=("none",))
        # estimator over-predicts 2x (ratio 2.0): calibrated peak halves
        cal = Calibration.of(mem_error_ratio=2.0)
        corrected = mem.plan_memory(loss, build, batch, 1e12,
                                    policies=("none",), calibration=cal)
        assert corrected.predicted_peak_bytes == int(round(
            raw.predicted_peak_bytes / 2.0))
        # the MemoryPlanner handle is the same search
        planner = mem.MemoryPlanner(1e12, policies=("none",),
                                    calibration=cal)
        assert planner.plan(loss, build, batch).to_json() \
            == corrected.to_json()


# --------------------------------------------------- reconciliation seam

class TestReconcile:
    def test_gauge_and_drift_journal(self):
        from hetu_tpu.mem.estimator import reconcile
        j = EventJournal(clock=lambda: 0.0)
        with journal_use(j):
            ok = reconcile(1.1e9, 1.0e9)           # inside the 25% band
            bad = reconcile(2.0e9, 1.0e9)          # outside
        assert ok["within_band"] and not bad["within_band"]
        drift = [e for e in j.events if e["kind"] == "mem_estimate_drift"]
        assert len(drift) == 1
        assert drift[0]["ratio"] == 2.0 and drift[0]["band"] == 0.25
        snap = obs_registry.get_registry().snapshot()
        assert snap["hetu_mem_estimator_error_ratio"] == 2.0
        # absent XLA numbers: ratio 0.0 (absent, not infinite), no drift
        assert reconcile(1e9, 0.0) == {"ratio": 0.0, "within_band": True}

    def test_reconcile_feeds_installed_store(self):
        from hetu_tpu.mem.estimator import reconcile
        s = _store()
        calib.install_store(s)
        try:
            reconcile(2.0e9, 1.0e9, model_sig="train.step")
        finally:
            calib.install_store(None)
        rec = s.get("mem", model_sig="train.step")
        assert rec is not None and rec["values"]["ratio"] == 2.0


# ------------------------------------------------------ measurement seams

class TestSeams:
    def test_autotune_record_entry_feeds_store(self, tmp_path, monkeypatch):
        from hetu_tpu.ops.pallas import autotune as at
        monkeypatch.setenv(at._CACHE_ENV, str(tmp_path / "tune.json"))
        at.clear_tune_cache()
        s = _store()
        calib.install_store(s)
        try:
            at.record_entry("lm_head", "N64|E32|V256",
                            {"block_n": 32, "block_v": 128,
                             "table": {"32x128": 0.002, "64x128": 0.003}})
        finally:
            calib.install_store(None)
            at.clear_tune_cache()
        rec = s.get("kernel", model_sig="lm_head|N64|E32|V256",
                    device_kind=at._device_kind())
        assert rec is not None
        assert rec["values"]["best_s"] == 0.002
        assert rec["values"]["block_n"] == 32.0

    def test_ingest_autotune_reads_db(self, tmp_path, monkeypatch):
        from hetu_tpu.ops.pallas import autotune as at
        monkeypatch.setenv(at._CACHE_ENV, str(tmp_path / "tune.json"))
        at.clear_tune_cache()
        at.record_entry("paged_decode", "h4|d64|p16",
                        {"head_block": 2, "table": {"2": 0.001}})
        s = _store()
        try:
            recs = s.ingest_autotune()
        finally:
            at.clear_tune_cache()
        assert any(r["values"].get("head_block") == 2.0 for r in recs)

    def test_ingest_op_breakdown(self):
        s = _store()
        s.ingest_op_breakdown({"fusion.1": 0.5, "copy.2": 0.1},
                              {"device_s": 0.6, "copy_s": 0.1},
                              model_sig="bert128")
        v = s.get("ops", model_sig="bert128")["values"]
        assert v["device_s"] == 0.6 and v["op:fusion.1_s"] == 0.5

    def test_peak_flops_unknown_tpu_kind_raises(self):
        with pytest.raises(KeyError, match="PEAK_BF16"):
            obs_goodput.peak_flops("TPU v99")
        assert obs_goodput.peak_flops("TPU v4") == 275e12
        assert obs_goodput.peak_flops("cpu") == 1e12


# ------------------------------------------------------------- endpoints

def _get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return json.loads(r.read().decode())


class TestEndpoints:
    def test_calibration_scrape_after_two_instrumented_steps(self):
        """Tier-1 smoke: two instrumented train steps feed the meter, the
        store ingests, and ``/calibration`` renders a line-validated
        summary."""
        from hetu_tpu import obs
        meter = GoodputMeter(registry=obs_registry.MetricsRegistry())
        meter.set_flops_model(1e9, peak=1e12)
        for i, d in enumerate((1.0, 1.1)):   # two instrumented steps
            meter.record_step(d, step=i, waited=0.1)
        s = _store()
        s.ingest_goodput(meter, model_sig="gpt-tiny", mesh_sig="dp1",
                         device_kind=CPU)
        calib.install_store(s)
        try:
            with obs.serve() as srv:
                body = _get(srv.url + "/calibration")
        finally:
            calib.install_store(None)
        assert body["installed"] is True
        assert body["format"] == calib.STORE_FORMAT
        assert body["kinds"] == {"goodput": 1}
        key = str(CalibrationKey("goodput", "gpt-tiny", "dp1", "", CPU))
        latest = body["latest"][key]
        assert latest["version"] == 1
        assert latest["values"]["mfu_rolling"] > 0
        assert latest["values"]["useful_s"] == pytest.approx(1.9)
        assert body["regressions"] == []

    def test_uninstalled_scrape(self):
        from hetu_tpu import obs
        assert calib.get_store() is None
        with obs.serve() as srv:
            assert _get(srv.url + "/calibration") == {"installed": False}

    def test_healthz_red_flag(self):
        from hetu_tpu import obs
        s = _store()
        s.put("goodput", {"mfu_rolling": 0.5}, **KEY)
        s.put("goodput", {"mfu_rolling": 0.3}, **KEY)
        calib.install_store(s)
        try:
            with obs.serve() as srv:
                body = _get(srv.url + "/healthz")
        finally:
            calib.install_store(None)
        assert body["status"] == "unhealthy"
        flags = {f["flag"]: f for f in body["flags"]}
        assert flags["perf_regression"]["count"] == 1
        assert flags["perf_regression"]["worst"] == "mfu_rolling"

    def test_fleet_calibration_endpoint(self, tmp_path):
        from hetu_tpu.obs.fleet import serve_fleet
        gang_dir = str(tmp_path)
        shared = ProfileStore(calib.store_path(gang_dir),
                              clock=lambda: 0.0,
                              registry=obs_registry.MetricsRegistry())
        shared.put("step", {"step_time_s": 1.0}, **KEY)
        shared.put("step", {"step_time_s": 1.6}, **KEY)
        srv = serve_fleet(gang_dir, with_telemetry=False)
        try:
            body = _get(srv.url + "/fleet/calibration")
        finally:
            srv.stop()
        assert body["installed"] is True
        assert body["keys"] == 1
        assert [r["metric"] for r in body["regressions"]] \
            == ["step_time_s"]
        assert body["perf_regressions"] == []  # no worker snapshots


# ------------------------------------------------- end-to-end acceptance

class TestAcceptance:
    def _run(self):
        """One instrumented GPT train step + seeded step billing →
        ingest → fit.  Deterministic by construction: the compile seam's
        clock is a counter, the meter durations are seeded, the store
        clock is pinned."""
        from hetu_tpu.exec.executor import Trainer
        from hetu_tpu.models.gpt import GPT, GPTConfig
        from hetu_tpu.optim.optimizers import SGDOptimizer
        tiny = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                         num_heads=2, max_seq_len=32)
        set_random_seed(0)
        model = GPT(tiny)
        tr = Trainer(model, SGDOptimizer(0.1),
                     lambda m, b, k: (m.loss(b, training=False), {}))
        # deterministic compile clock: compile_s is an exact tick count
        ticks = itertools.count()
        tr._train_step.clock = lambda: float(next(ticks))
        rng = np.random.default_rng(0)
        batch = jnp.array(rng.integers(0, tiny.vocab_size,
                                       (2, tiny.max_seq_len)))
        tr.step(batch)                      # the instrumented step
        assert tr._train_step.compile_count == 1
        meter = GoodputMeter(registry=obs_registry.MetricsRegistry())
        meter.set_flops_model(1e9, peak=1e12)
        drng = np.random.default_rng(7)
        for i, d in enumerate(drng.uniform(0.9, 1.1, 8)):
            meter.record_step(float(d), step=i, waited=float(d) * 0.1)
        store = _store()
        store.ingest_goodput(meter, **KEY)
        store.ingest_compile(tr._train_step, **KEY)
        cal = fit_calibration(store, n_layers=2, **KEY)
        return store, cal

    def test_calibrated_search_bitwise_across_replays(self):
        from hetu_tpu.parallel.autoparallel import (
            ClusterSpec, dp_search, transformer_layer_spec)
        store1, cal1 = self._run()
        store2, cal2 = self._run()
        # fitted constants, residuals, and store bytes all bitwise
        assert cal1.to_json() == cal2.to_json()
        assert store1.to_json() == store2.to_json()
        mfu = cal1.constant("mfu")
        assert mfu is not None and mfu.n == 1
        # waited=10% of each step: the measured overlap partition
        ov = cal1.constant("dp_overlap")
        assert ov is not None and ov.value == pytest.approx(0.9)
        # dp_search consumes the MEASURED mfu: on one device the plan
        # time is pure compute, so it scales exactly by guess/measured
        specs = [transformer_layer_spec(64, 32, name=f"l{i}")
                 for i in range(2)]
        cl = ClusterSpec(n_devices=1, hbm_bytes=16e9)
        t_guess = dp_search(specs, cl, global_batch=4).time
        plan = dp_search(specs, cl, global_batch=4, calibration=cal1)
        assert plan.time == pytest.approx(t_guess * 0.4 / mfu.value)
        replay = dp_search(specs, cl, global_batch=4, calibration=cal2)
        assert replay.time == plan.time  # bitwise: identical calibration
