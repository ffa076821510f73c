"""Fleet observability plane: snapshot publication, cross-worker
aggregation, stitched traces, online goodput/MFU accounting, straggler
attribution — and the chaos acceptance test asserting the whole surface
EXACTLY under a seeded ``worker_stall`` + ``worker_kill`` plan.
"""

import json
import math
import os
import re
import textwrap
import time
import urllib.request

import numpy as np
import pytest

from hetu_tpu import obs
from hetu_tpu.core import set_random_seed
from hetu_tpu.exec import (ElasticGang, PartialReduceConfig, Trainer, faults)
from hetu_tpu.models import MLP
from hetu_tpu.obs import fleet as obs_fleet
from hetu_tpu.obs import goodput as obs_goodput
from hetu_tpu.obs import journal as obs_journal
from hetu_tpu.obs import registry as obs_registry
from hetu_tpu.obs.fleet import (FleetAggregator, SnapshotPublisher,
                                fleet_routes, serve_fleet, snapshot_path)
from hetu_tpu.obs.goodput import BUCKETS, GoodputMeter
from hetu_tpu.obs.tracing import SPAN_PID
from hetu_tpu.optim import SGDOptimizer
from hetu_tpu.ops import softmax_cross_entropy_sparse
from test_obs import _valid_prom_line

pytestmark = pytest.mark.obs


# ---------------------------------------------------------------- helpers

def worker_telemetry(rank, *, steps=3, clock=lambda: 100.0):
    """One synthetic worker's (registry, journal, tracer) — the
    per-process state a real gang worker would publish."""
    reg = obs_registry.MetricsRegistry()
    c = reg.counter("hetu_fw_steps_total", "steps", ("outcome",))
    g = reg.gauge("hetu_fw_lag_seconds", "lag", ("worker",))
    h = reg.histogram("hetu_fw_latency_seconds", "lat", buckets=(0.1, 1.0))
    for i in range(steps):
        c.labels(outcome="ok").inc()
        h.observe(0.05 * (rank + 1) * (i + 1))
    g.labels(worker=str(rank)).set(float(rank))
    jr = obs_journal.EventJournal(clock=clock)
    for i in range(steps):
        jr.record("partial_step", step=i + 1, rank=rank)
    clk = iter(range(100))
    tr = obs.Tracer(clock=lambda: next(clk))
    with tr.collect():
        with tr.span("train.step", rank=rank):
            pass
    return reg, jr, tr


def publish_fleet(gang_dir, n=3, *, clock=lambda: 100.0, steps=3):
    pubs = []
    for rank in range(n):
        reg, jr, tr = worker_telemetry(rank, steps=steps, clock=clock)
        pub = SnapshotPublisher(str(gang_dir), rank, registry=reg,
                                journal=jr, tracer=tr, clock=clock)
        pub.publish()
        pubs.append(pub)
    return pubs


def prom_samples(text):
    """{sample_key: float} from a Prometheus text exposition."""
    out = {}
    for line in text.splitlines():
        if line.startswith("#") or not line:
            continue
        key, val = line.rsplit(" ", 1)
        out[key] = float(val)
    return out


# ------------------------------------------------------------- publisher

class TestSnapshotPublisher:
    def test_publish_writes_atomic_snapshot(self, tmp_path):
        reg, jr, tr = worker_telemetry(0)
        pub = SnapshotPublisher(str(tmp_path), 0, registry=reg, journal=jr,
                                tracer=tr, clock=lambda: 42.0)
        path = pub.publish()
        assert path == snapshot_path(str(tmp_path), 0)
        body = json.load(open(path))
        assert body["format"] == obs_fleet.SNAPSHOT_FORMAT
        assert body["worker"] == 0 and body["seq"] == 1
        assert body["ts"] == 42.0
        assert {f["name"] for f in body["registry"]["families"]} == {
            "hetu_fw_steps_total", "hetu_fw_lag_seconds",
            "hetu_fw_latency_seconds"}
        assert [e["seq"] for e in body["journal"]] == [1, 2, 3]
        assert body["spans"][0]["name"] == "train.step"
        # no tmp file left behind (atomic replace)
        assert [n for n in os.listdir(tmp_path / "obs")
                if ".tmp." in n] == []

    def test_interval_throttle_and_journal_tail(self, tmp_path):
        now = [0.0]
        reg, jr, tr = worker_telemetry(1, clock=lambda: now[0])
        pub = SnapshotPublisher(str(tmp_path), 1, interval=0.5, registry=reg,
                                journal=jr, tracer=tr, clock=lambda: now[0],
                                journal_tail=2)
        assert pub.publish(force=False) is not None  # first always lands
        assert pub.publish(force=False) is None      # throttled
        now[0] += 0.6
        assert pub.publish(force=False) is not None
        assert pub.publish() is not None             # force bypasses
        assert pub.published == 3
        body = json.load(open(snapshot_path(str(tmp_path), 1)))
        assert [e["seq"] for e in body["journal"]] == [2, 3]  # tail cap

    def test_zero_cost_when_off(self, tmp_path):
        """Acceptance: publication is a single flag check when disabled —
        HETU_OBS=0 publishes nothing, and maybe_publish with no installed
        publisher is one global load + branch (timed generously)."""
        assert obs_fleet.get_publisher() is None
        assert obs_fleet.maybe_publish() is False
        t0 = time.perf_counter()
        for _ in range(200_000):
            obs_fleet.maybe_publish()
        assert time.perf_counter() - t0 < 1.0  # ~µs-scale per call
        pub = SnapshotPublisher(str(tmp_path), 0)
        obs.disable()
        try:
            assert pub.publish() is None
            assert obs_goodput.record_step(1.0) is None  # meter seam too
        finally:
            obs.enable()
        assert not os.path.exists(snapshot_path(str(tmp_path), 0))
        # env builder: unset env -> no publisher
        assert obs_fleet.publisher_from_env(str(tmp_path), 0) is None

    def test_install_and_maybe_publish(self, tmp_path):
        reg, jr, tr = worker_telemetry(0)
        pub = SnapshotPublisher(str(tmp_path), 0, interval=0.0, registry=reg,
                                journal=jr, tracer=tr)
        try:
            assert obs_fleet.install_publisher(pub) is pub
            assert obs_fleet.get_publisher() is pub
            assert obs_fleet.maybe_publish() is True
        finally:
            obs_fleet.install_publisher(None)
        assert os.path.exists(snapshot_path(str(tmp_path), 0))


# ------------------------------------------------------------ aggregation

class TestFleetAggregator:
    def test_counters_sum_gauges_max_histograms_bucketwise(self, tmp_path):
        publish_fleet(tmp_path, 3)
        agg = FleetAggregator(str(tmp_path), clock=lambda: 100.0)
        agg.refresh()
        m = agg.merged("hetu_fw_steps_total")
        assert m["kind"] == "counter"
        assert m["children"][("ok",)] == 9.0  # 3 workers x 3 steps
        lag = agg.merged("hetu_fw_lag_seconds", agg="max")
        # each worker published only its own series; max folds them
        assert {k: v for k, v in lag["children"].items()} == {
            ("0",): 0.0, ("1",): 1.0, ("2",): 2.0}
        h = agg.merged("hetu_fw_latency_seconds")
        child = h["children"][()]
        # bucket-wise: per-bucket counts add index by index
        assert sum(child["counts"]) == child["count"] == 9
        assert child["sum"] == pytest.approx(sum(
            0.05 * (r + 1) * (i + 1) for r in range(3) for i in range(3)))
        assert agg.merged("hetu_never_registered_total") is None

    def test_render_prometheus_worker_label_and_validity(self, tmp_path):
        publish_fleet(tmp_path, 2)
        agg = FleetAggregator(str(tmp_path), clock=lambda: 101.0)
        agg.refresh()
        text = agg.render_prometheus()
        for line in text.splitlines():
            assert _valid_prom_line(line), f"invalid line: {line!r}"
        samples = prom_samples(text)
        assert samples["hetu_fleet_workers"] == 2
        for w in ("0", "1"):
            assert samples[
                f'hetu_fw_steps_total{{outcome="ok",worker="{w}"}}'] == 3
            assert samples[
                f'hetu_fleet_snapshot_age_seconds{{worker="{w}"}}'] == \
                pytest.approx(1.0)
        # histogram series carry the worker label after le
        assert ('hetu_fw_latency_seconds_bucket{worker="0",le="+Inf"}'
                in samples)

    def test_schema_conflict_dropped_and_reported(self, tmp_path):
        publish_fleet(tmp_path, 2)
        # worker 2 publishes the counter's name as a GAUGE
        reg = obs_registry.MetricsRegistry()
        reg.gauge("hetu_fw_steps_total", "wrong kind").set(7.0)
        SnapshotPublisher(str(tmp_path), 2, registry=reg,
                          journal=obs_journal.EventJournal(),
                          tracer=obs.Tracer(),
                          clock=lambda: 100.0).publish()
        agg = FleetAggregator(str(tmp_path), clock=lambda: 100.0)
        agg.refresh()
        m = agg.merged("hetu_fw_steps_total")
        assert m["children"][("ok",)] == 6.0  # conflicting worker dropped
        health = agg.healthz()
        assert health["status"] == "degraded"
        assert health["schema_conflicts"][0]["family"] == \
            "hetu_fw_steps_total"
        assert health["schema_conflicts"][0]["worker"] == 2

    def test_merged_journal_global_order_and_gap_detection(self, tmp_path):
        publish_fleet(tmp_path, 3)
        agg = FleetAggregator(str(tmp_path))
        agg.refresh()
        merged = agg.merged_journal()
        # (seq, worker) lexicographic: all seq-1 events first, by rank
        assert [(e["seq"], e["worker"]) for e in merged] == [
            (s, w) for s in (1, 2, 3) for w in (0, 1, 2)]
        assert all(e["kind"] == "partial_step" for e in merged)
        # a gap in one worker's stream is named, not papered over
        body = json.load(open(snapshot_path(str(tmp_path), 1)))
        del body["journal"][1]  # lose seq 2
        json.dump(body, open(snapshot_path(str(tmp_path), 1), "w"))
        agg.refresh()
        with pytest.raises(ValueError, match="worker 1.*sequence gap"):
            agg.merged_journal()
        assert len(agg.merged_journal(strict=False)) == 8

    def test_stitched_trace_one_pid_row_per_worker(self, tmp_path):
        publish_fleet(tmp_path, 3)
        agg = FleetAggregator(str(tmp_path))
        agg.refresh()
        events = agg.stitched_trace_events()
        assert {e["pid"] for e in events} == {SPAN_PID, SPAN_PID + 1,
                                             SPAN_PID + 2}
        xs = [e for e in events if e["ph"] == "X"]
        assert len(xs) == 3 and all(e["name"] == "train.step" for e in xs)

    def test_healthz_flags_stale_workers(self, tmp_path):
        now = [100.0]
        publish_fleet(tmp_path, 2, clock=lambda: now[0])
        now[0] = 102.0
        # worker 1 republishes fresh; worker 0 goes stale
        reg, jr, tr = worker_telemetry(1, clock=lambda: now[0])
        SnapshotPublisher(str(tmp_path), 1, registry=reg, journal=jr,
                          tracer=tr, clock=lambda: now[0]).publish()
        agg = FleetAggregator(str(tmp_path), stale_after=1.0,
                              clock=lambda: now[0])
        agg.refresh()
        health = agg.healthz()
        assert health["status"] == "degraded"
        assert health["stale_workers"] == [0]
        assert health["workers"]["0"]["age_s"] == pytest.approx(2.0)
        assert health["workers"]["1"]["stale"] is False

    def test_stragglers_ranked_worst_first(self, tmp_path):
        for rank, lag in ((0, 0.1), (1, 2.5), (2, 0.9)):
            reg = obs_registry.MetricsRegistry()
            reg.gauge("hetu_partial_worker_lag_seconds", "lag",
                      ("worker",)).labels(worker=str(rank)).set(lag)
            SnapshotPublisher(str(tmp_path), rank, registry=reg,
                              journal=obs_journal.EventJournal(),
                              tracer=obs.Tracer(),
                              clock=lambda: 100.0).publish()
        agg = FleetAggregator(str(tmp_path), clock=lambda: 100.0)
        agg.refresh()
        top = agg.stragglers(2)
        assert [(e["worker"], e["lag"]) for e in top] == [(1, 2.5), (2, 0.9)]
        assert agg.stragglers(0) == []


# -------------------------------------------------------- fleet endpoints

def test_fleet_endpoints_http(tmp_path):
    publish_fleet(tmp_path, 2, clock=time.time)  # fresh vs the real clock
    meter = GoodputMeter()
    meter.record_step(1.0, step=1)
    obs_goodput.install_meter(meter)
    try:
        with serve_fleet(str(tmp_path), stale_after=1e9) as srv:
            def get(path):
                with urllib.request.urlopen(srv.url + path, timeout=10) as r:
                    assert r.status == 200
                    return r.headers["Content-Type"], r.read().decode()

            ctype, text = get("/fleet/metrics")
            assert ctype.startswith("text/plain")
            for line in text.splitlines():
                assert _valid_prom_line(line), line
            assert 'hetu_fw_steps_total{outcome="ok",worker="1"} 3' in text
            _, health = get("/fleet/healthz")
            assert json.loads(health)["status"] == "ok"
            # ?since= on the fleet journal is an INDEX cursor into the
            # merged stream (per-worker seqs repeat across workers)
            _, jtext = get("/fleet/journal?since=4")
            assert [(e["seq"], e["worker"])
                    for e in json.loads(jtext)] == [(3, 0), (3, 1)]
            _, trace = get("/fleet/trace")
            assert {e["pid"] for e in json.loads(trace)["traceEvents"]} == \
                {SPAN_PID, SPAN_PID + 1}
            _, gp = get("/fleet/goodput")
            assert json.loads(gp)["totals"]["useful"] == 1.0
            # per-process telemetry rides the same port
            _, own = get("/metrics")
            assert own.splitlines()  # valid scrape of this process
    finally:
        obs_goodput.install_meter(None)


# ----------------------------------------------------------- goodput meter

class TestGoodputMeter:
    def test_buckets_partition_exactly(self):
        m = GoodputMeter(registry=obs_registry.MetricsRegistry())
        m.record_step(1.0, step=1)                       # useful
        m.record_step(3.0, step=2, waited=2.0, straggler=3)
        m.record_step(1.0, step=3, skipped=True)         # rollback
        m.record_step(1.0, step=2)                       # replay -> rescale
        m.record_event("checkpoint", 0.5)
        m.record_event("rescale", 0.25)
        assert m.totals == {"useful": 2.0, "straggler_wait": 2.0,
                            "rollback": 1.0, "rescale": 1.25,
                            "checkpoint": 0.5, "retune": 0.0,
                            "compile": 0.0}
        assert m.total() == sum(m.totals.values()) == 6.75
        assert m.by_worker == {3: 2.0}
        fr = m.fractions()
        assert sum(fr.values()) == pytest.approx(1.0)
        assert set(fr) == set(BUCKETS)
        with pytest.raises(ValueError, match="unknown goodput bucket"):
            m.record_event("coffee", 1.0)

    def test_gauges_and_counters_published(self):
        reg = obs_registry.MetricsRegistry()
        m = GoodputMeter(registry=reg)
        m.record_step(2.0, step=1, waited=1.0, straggler=2)
        snap = reg.snapshot()
        assert snap['hetu_goodput_seconds_total{bucket="useful"}'] == 1.0
        assert snap[
            'hetu_goodput_seconds_total{bucket="straggler_wait"}'] == 1.0
        assert snap[
            'hetu_goodput_straggler_wait_seconds_total{worker="2"}'] == 1.0
        assert snap['hetu_goodput_fraction{bucket="useful"}'] == 0.5
        assert snap["hetu_goodput_mfu"] == 0.0  # no flops model yet

    def test_rolling_mfu(self):
        m = GoodputMeter(registry=obs_registry.MetricsRegistry(), window=2)
        m.set_flops_model(50.0, peak=100.0)
        m.record_step(1.0, step=1)
        assert m.mfu() == pytest.approx(0.5)   # 50 flops / 1s / 100 peak
        m.record_step(4.0, step=2)
        m.record_step(4.0, step=3)             # window drops step 1
        assert m.mfu() == pytest.approx(100.0 / 8.0 / 100.0)
        snap = m.snapshot()
        assert snap["mfu_rolling"] == pytest.approx(m.mfu())
        assert snap["mfu_cumulative"] == pytest.approx(150.0 / 9.0 / 100.0)
        # skipped steps never count as useful flops
        m.record_step(1.0, step=4, skipped=True)
        assert m.snapshot()["mfu_cumulative"] == pytest.approx(
            150.0 / 10.0 / 100.0)

    def test_ingest_journal_kinds(self):
        m = GoodputMeter(registry=obs_registry.MetricsRegistry())
        events = [
            {"seq": 1, "kind": "checkpoint_saved", "duration_s": 0.5},
            {"seq": 2, "kind": "nan_skip"},
            {"seq": 3, "kind": "retune", "duration_s": 2.0},
            # AOT compile wall is pure lower+compile -> billed; a
            # watch-mode first-call wall includes the step's execution,
            # already billed useful by record_step -> NOT billed again
            {"seq": 4, "kind": "compile", "aot": True, "duration_s": 0.25},
            {"seq": 5, "kind": "recompile", "aot": False,
             "duration_s": 9.0},
        ]
        cursor = m.ingest(events)
        assert cursor == 5
        assert m.totals["checkpoint"] == 0.5 and m.totals["retune"] == 2.0
        assert m.totals["compile"] == 0.25
        # incremental: an already-consumed prefix is not re-billed
        events.append({"seq": 6, "kind": "checkpoint_saved",
                       "duration_s": 0.25})
        assert m.ingest(events, since_seq=cursor) == 6
        assert m.totals["checkpoint"] == 0.75

    def test_module_level_seam_noop_without_meter(self):
        assert obs_goodput.get_meter() is None
        obs_goodput.record_step(1.0)        # no meter: pure branch
        obs_goodput.record_event("useful", 1.0)
        t0 = time.perf_counter()
        for _ in range(200_000):
            obs_goodput.record_step(1.0)
        assert time.perf_counter() - t0 < 1.0


# ------------------------------------------------------ straggler EWMA

class TestWorkerLagEWMA:
    def test_ewma_math_and_top(self):
        from hetu_tpu.exec.partial import WorkerLagEWMA
        e = WorkerLagEWMA(alpha=0.5)
        e.observe({0: 0.0, 1: 4.0})
        assert e.lag == {0: 0.0, 1: 4.0}  # first observation seeds
        e.observe({0: 0.0, 1: 0.0})
        assert e.lag[1] == 2.0            # (1-a)*4 + a*0
        e.observe({2: 6.0})
        assert e.top(2) == [(2, 6.0), (1, 2.0)]
        with pytest.raises(ValueError, match="alpha"):
            WorkerLagEWMA(alpha=0.0)

    def test_remap_rekeys_and_drops_evicted(self):
        from hetu_tpu.exec.partial import WorkerLagEWMA
        reg = obs_registry.get_registry()
        e = WorkerLagEWMA()
        e.observe({0: 1.0, 1: 2.0, 2: 3.0})
        snap = reg.snapshot()
        assert snap['hetu_partial_worker_lag_seconds{worker="1"}'] == 2.0
        e.remap({0: 0, 2: 1})  # worker 1 evicted; 2 re-ranks to 1
        assert e.lag == {0: 1.0, 1: 3.0}
        snap = reg.snapshot()
        assert snap['hetu_partial_worker_lag_seconds{worker="1"}'] == 3.0
        assert 'hetu_partial_worker_lag_seconds{worker="2"}' not in snap


# ------------------------------------------- 2-worker multiprocess smoke

def test_two_worker_fleet_smoke(tmp_path):
    """Tier-1 acceptance smoke: a 2-worker ``simulate_workers`` gang
    publishes telemetry snapshots through the ``GangMembership`` heartbeat
    seam (publisher built from the launcher's env), and the rank-0
    ``/fleet/metrics`` scrape shows per-worker series, line-validated."""
    from hetu_tpu.launch import simulate_workers
    gang_dir = str(tmp_path / "gang")
    script = textwrap.dedent("""
        import os
        import hetu_tpu.exec.gang as G
        from hetu_tpu.obs import fleet as F
        from hetu_tpu.obs import journal as J
        from hetu_tpu.obs import registry as R

        rank = int(os.environ["HETU_TPU_PROC_ID"])
        gd = os.environ["HETU_TPU_GANG_DIR"]
        J.set_journal(J.EventJournal())
        mem = G.GangMembership(gd, rank, lease_ttl=10.0, interval=0.05)
        mem.start()  # installs the publisher from HETU_TPU_OBS_SNAPSHOT
        assert F.get_publisher() is not None, "publisher not installed"
        steps = R.get_registry().counter(
            "hetu_fleet_smoke_steps_total", "smoke steps")
        for i in range(3):
            steps.inc()
            J.record("partial_step", step=i + 1, arrivals=2)
            mem.heartbeat()  # publication rides the heartbeat seam
        pub = F.get_publisher()
        mem.leave()          # final forced snapshot + publisher uninstall
        assert F.get_publisher() is None, "leave() must uninstall"
        print("DONE", rank, pub.published, flush=True)
    """)
    outs = simulate_workers(2, script, timeout=120.0, gang_dir=gang_dir,
                            obs_snapshot=0.0)
    for rank, out in enumerate(outs):
        assert f"DONE {rank}" in out, out
    with serve_fleet(gang_dir, stale_after=1e9) as srv:
        with urllib.request.urlopen(srv.url + "/fleet/metrics",
                                    timeout=10) as r:
            assert r.status == 200
            text = r.read().decode()
        for line in text.splitlines():
            assert _valid_prom_line(line), f"invalid line: {line!r}"
        samples = prom_samples(text)
        assert samples["hetu_fleet_workers"] == 2
        for w in ("0", "1"):  # per-worker series present and exact
            assert samples[
                f'hetu_fleet_smoke_steps_total{{worker="{w}"}}'] == 3
            # already-worker-labeled families keep their own label; the
            # publishing rank rides the `publisher` label instead
            assert samples[
                f'hetu_gang_worker_alive{{worker="{w}",publisher="{w}"}}'
            ] == 1
        with urllib.request.urlopen(srv.url + "/fleet/journal?n=100",
                                    timeout=10) as r:
            merged = json.loads(r.read())
        steps = [e for e in merged if e["kind"] == "partial_step"]
        assert [(e["seq"], e["worker"]) for e in steps] == [
            (s, w) for s in (1, 2, 3) for w in (0, 1)]


def test_simulate_workers_obs_snapshot_requires_gang_dir():
    from hetu_tpu.launch import simulate_workers
    with pytest.raises(ValueError, match="gang_dir"):
        simulate_workers(1, "print('x')", obs_snapshot=0.5)


# ------------------------------------------------ chaos acceptance test

@pytest.mark.chaos
def test_fleet_chaos_exact_telemetry(tmp_path):
    """Acceptance: a 4-worker gang under a seeded ``worker_stall`` +
    ``worker_kill`` plan yields (a) an aggregated /fleet/metrics scrape
    whose summed per-worker counter deltas exactly equal the injected
    fault counts, (b) a merged journal that is gapless and identically
    ordered across two same-seed runs, and (c) goodput buckets that sum
    exactly to total (sim-clock) wall time, with straggler-wait
    attributed to the stalled worker's rank."""
    KILLS, STALLS, STALL_UNITS = 1, 2, 5.0  # the injected ground truth

    def make_trainer():
        set_random_seed(0)
        model = MLP((8, 16, 3))

        def loss_fn(model, batch, key):
            logits = model(batch["x"])
            return (softmax_cross_entropy_sparse(logits, batch["y"]).mean(),
                    {})

        return Trainer(model, SGDOptimizer(0.1), loss_fn, donate=False)

    rng = np.random.default_rng(0)
    data = []
    for _ in range(40):
        x = rng.standard_normal((16, 8)).astype(np.float32)
        data.append({"x": x, "y": (x[:, 0] > 0).astype(np.int32)})

    reg = obs_registry.get_registry()

    def scrape(gang_dir):
        agg = FleetAggregator(str(gang_dir), clock=lambda: 1000.0)
        agg.refresh()
        text = agg.render_prometheus()
        for line in text.splitlines():
            assert _valid_prom_line(line), line
        return agg, prom_samples(text)

    def run(tag):
        d = tmp_path / tag
        gang_dir = str(d / "gang")
        jr = obs_journal.EventJournal(str(d) + ".journal.jsonl")
        meter = GoodputMeter()
        pub = SnapshotPublisher(gang_dir, 0, registry=reg, journal=jr,
                                clock=lambda: 1000.0)
        # min_arrivals=4: any straggler degrades the cut to the full
        # barrier, so each stall costs exactly its length in waited
        # sim-time, attributed to the stalled rank — the exact arithmetic
        # this test asserts
        plan = faults.FaultPlan([
            (3, faults.Fault("worker_stall", worker=2, arg=3)),
            (6, faults.Fault("worker_kill", worker=3)),
            (8, faults.Fault("worker_stall", worker=2, arg=2)),
        ])
        with obs_journal.use(jr), faults.inject(plan):
            pub.publish()  # pre-run snapshot -> scrape baseline
            _agg, before = scrape(gang_dir)
            tr = make_trainer()
            g = ElasticGang(
                tr, gang_dir, world_size=4,
                data_fn=lambda s: data[s - 1], global_batch_size=16,
                seed=0, save_every=4,
                partial=PartialReduceConfig(deadline=1.0, tau=4,
                                            min_arrivals=4),
                goodput=meter)
            g.run_until(10)
            assert plan.remaining() == []  # every fault really fired
            pub.publish()  # post-run snapshot
        agg, after = scrape(gang_dir)
        jr.close()
        return g, meter, jr, agg, before, after

    def summed(samples, family, **labels):
        """Sum a family's samples across the worker label (exactly the
        'summed per-worker counters' the acceptance criterion names)."""
        want = "".join(f'{k}="{v}"' for k, v in labels.items())
        total = 0.0
        for key, val in samples.items():
            if key.startswith(family + "{") and want in key:
                total += val
        return total

    results = {}
    for tag in ("a", "b"):
        g, meter, jr, agg, before, after = run(tag)

        # -- (c) goodput partition: exact, in sim-clock units ------------
        assert meter.total() == sum(meter.totals.values()) == g.sim_time
        n_exec = len(g.history)
        assert meter.totals["straggler_wait"] == STALL_UNITS
        assert meter.totals["straggler_wait"] == g.sim_time - n_exec
        # worker 3 was killed LAST rank, so the survivors' re-rank is the
        # identity and the stalled worker keeps rank 2 across the rescale
        assert meter.by_worker == {2: STALL_UNITS}
        # useful = the 10 committed steps; rescale = the replayed ones
        assert meter.totals["useful"] == 10.0
        assert meter.totals["rescale"] == float(n_exec - 10)
        assert meter.totals["rescale"] > 0  # the kill really rewound
        assert meter.totals["rollback"] == 0.0
        assert sum(meter.fractions().values()) == pytest.approx(1.0)

        # -- straggler attribution surfaces ------------------------------
        top = g.reducer.lags.top(1)
        assert top[0][0] == 2 and top[0][1] > 0
        stragglers = agg.stragglers(4)
        assert stragglers[0]["worker"] == 2
        assert stragglers[0]["lag"] == top[0][1]

        # -- (a) scrape deltas == injected fault counts ------------------
        for family, expect in (
                ("hetu_gang_worker_lost_total", KILLS),
                ("hetu_gang_rescales_total", KILLS),
                ("hetu_partial_degraded_steps_total", STALLS)):
            delta = summed(after, family) - summed(before, family)
            assert delta == expect, (family, delta, expect)
        wait_delta = summed(
            after, "hetu_goodput_straggler_wait_seconds_total",
            worker="2") - summed(
            before, "hetu_goodput_straggler_wait_seconds_total", worker="2")
        assert wait_delta == STALL_UNITS

        # -- (b) merged journal gapless + globally ordered ---------------
        merged = agg.merged_journal()  # strict: per-worker gaplessness
        assert [e["seq"] for e in merged] == \
            list(range(1, len(merged) + 1))
        kinds = {e["kind"] for e in merged}
        assert {"worker_lost", "gang_rescale", "partial_step",
                "checkpoint_saved"} <= kinds
        results[tag] = {
            "journal": [(e["seq"], e["kind"], e.get("step"),
                         e.get("rank"), e.get("worker")) for e in merged],
            "totals": dict(meter.totals),
            "by_worker": dict(meter.by_worker),
            "sim_time": g.sim_time,
            "losses": g.losses_by_step,
        }

    # two same-seed runs: identically ordered journals, identical goodput
    assert results["a"]["journal"] == results["b"]["journal"]
    assert results["a"]["totals"] == results["b"]["totals"]
    assert results["a"]["by_worker"] == results["b"]["by_worker"]
    assert results["a"]["sim_time"] == results["b"]["sim_time"]
    assert results["a"]["losses"] == results["b"]["losses"]
