"""Unified runtime telemetry: registry/tracing/journal units, the
/metrics endpoint under live training, instrumented-seam behavior, the
disabled-overhead guard, and the exact-telemetry chaos acceptance test.
"""

import json
import math
import re
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu import obs
from hetu_tpu.core import set_random_seed
from hetu_tpu.exec import ResilientTrainer, Trainer, faults
from hetu_tpu.models import MLP
from hetu_tpu.optim import SGDOptimizer
from hetu_tpu.ops import softmax_cross_entropy_sparse

pytestmark = pytest.mark.obs


def make_trainer():
    set_random_seed(0)
    model = MLP((8, 16, 3))

    def loss_fn(model, batch, key):
        logits = model(batch["x"])
        return softmax_cross_entropy_sparse(logits, batch["y"]).mean(), {}

    return Trainer(model, SGDOptimizer(0.1), loss_fn, donate=False)


def make_batch(seed=0, n=16):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 8)).astype(np.float32)
    return {"x": jnp.asarray(x),
            "y": jnp.asarray((x[:, 0] > 0).astype(np.int32))}


# ---------------------------------------------------------------- registry

class TestRegistry:
    def test_counter_gauge_histogram(self):
        reg = obs.MetricsRegistry()
        c = reg.counter("t_total", "a counter", ("op",))
        c.labels(op="pull").inc()
        c.labels(op="pull").inc(2)
        c.labels("push").inc()
        assert c.labels(op="pull").value == 3
        assert c.labels(op="push").value == 1
        with pytest.raises(ValueError, match="only go up"):
            c.labels(op="pull").inc(-1)
        g = reg.gauge("t_gauge")
        g.set(2.5)
        g.inc()
        g.dec(0.5)
        assert g.value == 3.0
        h = reg.histogram("t_seconds", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        hc = h.labels()
        assert hc.count == 3 and hc.sum == pytest.approx(5.55)
        assert hc.cumulative() == [(0.1, 1), (1.0, 2), (float("inf"), 3)]

    def test_family_idempotent_and_schema_checked(self):
        reg = obs.MetricsRegistry()
        a = reg.counter("x_total", "h", ("op",))
        assert reg.counter("x_total", "h", ("op",)) is a
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("x_total")
        with pytest.raises(ValueError, match="already registered"):
            reg.counter("x_total", "h", ("other",))
        with pytest.raises(ValueError, match="invalid metric name"):
            reg.counter("0bad")
        with pytest.raises(ValueError, match="expected labels"):
            a.labels(op="a", extra="b")

    def test_snapshot_delta(self):
        reg = obs.MetricsRegistry()
        c = reg.counter("d_total", "", ("op",))
        g = reg.gauge("d_gauge")
        c.labels(op="a").inc(5)
        g.set(10.0)
        s0 = reg.snapshot()
        c.labels(op="a").inc(2)
        c.labels(op="b").inc(7)  # new sample counts from zero
        g.set(3.0)
        d = reg.delta(reg.snapshot(), s0)
        assert d['d_total{op="a"}'] == 2
        assert d['d_total{op="b"}'] == 7
        assert d["d_gauge"] == 3.0  # gauges pass through, not subtract

    def test_disabled_is_noop(self):
        reg = obs.MetricsRegistry()
        c = reg.counter("off_total")
        h = reg.histogram("off_seconds")
        obs.disable()
        try:
            c.inc(100)
            h.observe(1.0)
        finally:
            obs.enable()
        assert c.value == 0 and h.labels().count == 0

    def test_thread_safety(self):
        reg = obs.MetricsRegistry()
        c = reg.counter("race_total")

        def work():
            for _ in range(1000):
                c.inc()

        ths = [threading.Thread(target=work) for _ in range(4)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        assert c.value == 4000

    def test_prometheus_rendering_and_escaping(self):
        reg = obs.MetricsRegistry()
        reg.counter("esc_total", "multi\nline", ("p",)).labels(
            p='we"ird\\path\n').inc()
        reg.histogram("lat_seconds", "lat", buckets=(0.5,)).observe(0.1)
        text = reg.render_prometheus()
        assert "# HELP esc_total multi\\nline" in text
        assert '\\"ird\\\\path\\n' in text
        assert 'lat_seconds_bucket{le="+Inf"} 1' in text
        for line in text.splitlines():
            assert _valid_prom_line(line), line

    def test_export_jsonl(self, tmp_path):
        reg = obs.MetricsRegistry()
        reg.counter("j_total").inc(3)
        p = str(tmp_path / "metrics.jsonl")
        reg.export_jsonl(p, extra={"step": 1})
        reg.counter("j_total").inc()
        reg.export_jsonl(p, extra={"step": 2})
        recs = [json.loads(ln) for ln in open(p)]
        assert [r["step"] for r in recs] == [1, 2]
        assert recs[0]["metrics"]["j_total"] == 3
        assert recs[1]["metrics"]["j_total"] == 4
        assert recs[0]["ts"] <= recs[1]["ts"]

    def test_set_total_mirrors_monotonically(self):
        reg = obs.MetricsRegistry()
        c = reg.counter("m_total")
        c.set_total(10)
        c.set_total(4)  # a restarted source must not move the series back
        assert c.value == 10
        c.set_total(12)
        assert c.value == 12

    def test_histogram_quantile(self):
        reg = obs.MetricsRegistry()
        h = reg.histogram("q_seconds", buckets=(0.1, 1.0)).labels()
        assert math.isnan(h.quantile(0.5))  # nothing observed yet
        for _ in range(4):
            h.observe(0.05)
        # all mass in the first bucket: linear interpolation inside it
        assert 0.0 < h.quantile(0.5) <= 0.1
        assert h.quantile(1.0) == pytest.approx(0.1)
        since = h.cumulative()
        for _ in range(10):
            h.observe(0.5)
        # windowed form: only the post-snapshot observations count
        assert 0.1 < h.quantile(0.5, since=since) <= 1.0
        # +Inf bucket reports its lower (finite) edge
        h2 = reg.histogram("q2_seconds", buckets=(0.1,)).labels()
        h2.observe(5.0)
        assert h2.quantile(0.5) == 0.1

    def test_histogram_quantile_edge_semantics(self):
        """Satellite: empty and single-bucket histograms answer
        deterministically — an empty delta is nan (never a plausible
        latency), the +Inf bucket reports its finite lower edge (0.0
        for a bucketless histogram), and a single-bucket histogram
        interpolates inside its one bucket up to its bound at q=1."""
        reg = obs.MetricsRegistry()
        # empty: nan on every quantile, fresh or windowed
        h = reg.histogram("qe_seconds", buckets=(0.1,)).labels()
        for q in (0.0, 0.5, 0.99, 1.0):
            assert math.isnan(h.quantile(q))
        snap = h.cumulative()
        assert math.isnan(h.quantile(0.5, since=snap))
        # single bucket, all mass inside it: interpolation + exact edge
        h.observe(0.05)
        h.observe(0.05)
        assert 0.0 < h.quantile(0.5) <= 0.1
        assert h.quantile(1.0) == pytest.approx(0.1)
        # single bucket, all mass ABOVE it: the +Inf bucket's lower edge
        h1 = reg.histogram("qo_seconds", buckets=(0.1,)).labels()
        h1.observe(7.0)
        assert h1.quantile(0.5) == 0.1
        assert h1.quantile(0.99) == 0.1
        # bucketless histogram: +Inf is the only bucket; lower edge is 0.0
        h0 = reg.histogram("qz_seconds", buckets=()).labels()
        assert math.isnan(h0.quantile(0.5))
        h0.observe(3.0)
        assert h0.quantile(0.5) == 0.0
        # static form mirrors the instance form
        empty = [(0.1, 0), (math.inf, 0)]
        assert math.isnan(
            obs.Histogram.quantile_from_cumulative(empty, empty, 0.5))

    def test_dump_roundtrips_schema_and_state(self):
        """registry.dump() is the re-aggregatable export the fleet plane
        publishes: schema (kind/help/labels/buckets) + raw bucket counts
        (NOT cumulative), JSON-serializable."""
        reg = obs.MetricsRegistry()
        reg.counter("dmp_total", "ct", ("op",)).labels(op="a").inc(3)
        reg.gauge("dmp_gauge", "gg").set(2.5)
        h = reg.histogram("dmp_seconds", "hh", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 0.5, 5.0):
            h.observe(v)
        d = json.loads(json.dumps(reg.dump()))  # JSON-serializable
        fams = {f["name"]: f for f in d["families"]}
        assert fams["dmp_total"]["kind"] == "counter"
        assert fams["dmp_total"]["labelnames"] == ["op"]
        assert fams["dmp_total"]["children"][0] == {"labels": ["a"],
                                                    "value": 3.0}
        assert fams["dmp_gauge"]["children"][0]["value"] == 2.5
        hist = fams["dmp_seconds"]
        assert hist["buckets"] == [0.1, 1.0]
        child = hist["children"][0]
        assert child["counts"] == [1, 2, 1]  # per-bucket, not cumulative
        assert child["count"] == 4 and child["sum"] == pytest.approx(6.05)


# ----------------------------------------------------------------- tracing

class TestTracing:
    def test_deterministic_span_tree(self):
        clock = iter(range(100))
        tr = obs.Tracer(clock=lambda: next(clock))
        with tr.collect():
            with tr.span("step", idx=0) as root:
                with tr.span("rpc") as child:
                    pass
            with tr.span("save"):
                pass
        spans = {s.name: s for s in tr.spans}
        assert spans["rpc"].trace_id == spans["step"].trace_id
        assert spans["rpc"].parent_id == spans["step"].span_id
        assert spans["save"].parent_id is None
        assert spans["save"].trace_id != spans["step"].trace_id
        assert spans["step"].start == 0 and spans["step"].duration == 3
        assert spans["rpc"].start == 1 and spans["rpc"].duration == 1
        assert root.attrs == {"idx": 0}
        assert child is not None
        # same construction again -> identical ids (deterministic)
        clock2 = iter(range(100))
        tr2 = obs.Tracer(clock=lambda: next(clock2))
        with tr2.collect():
            with tr2.span("step", idx=0):
                with tr2.span("rpc"):
                    pass
            with tr2.span("save"):
                pass
        assert [(s.span_id, s.parent_id) for s in tr2.spans] == \
            [(s.span_id, s.parent_id) for s in tr.spans[:3]]

    def test_not_recording_is_noop(self):
        tr = obs.Tracer()
        with tr.span("x") as sp:
            assert sp is None
        assert tr.spans == []
        obs.disable()
        try:
            tr.start()
            with tr.span("y") as sp:
                assert sp is None  # master switch wins over recording
        finally:
            obs.enable()
            tr.stop()
        assert tr.spans == []

    def test_span_parentage_across_worker_threads(self):
        """Satellite: the module docstring's ``contextvars.copy_context()``
        recipe — a worker thread run under the copied context parents its
        spans to the span current at copy time; a plain thread starts a
        fresh trace."""
        import contextvars
        clock = iter(range(100))
        tr = obs.Tracer(clock=lambda: next(clock))
        with tr.collect():
            with tr.span("driver"):
                ctx = contextvars.copy_context()

                def inherited():
                    with tr.span("worker.pull"):
                        pass

                def orphan():
                    with tr.span("worker.orphan"):
                        pass

                t1 = threading.Thread(target=lambda: ctx.run(inherited))
                t2 = threading.Thread(target=orphan)
                t1.start(); t1.join()
                t2.start(); t2.join()
        spans = {s.name: s for s in tr.spans}
        driver = spans["driver"]
        assert spans["worker.pull"].parent_id == driver.span_id
        assert spans["worker.pull"].trace_id == driver.trace_id
        # no copied context -> no inherited parentage (fresh trace root)
        assert spans["worker.orphan"].parent_id is None
        assert spans["worker.orphan"].trace_id != driver.trace_id

    def test_stitched_pid_offset(self):
        """span_pid / spans_to_chrome_events: worker rank offsets the
        reserved pid so a stitched fleet trace shows one row per worker."""
        from hetu_tpu.obs.tracing import (SPAN_PID, span_pid,
                                          spans_to_chrome_events)
        assert span_pid() == SPAN_PID
        assert span_pid(3) == SPAN_PID + 3
        clock = iter(range(10))
        tr = obs.Tracer(clock=lambda: next(clock))
        with tr.collect():
            with tr.span("step"):
                pass
        ev = spans_to_chrome_events(tr.span_dicts(), worker=3)
        assert all(e["pid"] == SPAN_PID + 3 for e in ev)
        meta = [e for e in ev if e["ph"] == "M"][0]
        assert "worker 3" in meta["args"]["name"]
        # default export is unchanged (worker=None -> base pid)
        assert all(e["pid"] == SPAN_PID for e in tr.to_chrome_events())

    def test_chrome_export(self, tmp_path):
        clock = iter(range(10))
        tr = obs.Tracer(clock=lambda: next(clock))
        with tr.collect():
            with tr.span("step"):
                pass
        out = str(tmp_path / "spans.json")
        tr.export_chrome(out)
        data = json.load(open(out))
        phases = {e["ph"] for e in data["traceEvents"]}
        assert phases == {"M", "X"}
        x = [e for e in data["traceEvents"] if e["ph"] == "X"][0]
        assert x["name"] == "step" and x["dur"] == 1e6  # 1 "second"
        assert x["args"]["parent_id"] is None

    def test_a_span_that_outlives_its_recording_is_dropped(self):
        tr = obs.Tracer()
        tr.start()
        with tr.span("cut") as outer:
            with tr.span("whole"):
                pass
            tr.stop()
            with tr.span("unseen") as sp:
                assert sp is None
        # kept, it would stand there with a child missing
        assert [s.name for s in tr.spans] == ["whole"]
        assert outer.end_time is None


class TestProfilerSession:
    """A live JAX profiler session is the switch: spans record, and land
    in the profile on their host thread's line."""

    @pytest.fixture(scope="class")
    def session(self, tmp_path_factory):
        """One session: spans opened before it, inside it (nested, and
        under ``HETU_OBS=0``) and after it, and the host plane's events."""
        import glob
        import os

        from jax.profiler import ProfileData
        logdir = str(tmp_path_factory.mktemp("profile"))
        tr = obs.Tracer()
        seen = {}
        with tr.span("case.before") as sp:
            seen["before"] = sp
        assert not tr.recording
        jax.profiler.start_trace(logdir)
        try:
            seen["recording"] = tr.recording
            with tr.span("case.outer", tick=1) as outer:
                with tr.span("case.inner") as inner:
                    time.sleep(0.002)
                time.sleep(0.002)
            seen["outer"], seen["inner"] = outer, inner
            obs.disable()
            try:
                with tr.span("case.disabled") as sp:
                    seen["disabled"] = sp
            finally:
                obs.enable()
        finally:
            jax.profiler.stop_trace()
        with tr.span("case.after") as sp:
            seen["after"] = sp
        path, = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                          recursive=True)
        events = {}
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("case."):
                        events[ev.name] = (plane.name, line.name, ev.start_ns,
                                           ev.start_ns + ev.duration_ns,
                                           dict(ev.stats))
        return tr, seen, events

    def test_spans_record_and_nest_in_the_buffer(self, session):
        tr, seen, _ = session
        assert seen["recording"] is True and tr.recording is False
        assert [s.name for s in tr.spans] == ["case.inner", "case.outer"]
        outer, inner = seen["outer"], seen["inner"]
        assert inner.parent_id == outer.span_id and outer.parent_id is None
        assert inner.trace_id == outer.trace_id
        assert outer.start <= inner.start <= inner.end_time <= outer.end_time
        assert outer.attrs == {"tick": 1}

    def test_spans_appear_by_name_on_the_host_plane(self, session):
        _, _, events = session
        outer, inner = events["case.outer"], events["case.inner"]
        assert outer[0] == inner[0] == "/host:CPU"
        assert outer[1] == inner[1]            # one thread, one line
        # nested on the profile's clock as in the buffer
        assert outer[2] <= inner[2] <= inner[3] <= outer[3]
        assert inner[3] - inner[2] >= 2e6 and outer[3] - outer[2] >= 4e6
        assert inner[4] == {"parent": "case.outer"} and outer[4] == {}

    @pytest.mark.parametrize("case", ["before", "after", "disabled"])
    def test_no_record_and_no_annotation_outside_or_disabled(self, session,
                                                             case):
        tr, seen, events = session
        assert seen[case] is None
        assert f"case.{case}" not in events
        assert f"case.{case}" not in [s.name for s in tr.spans]


# ----------------------------------------------------------------- journal

class TestJournal:
    def test_monotonic_seq_and_roundtrip(self, tmp_path):
        p = str(tmp_path / "journal.jsonl")
        with obs.EventJournal(p, clock=lambda: 123.0) as j:
            j.record("checkpoint_saved", step=2, bytes=10)
            j.record("nan_skip", step=3)
            j.record("rollback", at_step=3, to_step=2)
        back = obs.EventJournal.read(p)
        assert [e["seq"] for e in back] == [1, 2, 3]
        assert [e["kind"] for e in back] == ["checkpoint_saved", "nan_skip",
                                            "rollback"]
        assert all(e["ts"] == 123.0 for e in back)

    def test_read_detects_sequence_gap(self, tmp_path):
        p = str(tmp_path / "j.jsonl")
        with open(p, "w") as f:
            f.write(json.dumps({"seq": 1, "ts": 0, "kind": "a"}) + "\n")
            f.write(json.dumps({"seq": 3, "ts": 0, "kind": "b"}) + "\n")
        with pytest.raises(ValueError, match="sequence gap"):
            obs.EventJournal.read(p)

    def test_global_install_and_restore(self):
        j1, j2 = obs.EventJournal(), obs.EventJournal()
        obs.set_journal(j1)
        try:
            obs.record("a")
            with obs.use(j2):
                obs.record("b")
            obs.record("c")
        finally:
            obs.set_journal(None)
        assert [e["kind"] for e in j1.events] == ["a", "c"]
        assert [e["kind"] for e in j2.events] == ["b"]
        assert obs.record("dropped") is None  # no journal installed

    def test_record_noop_when_disabled(self):
        j = obs.EventJournal()
        with obs.use(j):
            obs.disable()
            try:
                obs.record("hidden")
            finally:
                obs.enable()
            obs.record("seen")
        assert [e["kind"] for e in j.events] == ["seen"]

    def test_thread_interleaving_keeps_total_order(self):
        j = obs.EventJournal()

        def work(tag):
            for _ in range(200):
                j.record(tag)

        ths = [threading.Thread(target=work, args=(t,)) for t in "ab"]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        assert [e["seq"] for e in j.events] == list(range(1, 401))

    def test_events_since_cursor(self):
        j = obs.EventJournal()
        for kind in "abcde":
            j.record(kind)
        assert [e["kind"] for e in j.events_since(2)] == ["c", "d", "e"]
        assert [e["kind"] for e in j.events_since(0)] == list("abcde")
        assert j.events_since(-3) == j.events_since(0)
        assert j.events_since(5) == [] and j.events_since(99) == []


# ------------------------------------------------- /metrics endpoint smoke

_PROM_COMMENT = re.compile(r"^# (HELP [a-zA-Z_:][a-zA-Z0-9_:]* .*"
                           r"|TYPE [a-zA-Z_:][a-zA-Z0-9_:]* "
                           r"(counter|gauge|histogram|summary|untyped))$")
_PROM_SAMPLE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\.)*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\.)*")*\})?'
    r' (-?\d+(\.\d+)?([eE][+-]?\d+)?|[+-]Inf|NaN)$')


def _valid_prom_line(line: str) -> bool:
    return bool(_PROM_COMMENT.match(line) or _PROM_SAMPLE.match(line))


def test_metrics_endpoint_live_training(tmp_path):
    """Tier-1-safe acceptance smoke: /metrics serves valid Prometheus text
    exposition, validated line by line, WHILE a Trainer is stepping."""
    tr = make_trainer()
    b = make_batch()
    tr.step(b)  # compile before the timed loop
    stop = threading.Event()

    def train():
        while not stop.is_set():
            tr.step(b)

    th = threading.Thread(target=train, daemon=True)
    with obs.serve() as srv:
        th.start()
        try:
            bodies = []
            for _ in range(3):
                with urllib.request.urlopen(srv.url + "/metrics",
                                            timeout=10) as r:
                    assert r.status == 200
                    assert r.headers["Content-Type"].startswith("text/plain")
                    bodies.append(r.read().decode())
                time.sleep(0.02)
        finally:
            stop.set()
            th.join(10)
        text = bodies[-1]
        for line in text.splitlines():
            assert _valid_prom_line(line), f"invalid exposition line: {line!r}"
        assert "hetu_step_latency_seconds_bucket" in text
        assert 'hetu_train_steps_total{outcome="ok"}' in text
        # health + JSON mirrors
        with urllib.request.urlopen(srv.url + "/healthz", timeout=10) as r:
            health = json.loads(r.read())
        assert health["status"] == "ok" and health["uptime_s"] >= 0
        with urllib.request.urlopen(srv.url + "/metrics.json",
                                    timeout=10) as r:
            snap = json.loads(r.read())
        assert any(k.startswith("hetu_train_steps_total") for k in snap)


def test_journal_endpoint_since_cursor():
    """Satellite: /journal?since=<seq> cursor pagination — incremental
    polls (the fleet aggregator's form) alongside the tail ?n= form."""
    j = obs.EventJournal()
    with obs.use(j), obs.serve() as srv:
        for i in range(1, 6):
            j.record("evt", i=i)

        def get(qs):
            with urllib.request.urlopen(srv.url + "/journal" + qs,
                                        timeout=10) as r:
                return [e["seq"] for e in json.loads(r.read())]

        assert get("?since=3") == [4, 5]
        assert get("?since=0") == [1, 2, 3, 4, 5]
        assert get("?since=99") == []
        assert get("?since=1&n=2") == [2, 3]  # cursor + cap composes
        assert get("?n=2") == [4, 5]          # tail form unchanged
        # incremental poll picks up exactly the new events
        j.record("evt", i=6)
        assert get("?since=5") == [6]


def test_metric_naming_conventions():
    """Satellite lint: every reg.counter/gauge/histogram registration in
    the tree follows Prometheus conventions — hetu_ prefix, _total suffix
    on counters (and never on gauges), unit suffixes on histograms — and
    no two sites register the same name with a different kind, label
    schema, or help text."""
    import ast
    import pathlib

    import hetu_tpu
    root = pathlib.Path(hetu_tpu.__file__).parent
    files = sorted(root.rglob("*.py"))
    sites = {}  # name -> [(kind, labels_or_None, help_or_None, where)]
    for path in files:
        tree = ast.parse(path.read_text(), filename=str(path))
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("counter", "gauge", "histogram")
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                continue
            name = node.args[0].value
            kind = node.func.attr
            help_text = None
            if len(node.args) > 1 and isinstance(node.args[1], ast.Constant):
                help_text = node.args[1].value
            labels = None
            label_node = node.args[2] if len(node.args) > 2 else next(
                (kw.value for kw in node.keywords
                 if kw.arg == "labelnames"), None)
            if isinstance(label_node, (ast.Tuple, ast.List)):
                labels = tuple(e.value for e in label_node.elts
                               if isinstance(e, ast.Constant))
            where = f"{path.relative_to(root.parent)}:{node.lineno}"
            sites.setdefault(name, []).append(
                (kind, labels, help_text, where))
    assert len(sites) > 30, "scanner found suspiciously few registrations"
    problems = []
    for name, regs in sorted(sites.items()):
        kinds = {k for k, _l, _h, _w in regs}
        if len(kinds) > 1:
            problems.append(f"{name}: registered as {sorted(kinds)} "
                            f"at {[w for *_x, w in regs]}")
            continue
        kind = kinds.pop()
        if not re.match(r"^hetu_[a-z0-9_]+$", name):
            problems.append(f"{name}: not hetu_-prefixed lowercase "
                            f"({regs[0][3]})")
        if kind == "counter" and not name.endswith("_total"):
            problems.append(f"{name}: counter without _total ({regs[0][3]})")
        if kind == "gauge" and name.endswith("_total"):
            problems.append(f"{name}: gauge must not claim _total "
                            f"({regs[0][3]})")
        if kind == "histogram" and not name.endswith(
                ("_seconds", "_bytes", "_steps")):
            problems.append(f"{name}: histogram without a unit suffix "
                            f"({regs[0][3]})")
        # byte-unit clause (PR 17): a family whose name claims bytes
        # must put the unit where Prometheus conventions expect it —
        # gauges end _bytes, counters end _bytes_total.  A family like
        # hetu_x_bytes_fraction would dashboard as bytes and alert wrong.
        if "bytes" in name:
            if kind == "gauge" and not name.endswith("_bytes"):
                problems.append(f"{name}: byte gauge must end _bytes "
                                f"({regs[0][3]})")
            if kind == "counter" and not name.endswith("_bytes_total"):
                problems.append(f"{name}: byte counter must end "
                                f"_bytes_total ({regs[0][3]})")
        # the per-tenant metering family must be attributable: every
        # hetu_tenant_* registration declares a `tenant` label (an
        # unlabeled tenant metric is a billing artifact with no payer)
        if name.startswith("hetu_tenant_"):
            tenant_labels = [l for _k, l, _h, _w in regs if l is not None]
            if not tenant_labels or any("tenant" not in l
                                        for l in tenant_labels):
                problems.append(f"{name}: hetu_tenant_* family must "
                                f"declare a 'tenant' label ({regs[0][3]})")
        # conflicting re-registration: among sites that state a schema
        # (a help text or labels — a bare name is a family lookup, not a
        # registration), everyone must agree
        helps = {h for _k, _l, h, _w in regs if h is not None}
        labels = {l for _k, l, _h, _w in regs if l is not None}
        if len(helps) > 1:
            problems.append(f"{name}: conflicting help texts at "
                            f"{[w for *_x, w in regs]}")
        if len(labels) > 1:
            problems.append(f"{name}: conflicting label schemas "
                            f"{sorted(labels)} at {[w for *_x, w in regs]}")
    assert not problems, "\n".join(problems)


def test_plan_determinism_lint():
    """Satellite lint (PR 18): ``hetu_tpu/plan/`` must stay a pure
    function of (spec, calibration) — a Plan that depends on a wall
    clock, entropy, or hash-order dict iteration cannot be
    byte-identical across replays.  The AST lint rejects any ``time`` /
    ``random`` import (plain, dotted, or from-import) and requires
    every ``.items()`` / ``.keys()`` / ``.values()`` call to be the
    DIRECT argument of ``sorted(...)`` — iteration order pinned at the
    call site, not downstream.  ``hetu_tpu/broker/`` joins the linted
    set: a capacity broker whose lease decisions read wall clocks or
    walk dicts in hash order cannot replay its lease journal bitwise.
    ``hetu_tpu/serve/fleet/failover.py`` joins too (PR 20): a failover
    decision that cannot replay bitwise cannot be audited."""
    import ast
    import pathlib

    import hetu_tpu.broker
    import hetu_tpu.plan
    import hetu_tpu.serve.fleet.failover
    roots = [pathlib.Path(hetu_tpu.plan.__file__).parent,
             pathlib.Path(hetu_tpu.broker.__file__).parent]
    files = [p for root in roots for p in sorted(root.glob("*.py"))]
    files.append(pathlib.Path(hetu_tpu.serve.fleet.failover.__file__))
    assert len({p.parent for p in files}) == 3, \
        "plan, broker, or failover has no sources to lint"
    problems = []
    for path in files:
        tree = ast.parse(path.read_text(), filename=str(path))
        parents = {}
        for node in ast.walk(tree):
            for child in ast.iter_child_nodes(node):
                parents[child] = node
        for node in ast.walk(tree):
            where = f"{path.name}:{getattr(node, 'lineno', '?')}"
            if isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name.split(".")[0] in ("time", "random"):
                        problems.append(
                            f"{where}: import {alias.name} — a plan "
                            f"must not read clocks or entropy")
            elif isinstance(node, ast.ImportFrom):
                if (node.module or "").split(".")[0] in ("time",
                                                         "random"):
                    problems.append(
                        f"{where}: from {node.module} import ... — a "
                        f"plan must not read clocks or entropy")
            elif (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("items", "keys", "values")
                    and not node.args and not node.keywords):
                parent = parents.get(node)
                wrapped = (isinstance(parent, ast.Call)
                           and isinstance(parent.func, ast.Name)
                           and parent.func.id == "sorted"
                           and parent.args and parent.args[0] is node)
                if not wrapped:
                    problems.append(
                        f"{where}: .{node.func.attr}() not directly "
                        f"inside sorted(...) — dict iteration order "
                        f"must be pinned at the call site")
    assert not problems, "\n".join(problems)


def test_span_naming_conventions():
    """Satellite lint: the PR-8 metric-naming AST lint extended to span
    names — every span opened in the tree uses a dotted lowercase
    namespace (``serve.*`` / ``compile.*`` / ``train.*`` / ``ps.*``)
    given as a string LITERAL.  Dynamic span-name construction is banned:
    a name built from runtime values is unbounded-cardinality and breaks
    the stitched-trace grouping the fleet plane relies on."""
    import ast
    import pathlib

    import hetu_tpu
    root = pathlib.Path(hetu_tpu.__file__).parent
    files = sorted(root.rglob("*.py"))
    # obs/tracing.py is the framework itself: its module-level span()
    # forwarder passes its `name` parameter through by definition
    skip = {root / "obs" / "tracing.py"}
    pat = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")
    names, problems = set(), []
    for path in files:
        if path in skip:
            continue
        tree = ast.parse(path.read_text(), filename=str(path))
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            f = node.func
            # every tracing span is opened through an attribute call
            # (tracer.span / tl.span / obs.span); a bare name is some
            # local helper, not the tracing API
            if not (isinstance(f, ast.Attribute) and f.attr == "span"):
                continue
            where = f"{path.relative_to(root.parent)}:{node.lineno}"
            arg = node.args[0]
            if not (isinstance(arg, ast.Constant)
                    and isinstance(arg.value, str)):
                problems.append(
                    f"{where}: span name is not a string literal "
                    f"(dynamic construction is banned)")
                continue
            if not pat.match(arg.value):
                problems.append(
                    f"{where}: span name {arg.value!r} is not a dotted "
                    f"lowercase namespace (like serve.decode)")
            names.add(arg.value)
    assert not problems, "\n".join(problems)
    # the namespaces the obs plane documents must actually be in use
    roots = {n.split(".", 1)[0] for n in names}
    assert {"serve", "compile", "train", "ps"} <= roots, roots


def test_journal_event_kinds_registered():
    """Satellite lint: every ``record("kind", ...)`` call in the tree
    (the process-wide ``obs.journal.record`` seam) must name a kind
    registered in ``journal.EVENT_KINDS`` — with its kind as a string
    literal (an IfExp over literals is the one allowed dynamic form,
    the compile/recompile site) — and its statically-visible keyword
    arguments must cover the kind's required fields.  Unregistered
    kinds and silently-missing fields are exactly how a journal schema
    rots; direct ``EventJournal.record`` calls in tests stay free-form."""
    import ast
    import pathlib

    import hetu_tpu
    from hetu_tpu.obs.journal import EVENT_KINDS
    root = pathlib.Path(hetu_tpu.__file__).parent
    files = sorted(root.rglob("*.py"))
    # the journal module itself forwards record(kind, **fields) by design
    skip = {root / "obs" / "journal.py"}
    problems, seen_kinds = [], set()
    for path in files:
        if path in skip:
            continue
        tree = ast.parse(path.read_text(), filename=str(path))
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call) and node.args
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "record"):
                continue
            where = f"{path.relative_to(root.parent)}:{node.lineno}"
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                kinds = [arg.value]
            elif (isinstance(arg, ast.IfExp)
                  and isinstance(arg.body, ast.Constant)
                  and isinstance(arg.orelse, ast.Constant)):
                kinds = [arg.body.value, arg.orelse.value]
            else:
                problems.append(
                    f"{where}: journal kind is not a string literal "
                    f"(dynamic kind construction defeats the registry)")
                continue
            kwargs = {kw.arg for kw in node.keywords if kw.arg is not None}
            has_splat = any(kw.arg is None for kw in node.keywords)
            for kind in kinds:
                if kind not in EVENT_KINDS:
                    problems.append(
                        f"{where}: unregistered journal kind {kind!r} — "
                        f"add it to obs.journal.EVENT_KINDS with its "
                        f"required fields")
                    continue
                seen_kinds.add(kind)
                missing = EVENT_KINDS[kind] - kwargs
                if missing and not has_splat:
                    problems.append(
                        f"{where}: kind {kind!r} missing required "
                        f"fields {sorted(missing)}")
    assert not problems, "\n".join(problems)
    # the registry must describe reality: the new numerics kinds (and a
    # spread of the old ones) are actually emitted somewhere in the tree
    assert {"replica_divergence", "nan_provenance", "flight_dump",
            "nan_skip", "rollback", "partial_step"} <= seen_kinds, \
        sorted(seen_kinds)


def test_metrics_endpoint_404():
    import urllib.error
    with obs.serve() as srv:
        try:
            urllib.request.urlopen(srv.url + "/nope", timeout=10)
            pytest.fail("expected HTTPError")
        except urllib.error.HTTPError as e:
            assert e.code == 404


# -------------------------------------------- instrumented trainer seam

class TestTrainerTelemetry:
    def test_step_metrics_recorded(self):
        reg = obs.get_registry()
        tr = make_trainer()
        b = make_batch()
        s0 = reg.snapshot()
        for _ in range(3):
            tr.step(b)
        d = reg.delta(reg.snapshot(), s0)
        assert d['hetu_train_steps_total{outcome="ok"}'] == 3
        assert d["hetu_step_latency_seconds_count"] == 3
        assert d["hetu_train_examples_total"] == 3 * 16
        assert reg.snapshot()["hetu_examples_per_second"] > 0

    def test_grad_norm_gauge_from_guarded_trainer(self, tmp_path):
        tr = make_trainer()
        rt = ResilientTrainer(tr, str(tmp_path), save_every=0)
        rt.step(make_batch())
        rt.close()
        v = obs.get_registry().snapshot()["hetu_grad_norm"]
        assert v > 0 and np.isfinite(v)

    def test_step_spans_parent_ps_rpcs(self):
        """Cross-layer propagation: a step span exists; PS RPC spans issued
        inside a traced pull are children of the enclosing span."""
        from hetu_tpu.embed.net import EmbeddingServer, RemoteEmbeddingTable
        tracer = obs.get_tracer()
        tracer.reset()
        with EmbeddingServer() as srv:
            t = RemoteEmbeddingTable(f"127.0.0.1:{srv.port}", 870, 16, 4)
            with tracer.collect():
                with tracer.span("driver"):
                    t.pull([1, 2, 3])
            spans = tracer.spans
            by_name = {}
            for s in spans:
                by_name.setdefault(s.name, []).append(s)
            assert len(by_name["ps.rpc"]) == 1
            rpc, driver = by_name["ps.rpc"][0], by_name["driver"][0]
            assert rpc.parent_id == driver.span_id
            assert rpc.trace_id == driver.trace_id
            assert rpc.attrs["op"] == "pull"
        tracer.reset()

    def test_step_span_has_its_two_children(self):
        """``train.step`` splits into the call of the jitted step and all
        that follows it; a guarded step that is skipped does too."""
        tracer = obs.get_tracer()
        tracer.reset()
        tr = make_trainer()
        tr.step(make_batch())              # compiles outside the record
        with tracer.collect():
            tr.step(make_batch(seed=1))
            tr.grad_guard = lambda metrics: False
            assert tr.step(make_batch(seed=2))["skipped"] is True
        spans = tracer.spans
        tracer.reset()
        steps = [s for s in spans if s.name == "train.step"]
        assert len(steps) == 2 and all(s.parent_id is None for s in steps)
        for step in steps:
            kids = sorted((s for s in spans if s.parent_id == step.span_id),
                          key=lambda s: s.start)
            assert [s.name for s in kids] == ["train.step.dispatch",
                                              "train.step.host"]
            assert step.start <= kids[0].start
            assert kids[0].end_time <= kids[1].start
            assert kids[1].end_time <= step.end_time

    def test_disabled_overhead_indistinguishable(self):
        """Acceptance guard: with telemetry disabled, Trainer.step must be
        statistically indistinguishable from the bare (seed) step — the
        wrapper is one global load + branch.  Medians over interleaved
        trials, with a generous CI-noise bound."""
        tr = make_trainer()
        b = make_batch()
        tr.step(b)
        reg = obs.get_registry()
        obs.disable()
        try:
            s0 = reg.snapshot()

            def timed(fn, n=60):
                out = []
                for _ in range(n):
                    t0 = time.perf_counter()
                    fn()
                    out.append(time.perf_counter() - t0)
                return out

            # interleave to decorrelate from machine noise drift
            instrumented, bare = [], []
            for _ in range(5):
                instrumented += timed(lambda: tr.step(b), 30)
                bare += timed(lambda: tr._step_impl(b), 30)
            # disabled telemetry mutated nothing
            d = reg.delta(reg.snapshot(), s0)
            assert all(v == 0 for k, v in d.items()
                       if k.startswith(("hetu_train", "hetu_step"))), d
            ratio = np.median(instrumented) / np.median(bare)
            assert ratio < 1.5, (
                f"disabled-telemetry step is {ratio:.2f}x the bare step "
                f"(median {np.median(instrumented)*1e6:.1f}us vs "
                f"{np.median(bare)*1e6:.1f}us)")
        finally:
            obs.enable()


# ------------------------------------------------- instrumented PS seam

class TestPsTelemetry:
    def test_rpc_latency_bytes_and_totals(self):
        from hetu_tpu.embed.net import EmbeddingServer, RemoteEmbeddingTable
        reg = obs.get_registry()
        with EmbeddingServer() as srv:
            t = RemoteEmbeddingTable(f"127.0.0.1:{srv.port}", 871, 32, 4)
            s0 = reg.snapshot()
            t.pull(np.arange(8))
            t.push(np.arange(8), np.zeros((8, 4), np.float32))
            t.pull(np.arange(4))
            d = reg.delta(reg.snapshot(), s0)
        assert d['hetu_ps_rpc_total{op="pull"}'] == 2
        assert d['hetu_ps_rpc_total{op="push"}'] == 1
        assert d['hetu_ps_rpc_latency_seconds_count{op="pull"}'] == 2
        # pull rx: (8 + 4) rows x 4 dims x 4 bytes
        assert d['hetu_ps_rpc_bytes_total{op="pull",direction="rx"}'] == \
            12 * 4 * 4
        # pull tx: 12 keys x 8 bytes; push tx: keys + grads
        assert d['hetu_ps_rpc_bytes_total{op="pull",direction="tx"}'] == \
            12 * 8
        assert d['hetu_ps_rpc_bytes_total{op="push",direction="tx"}'] == \
            8 * 8 + 8 * 4 * 4

    def test_remote_cache_stats_mirrors_local_surface(self):
        """Satellite: RemoteCacheTable.stats() must expose the exact keys
        CacheTable.stats() does, and both must land in the registry."""
        from hetu_tpu.embed.engine import CacheTable, HostEmbeddingTable
        from hetu_tpu.embed.net import (EmbeddingServer,
                                        RemoteCacheTable,
                                        RemoteEmbeddingTable)
        reg = obs.get_registry()
        local = CacheTable(HostEmbeddingTable(32, 4, seed=3), 8,
                           name="obs-local")
        with EmbeddingServer() as srv:
            rt = RemoteEmbeddingTable(f"127.0.0.1:{srv.port}", 872, 32, 4,
                                      seed=3)
            remote = RemoteCacheTable(rt, 8, name="obs-remote")
            # duplicate-free batches: the local cache counts per key
            # occurrence while the remote counts unique keys per sync, so
            # only dedup'd workloads compare exactly
            for keys in ([1, 2, 3], [1, 2, 9]):
                local.sync(keys)
                remote.sync(keys)
            ls, rs = local.stats(), remote.stats()
        assert list(ls) == list(rs) == ["hits", "misses", "size",
                                        "hit_rate"]
        assert ls["hits"] == rs["hits"] and ls["misses"] == rs["misses"]
        snap = reg.snapshot()
        for name in ("obs-local", "obs-remote"):
            assert snap[f'hetu_cache_hits_total{{cache="{name}"}}'] == \
                ls["hits"]
            assert snap[f'hetu_cache_misses_total{{cache="{name}"}}'] == \
                ls["misses"]
        assert snap['hetu_cache_size_rows{cache="obs-local"}'] == ls["size"]

    def test_cache_eviction_counter_derived(self):
        from hetu_tpu.embed.engine import CacheTable, HostEmbeddingTable
        cache = CacheTable(HostEmbeddingTable(64, 4), 4, name="obs-evict")
        cache.sync(np.arange(12))  # 12 misses into a 4-row cache
        st = cache.stats()
        snap = obs.get_registry().snapshot()
        assert snap['hetu_cache_evictions_total{cache="obs-evict"}'] == \
            st["misses"] - st["size"] >= 8


# ------------------------------------------------ worker heartbeat gauges

def test_simulate_workers_straggler_gauge():
    from hetu_tpu.launch import simulate_workers
    reg = obs.get_registry()
    # two plain-python workers (no jax needed): one instant, one straggling
    outs = simulate_workers(
        2, "import os, time, sys\n"
        "time.sleep(0.0 if os.environ['HETU_TPU_PROC_ID'] == '0' else 0.7)\n"
        "print('done', os.environ['HETU_TPU_PROC_ID'])",
        timeout=30.0)
    assert [o.strip().split()[-1] for o in outs] == ["0", "1"]
    snap = reg.snapshot()
    # the straggler gauge holds the final spread: worker 1 lagged ~0.7s
    assert snap["hetu_worker_straggler_seconds"] > 0.25
    assert 'hetu_worker_heartbeat_age_seconds{worker="0"}' in snap
    assert 'hetu_worker_heartbeat_age_seconds{worker="1"}' in snap


# ----------------------------------------------- chaos telemetry acceptance

@pytest.mark.chaos
def test_chaos_exact_telemetry(tmp_path):
    """Acceptance: a seeded FaultPlan run (socket kill + NaN batch +
    checkpoint corruption) produces EXACT telemetry — the redial counter
    equals the injected socket faults, the journal carries one nan_skip
    then one rollback in order, and cache hit/miss counters are identical
    across two runs with the same seed."""
    from hetu_tpu.core.module import Module
    from hetu_tpu.embed.engine import CacheTable, HostEmbeddingTable
    from hetu_tpu.embed.net import EmbeddingServer, RemoteHostEmbedding
    from hetu_tpu.layers import Linear
    from hetu_tpu.ops import binary_cross_entropy_with_logits
    reg = obs.get_registry()

    rng = np.random.default_rng(3)
    sps = [rng.integers(0, 60, (8, 4)) for _ in range(6)]
    bs = [{"sp": jnp.asarray(sp),
           "y": jnp.asarray((sp.sum(1) % 2).astype(np.float32))}
          for sp in sps]

    def run(tag, ckpt_dir):
        journal = obs.EventJournal(str(ckpt_dir) + ".journal.jsonl")
        snap0 = reg.snapshot()
        with obs.use(journal), EmbeddingServer() as srv:
            set_random_seed(0)

            class M(Module):
                def __init__(self):
                    self.embed = RemoteHostEmbedding(
                        60, 4, servers=[f"127.0.0.1:{srv.port}"],
                        table_id=895, optimizer="sgd", lr=0.1, seed=5,
                        reconnect_attempts=5, reconnect_backoff=0.01)
                    self.head = Linear(16, 1)

                def loss(self, sp, y):
                    e = self.embed(sp).reshape(sp.shape[0], -1)
                    return binary_cross_entropy_with_logits(
                        self.head(e)[:, 0], y).mean()

            m = M()
            tr = Trainer(m, SGDOptimizer(0.1),
                         lambda mm, b, k: (mm.loss(b["sp"], b["y"]), {}),
                         donate=False)
            rt = ResilientTrainer(tr, str(ckpt_dir), save_every=2, keep=4,
                                  max_consecutive_anomalies=1)
            plan = faults.FaultPlan([(2, "ps_socket_kill"),
                                    (5, "grad_nan"),
                                    (4, "ckpt_corrupt")])
            with faults.inject(plan):
                for i in range(6):
                    for mod in rt.trainer.staged_modules():
                        mod.stage(sps[i])
                    rt.step(bs[i])
            assert plan.remaining() == []  # every fault really fired
            rt.close()
            # seeded cache workload: hit/miss counters must reproduce
            cache = CacheTable(HostEmbeddingTable(64, 4, seed=1), 8,
                               name=f"chaos-{tag}")
            crng = np.random.default_rng(11)
            for _ in range(20):
                cache.sync(crng.integers(0, 64, 16))
            cache_stats = cache.stats()
        journal.close()
        delta = reg.delta(reg.snapshot(), snap0)
        return journal, delta, cache_stats

    j1, d1, s1 = run("a", tmp_path / "a")
    j2, d2, s2 = run("b", tmp_path / "b")

    for j, d in ((j1, d1), (j2, d2)):
        # exactly the injected socket faults drove redials
        redials = sum(v for k, v in d.items()
                      if k.startswith("hetu_ps_redials_total"))
        assert redials == 1
        assert sum(v for k, v in d.items() if k.startswith(
            'hetu_ps_rpc_errors_total{type="dead_socket"}')) == 1
        # one nan_skip then one rollback, in journal order
        nan_skips = j.of_kind("nan_skip")
        rollbacks = j.of_kind("rollback")
        assert len(nan_skips) == 1 and len(rollbacks) == 1
        assert nan_skips[0]["seq"] < rollbacks[0]["seq"]
        assert nan_skips[0]["step"] == 5
        # the step-4 save was corrupted, so the rollback lands on step 2
        assert rollbacks[0] == {**rollbacks[0], "at_step": 4, "to_step": 2}
        assert d["hetu_anomaly_skips_total"] == 1
        assert d["hetu_rollbacks_total"] == 1
        assert d['hetu_train_steps_total{outcome="skipped"}'] == 1
        # every durable checkpoint write journaled with integrity fields
        saved = j.of_kind("checkpoint_saved")
        assert saved and all(e["bytes"] > 0 and "crc32" in e
                             and e["duration_s"] >= 0 for e in saved)
        assert j.of_kind("ps_redial")[0]["attempt"] >= 1
        # the journal file is durable and gapless (NaN loss fields do not
        # compare equal to themselves, so match on seq/kind)
        back = obs.EventJournal.read(j.path)
        assert [(e["seq"], e["kind"]) for e in back] == \
            [(e["seq"], e["kind"]) for e in j.events]

    # identical seeded runs -> identical telemetry.  Kind multisets (not
    # sequences): the async checkpoint writer journals checkpoint_saved
    # whenever its write lands, so its interleaving with driver events is
    # timing-dependent even though the event set is exact.
    assert s1 == s2  # cache hit/miss counters, bitwise across runs
    assert sorted(e["kind"] for e in j1.events) == \
        sorted(e["kind"] for e in j2.events)
    snap = reg.snapshot()
    assert snap['hetu_cache_hits_total{cache="chaos-a"}'] == \
        snap['hetu_cache_hits_total{cache="chaos-b"}'] == s1["hits"]
    assert snap['hetu_cache_misses_total{cache="chaos-a"}'] == \
        snap['hetu_cache_misses_total{cache="chaos-b"}'] == s1["misses"]
