"""bench.py's on-TPU decision machinery, unit-tested with a stubbed timer.

The variant A/B (fused-LN on/off, flash vs xla-bhsd), the probe-reuse
rule, and the batch-48+remat trade only execute on a live chip.  The
driver's bench run must not be the first execution of the selection
logic, so it runs here against scripted timings: winner selection,
artifact fields, probe reuse (no re-measure when k matches), failure
disqualification, and both outcomes of the remat probe.
"""

import json

import pytest

import bench


class _Stub:
    """Scripted _bert_time: keyed by (attn, fused_ln, remat, batch)."""

    def __init__(self, table, fail=()):
        self.table = table
        self.fail = dict(fail)
        self.calls = []

    def __call__(self, on_tpu, kind, peak, *, seq, batch, k, attn,
                 fused_ln, remat=False):
        key = (attn, fused_ln, remat, batch)
        self.calls.append(key + (k,))
        if key in self.fail:
            raise self.fail[key]
        return {"median_s": self.table[key], "min_s": self.table[key],
                "spread": 1.0, "timing": "stub", "flops": 1e12,
                "batch": batch, "seq": seq}


@pytest.fixture
def capture(monkeypatch):
    lines = []
    monkeypatch.setattr(
        bench, "_line",
        lambda metric, value, unit, vs, **kw: lines.append(
            {"metric": metric, "value": value, **kw}) or lines[-1])
    return lines


def _run(monkeypatch, capture, stub, *, variants, remat_batch=None, k=3):
    monkeypatch.setattr(bench, "_bert_time", stub)
    bench._bert_mfu(True, "TPU v5 lite", 197e12, seq=512, batch=24, k=k,
                    variants=variants, metric="m", remat_batch=remat_batch)
    return capture[-1]


V4 = [("flash", False), ("xla", False), ("flash", True), ("xla", True)]


def test_winner_selection_and_probe_reuse(monkeypatch, capture):
    stub = _Stub({("flash", False, False, 24): 0.30,
                  ("xla", False, False, 24): 0.25,
                  ("flash", True, False, 24): 0.29,
                  ("xla", True, False, 24): 0.22})
    line = _run(monkeypatch, capture, stub, variants=V4)
    assert line["fused_ln"] is True and line["flash_attention"] is False
    assert line["ab_probe_ms"]["xla+fln"] == 220.0
    # k == probe k: the winning probe IS the measurement — 4 calls only
    assert len(stub.calls) == 4


def test_final_remeasured_when_k_differs(monkeypatch, capture):
    stub = _Stub({("xla", False, False, 24): 0.25,
                  ("xla", True, False, 24): 0.22})
    _run(monkeypatch, capture, stub,
         variants=[("xla", False), ("xla", True)], k=5)
    assert stub.calls[-1] == ("xla", True, False, 24, 5)


def test_deterministic_failure_disqualifies(monkeypatch, capture):
    stub = _Stub({("flash", False, False, 24): 0.30,
                  ("xla", False, False, 24): 0.25,
                  ("xla", True, False, 24): 0.27},
                 fail={("flash", True, False, 24): RuntimeError("Mosaic")})
    line = _run(monkeypatch, capture, stub, variants=V4)
    assert line["fused_ln"] is False and line["flash_attention"] is False
    assert line["ab_probe_ms"]["flash+fln"].startswith("failed:")


def test_remat_probe_wins_on_throughput(monkeypatch, capture):
    # 48/0.40 = 120 samples/s beats 24/0.22 = 109
    stub = _Stub({("flash", False, False, 24): 0.30,
                  ("xla", False, False, 24): 0.25,
                  ("flash", True, False, 24): 0.29,
                  ("xla", True, False, 24): 0.22,
                  ("xla", True, True, 48): 0.40})
    line = _run(monkeypatch, capture, stub, variants=V4, remat_batch=48)
    assert line["remat"] is True and line["batch"] == 48
    assert line["ab_probe_ms"]["b48+remat"] == 400.0


def test_remat_probe_loses_on_throughput(monkeypatch, capture):
    # 48/0.50 = 96 samples/s loses to 24/0.22 = 109
    stub = _Stub({("flash", False, False, 24): 0.30,
                  ("xla", False, False, 24): 0.25,
                  ("flash", True, False, 24): 0.29,
                  ("xla", True, False, 24): 0.22,
                  ("xla", True, True, 48): 0.50})
    line = _run(monkeypatch, capture, stub, variants=V4, remat_batch=48)
    assert line["remat"] is False and line["batch"] == 24


def test_remat_oom_disqualifies(monkeypatch, capture):
    stub = _Stub({("flash", False, False, 24): 0.30,
                  ("xla", False, False, 24): 0.25,
                  ("flash", True, False, 24): 0.29,
                  ("xla", True, False, 24): 0.22},
                 fail={("xla", True, True, 48):
                       RuntimeError("RESOURCE_EXHAUSTED: out of memory")})
    line = _run(monkeypatch, capture, stub, variants=V4, remat_batch=48)
    assert line["remat"] is False and line["batch"] == 24
    assert line["ab_probe_ms"]["b48+remat"].startswith("failed:")


def test_deadline_fallback_headlines_best_measured(monkeypatch, capture):
    """Satellite: when the soft deadline trips, _bert_mfu degrades to
    variants[0] with no probes — so the bert512 list must lead with the
    variant an earlier round measured fastest (the XLA bhsd core)."""
    assert bench.BERT512_VARIANTS[0] == ("xla", False)
    monkeypatch.setattr(bench, "_behind_schedule", lambda: True)
    stub = _Stub({("xla", False, False, 24): 0.25})
    line = _run(monkeypatch, capture, stub,
                variants=bench.BERT512_VARIANTS)
    # exactly one measurement: the fallback variant, no A/B probes
    assert [c[:2] for c in stub.calls] == [("xla", False)]
    assert line["flash_attention"] is False and line["fused_ln"] is False
    assert "ab_probe_ms" not in line


class TestPreflight:
    """Without a TPU the bench preflight exits 3 with the reason on
    stderr and NOTHING on stdout: a run that found no chip must never
    leave a line a driver could record as a benchmark result."""

    def test_cpu_exits_3_naming_the_platform(self, capsys):
        with pytest.raises(SystemExit) as ei:
            bench._require_tpu()
        assert ei.value.code == bench.PREFLIGHT_RC == 3
        out, err = capsys.readouterr()
        assert out == ""
        assert "PREFLIGHT FAILED" in err and "'cpu'" in err
        assert "not a perf regression" in err


class TestServeMode:
    """--mode serve machinery that must not first run on a live chip:
    histogram quantiles and the CLI mode gate."""

    def test_hist_quantile_interpolates(self):
        before = [(0.1, 0), (0.5, 0), (1.0, 0), (float("inf"), 0)]
        after = [(0.1, 2), (0.5, 6), (1.0, 10), (float("inf"), 10)]
        # p50: rank 5 lands in the (0.1, 0.5] bucket (2 -> 6): linear
        assert bench._hist_quantile(before, after, 0.5) == pytest.approx(
            0.1 + 0.4 * (5 - 2) / 4)
        # p99 lands in the (0.5, 1.0] bucket
        assert 0.5 < bench._hist_quantile(before, after, 0.99) <= 1.0

    def test_hist_quantile_inf_bucket_reports_lower_edge(self):
        before = [(0.1, 0), (float("inf"), 0)]
        after = [(0.1, 0), (float("inf"), 4)]
        assert bench._hist_quantile(before, after, 0.5) == 0.1

    def test_hist_quantile_empty_delta_is_nan(self):
        cum = [(0.1, 3), (float("inf"), 7)]
        v = bench._hist_quantile(cum, cum, 0.5)
        assert v != v  # nan, deterministically — never a fake latency
        assert bench._q_or_none(v) is None  # and null on the JSON line

    def test_unknown_mode_exits_before_preflight(self, monkeypatch):
        probed = []
        monkeypatch.setattr(bench, "_require_tpu",
                            lambda *a, **k: probed.append(1))
        monkeypatch.setattr(bench.sys, "argv", ["bench.py", "--mode", "fly"])
        with pytest.raises(SystemExit, match="unknown mode"):
            bench.main()
        monkeypatch.setattr(bench.sys, "argv", ["bench.py", "--mode"])
        with pytest.raises(SystemExit, match="--mode needs"):
            bench.main()
        monkeypatch.setattr(bench.sys, "argv",
                            ["bench.py", "--mode", "serve", "resnet"])
        with pytest.raises(SystemExit, match="takes no config"):
            bench.main()
        assert probed == []  # usage errors never touch the backend

    def test_ctr_mode_cli_gate_and_preflight(self, monkeypatch):
        """--mode ctr: usage errors exit before the preflight; the tiered
        A/B runs BEHIND it (a run without a chip must never record a
        bogus vs_baseline round or calibration baseline)."""
        probed = []
        monkeypatch.setattr(bench, "_require_tpu",
                            lambda *a, **k: probed.append(1))
        for argv, msg in ((["--mode", "ctr", "--embedding", "paged"],
                           "unknown embedding"),
                          (["--mode", "ctr", "--embedding"],
                           "--embedding needs"),
                          (["--mode", "ctr", "--storage", "f64"],
                           "unknown storage"),
                          (["--mode", "ctr", "resnet"],
                           "takes no config")):
            monkeypatch.setattr(bench.sys, "argv", ["bench.py"] + argv)
            with pytest.raises(SystemExit, match=msg):
                bench.main()
        assert probed == []  # usage errors never touch the backend

        order = []
        monkeypatch.setattr(bench, "_require_tpu",
                            lambda *a, **k: order.append("preflight"))
        monkeypatch.setattr(
            bench, "bench_ctr_tiered",
            lambda on_tpu, kind, peak, storage: order.append(
                f"tiered:{storage}"))
        monkeypatch.setattr(bench.sys, "argv",
                            ["bench.py", "--mode", "ctr", "--embedding",
                             "tiered", "--storage", "int8"])
        bench.main()
        assert order == ["preflight", "tiered:int8"]

        def dead(*a, **k):
            raise SystemExit(bench.PREFLIGHT_RC)

        monkeypatch.setattr(bench, "_require_tpu", dead)
        order.clear()
        with pytest.raises(SystemExit) as ei:
            bench.main()
        assert ei.value.code == bench.PREFLIGHT_RC and order == []

    def test_memory_section_from_snapshot(self):
        """The serve line's memory section: max peak occupancy across
        pools, shared-prefix fraction of the pages held at peak, and the
        ledger's total high-water mark."""
        snap = {"kv_pools": {
                    "0": {"peak_used_pages": 6, "peak_shared_pages": 3,
                          "peak_used_fraction": 0.75},
                    "1": {"peak_used_pages": 2, "peak_shared_pages": 0,
                          "peak_used_fraction": 0.25}},
                "hwm_bytes": {"total": 4096}}
        assert bench._memory_section(snap) == {
            "peak_pool_occupancy": 0.75,
            "shared_prefix_fraction": 0.375,  # 3 / 8 pages at peak
            "hwm_bytes": 4096}
        # an idle run (no pools touched) degrades to zeros, not a crash
        assert bench._memory_section(
            {"kv_pools": {}, "hwm_bytes": {"total": 0}}) == {
            "peak_pool_occupancy": 0.0, "shared_prefix_fraction": 0.0,
            "hwm_bytes": 0}

    def test_serve_line_carries_memory_section(self, monkeypatch, capture):
        """bench_serve threads the paged run's ledger-derived memory
        section into the JSON line verbatim."""
        mem = {"peak_pool_occupancy": 0.5, "shared_prefix_fraction": 0.0,
               "hwm_bytes": 1024}
        monkeypatch.setattr(
            bench, "_serve_run",
            lambda cfg, trace, *, paged, **kw:
                (10.0 if paged else 8.0, 0.01, 0.02, 8, {},
                 mem if paged else {"hwm_bytes": -1}))
        bench.bench_serve(False, "cpu", 0.0)
        assert capture[-1]["metric"] == "serve_decode_tokens_per_sec"
        assert capture[-1]["memory"] == mem

    def test_serve_mode_runs_behind_preflight(self, monkeypatch, capture):
        """--mode serve goes through the SAME fast-fail preflight as the
        training configs: no chip means rc=3 and NO stdout metric."""
        order = []
        monkeypatch.setattr(
            bench, "_require_tpu",
            lambda *a, **k: order.append("preflight"))
        monkeypatch.setattr(
            bench, "bench_serve",
            lambda on_tpu, kind, peak: order.append("serve"))
        monkeypatch.setattr(bench.sys, "argv", ["bench.py", "--mode",
                                                "serve"])
        bench.main()
        assert order == ["preflight", "serve"]

        def dead(*a, **k):
            raise SystemExit(bench.PREFLIGHT_RC)

        monkeypatch.setattr(bench, "_require_tpu", dead)
        order.clear()
        with pytest.raises(SystemExit) as ei:
            bench.main()
        assert ei.value.code == bench.PREFLIGHT_RC and order == []
