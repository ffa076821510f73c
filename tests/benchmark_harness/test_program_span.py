"""The reader of the program's own spans: its arithmetic on made-up spans,
its cut to one run's traced stretch, and the metrics it puts into the
traced lines of the tiny cells."""

import json
import os
import shutil
import types

import pytest

from benchmark.readers import program_span
from conftest import ROOT, TINY, run_tiny

SERVING = ["serve.tick_host_ms", "serve.decode_build_ms", "serve.emit_ms"]
CHAT_ONLY = ["serve.prefill_stall_ms", "serve.submit_wait_ms"]
NEW = {"tiny-gpt2.chat": SERVING + CHAT_ONLY, "tiny-gpt2.closed": SERVING,
       "tiny-bert.pretrain": ["train.dispatch_ms"]}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tiny cells in a directory of this file's own, with the new
    metrics laid over them as the repo's own benchmark has them: its metric
    files, and its ``per_layer`` entries less their ``workloads``, so that
    each is read wherever the end-to-end metric it moves is reported.  (The
    rehearsal tests run the same cells from ``TINY`` on another worker, and
    a cell's trace directory lies under its root.)"""
    root = shutil.copytree(TINY, str(tmp_path_factory.mktemp("tiny") / "r"))
    names = {n for new in NEW.values() for n in new}
    path = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(path))
    for m in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["per_layer"]:
        if m["name"] in names:
            bench["per_layer"].append({k: v for k, v in m.items()
                                       if k != "workloads"})
            shutil.copy(os.path.join(ROOT, "benchmark", "metrics",
                                     m["name"] + ".json"),
                        os.path.join(root, "benchmark", "metrics"))
    assert {m["name"] for m in bench["per_layer"]} >= names
    json.dump(bench, open(path, "w"))
    return root


@pytest.fixture(scope="module")
def lines(root):
    """workload -> (untraced line, traced line), each run once."""
    cache = {}

    def get(workload):
        if workload not in cache:
            cache[workload] = (run_tiny(workload, root=root),
                               run_tiny(workload, seed=2**31 + 7, trace=True,
                                        root=root))
        return cache[workload]
    return get


def span(name, sid, parent, start, end):
    return types.SimpleNamespace(name=name, span_id=sid, parent_id=parent,
                                 start=start, end_time=end)


def two_ticks():
    """A tick of 10 with a prefill (2 to 6, on the device 3 to 5), a build
    (6 to 7), the device (7 to 9) and an emit (9 to 9.5); a tick of 4 with
    a build (20 to 21) and the device (21 to 24) only; a wait of 3 and one
    of 1 on another thread."""
    return [
        span("serve.tick", "a", None, 0.0, 10.0),
        span("serve.tick.prefill", "a1", "a", 2.0, 6.0),
        span("serve.tick.prefill.device", "a11", "a1", 3.0, 5.0),
        span("serve.tick.decode.build", "a2", "a", 6.0, 7.0),
        span("serve.tick.decode.device", "a3", "a", 7.0, 9.0),
        span("serve.tick.emit", "a4", "a", 9.0, 9.5),
        span("serve.tick", "b", None, 20.0, 24.0),
        span("serve.tick.decode.build", "b1", "b", 20.0, 21.0),
        span("serve.tick.decode.device", "b2", "b", 21.0, 24.0),
        span("serve.submit.wait", "w1", None, 1.0, 4.0),
        span("serve.submit.wait", "w2", None, 21.0, 22.0),
    ]


def read(spans, **params):
    return program_span.read({"program_spans": spans}, None, params, {})


def test_self_time_is_the_length_less_the_named_descendants():
    # 10 - (2 + 2) and 4 - 3, grandchildren too
    got = program_span.values(two_ticks(), {"span": "serve.tick",
                                            "minus": ".device"})
    assert got == [6.0, 1.0]
    assert read(two_ticks(), span="serve.tick", minus=".device",
                stat="mean") == 3500.0


def test_mean_per_parent_counts_the_parents_without_any():
    # the emit of the one tick that has it, over both ticks
    assert read(two_ticks(), span="serve.tick.emit", per="serve.tick",
                stat="mean") == 250.0
    assert read(two_ticks(), span="serve.tick.decode.build",
                per="serve.tick", stat="mean") == 1000.0
    # a grandchild is summed by its tick as well
    assert program_span.values(two_ticks(), {
        "span": "serve.tick.prefill.device", "per": "serve.tick"}) == \
        [2.0, 0.0]


def test_mean_and_max_of_the_spans_themselves():
    assert read(two_ticks(), span="serve.submit.wait", stat="max") == 3000.0
    assert read(two_ticks(), span="serve.submit.wait", stat="mean") == 2000.0
    assert read(two_ticks(), span="serve.tick.prefill", stat="mean") == 4000.0


@pytest.mark.parametrize("params", [
    {"span": "train.step.dispatch", "stat": "mean"},
    {"span": "serve.tick.ingest", "per": "serve.tick", "stat": "mean"},
    {"span": "serve.tick.emit", "per": "train.step", "stat": "max"}])
def test_nothing_to_read_is_none_never_zero(params):
    assert read(two_ticks(), **params) is None
    assert read([], **params) is None


def test_the_cut_keeps_whole_chains_that_begin_in_the_stretch():
    spans = two_ticks()
    # from 5 on: the first tick began before, so its children go with it;
    # the first wait too
    kept = program_span.since(spans, 5.0)
    assert sorted(s.span_id for s in kept) == ["b", "b1", "b2", "w2"]
    # a child whose parent was never recorded (a tick cut by the session's
    # end is dropped by the tracer) does not count either
    orphan = spans + [span("serve.tick.emit", "c4", "c", 30.0, 31.0)]
    assert "c4" not in [s.span_id for s in program_span.since(orphan, 0.0)]
    assert len(program_span.since(spans, 0.0)) == len(spans)


def test_a_run_is_given_no_span_of_the_run_before(monkeypatch):
    """Several cells in one process: the window's last span cuts, and where
    a window holds none, what an earlier run was given does."""
    buffer = []
    monkeypatch.setattr(program_span.tracing, "get_tracer",
                        lambda: types.SimpleNamespace(spans=list(buffer)))
    monkeypatch.setattr(program_span, "_given_until", float("-inf"))
    buffer += [span("train.step", "s1", None, 5.0, 6.0),
               span("train.step.dispatch", "d1", "s1", 5.0, 5.5)]
    first = {"spans": {"train_step": [(0.0, 1.0), (1.0, 2.0)]}}
    assert [s.span_id for s in program_span.traced_spans(first)] == \
        ["s1", "d1"]
    buffer += [span("serve.tick", "t2", None, 9.0, 10.0),
               span("serve.tick.emit", "e2", "t2", 9.5, 10.0)]
    second = {"spans": {"tick": []}}
    assert [s.span_id for s in program_span.traced_spans(second)] == \
        ["t2", "e2"]
    # found once a run: the next metric of the same run reads the same
    buffer += [span("serve.tick", "t3", None, 11.0, 12.0)]
    assert [s.span_id for s in program_span.traced_spans(second)] == \
        ["t2", "e2"]
    third = {"spans": {"tick": [(10.5, 11.5)]}}
    assert program_span.traced_spans(third) == []


@pytest.mark.parametrize("workload", sorted(NEW))
def test_traced_lines_carry_the_new_metrics_and_untraced_do_not(lines, root,
                                                                workload):
    untraced, traced = lines(workload)
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    units = {m["name"]: m["unit"] for m in bench["per_layer"]}
    for name in NEW[workload]:
        assert name not in untraced["metrics"]
        assert traced["metrics"][name]["unit"] == units[name] == "ms"
        assert traced["metrics"][name]["value"] > 0
    others = {n for names in NEW.values() for n in names} - set(NEW[workload])
    assert not others & set(traced["metrics"])
    # what timed the same layers from outside is still there
    old = "train.host_step_ms" if workload == "tiny-bert.pretrain" \
        else "serve.tick_ms"
    assert traced["metrics"][old]["value"] > 0


def test_the_second_cell_of_a_process_counts_none_of_the_first(monkeypatch,
                                                               root):
    from hetu_tpu.obs import tracing
    given = []
    real = program_span.since

    def spy(spans, after):
        given.append(real(spans, after))
        return given[-1]

    monkeypatch.setattr(program_span, "since", spy)
    first = run_tiny("tiny-gpt2.closed", seed=11, trace=True, root=root)
    before = {id(s) for s in tracing.get_tracer().spans}
    second = run_tiny("tiny-gpt2.closed", seed=12, trace=True, root=root)
    assert len(given) == 2 and given[0] and given[1]
    assert {id(s) for s in given[0]} <= before
    assert not {id(s) for s in given[1]} & before
    # each run's ticks are its own: the means are of other spans
    ticks = [sum(1 for s in g if s.name == "serve.tick") for g in given]
    assert all(n > 0 for n in ticks)
    assert sum(ticks) <= sum(1 for s in tracing.get_tracer().spans
                             if s.name == "serve.tick")
    for line in (first, second):
        assert line["metrics"]["serve.tick_host_ms"]["value"] > 0
