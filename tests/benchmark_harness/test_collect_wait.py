"""``serve.collect_wait_ms``: the program's ``serve.tick.collect.device``
spans (each blocking fetch of a program's tokens), summed by tick.  Read by
the reader that was there, from its entry and its file laid over a copy of
the tiny cells; a program that records no such span, as one older than the
look-ahead does not, reads ``None`` and its line leaves the metric out."""

import json
import os
import shutil
import types

import pytest

from benchmark.readers import program_span
from conftest import ROOT, TINY, run_tiny

NAME = "serve.collect_wait_ms"
SPAN = "serve.tick.collect.device"
CELLS = ["tiny-gpt2.chat", "tiny-gpt2.closed"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tiny cells in a directory of this file's own, with the repo's
    entry (less its ``workloads``) and the repo's metric file laid over."""
    root = shutil.copytree(TINY, str(tmp_path_factory.mktemp("tiny") / "r"))
    path = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(path))
    entry, = [m for m in json.load(open(os.path.join(
        ROOT, "BENCHMARK.json")))["per_layer"] if m["name"] == NAME]
    bench["per_layer"].append({k: v for k, v in entry.items()
                               if k != "workloads"})
    shutil.copy(os.path.join(ROOT, "benchmark", "metrics", NAME + ".json"),
                os.path.join(root, "benchmark", "metrics"))
    json.dump(bench, open(path, "w"))
    return root


def params():
    return json.load(open(os.path.join(
        ROOT, "benchmark", "metrics", NAME + ".json")))["params"]


def span(name, sid, parent, start, end):
    return types.SimpleNamespace(name=name, span_id=sid, parent_id=parent,
                                 start=start, end_time=end)


def ticks(with_collect):
    """A tick of 10 that fetches a decode step (1 to 4) and a prefill (8 to
    9), and a tick of 4 that fetches nothing."""
    spans = [
        span("serve.tick", "a", None, 0.0, 10.0),
        span("serve.tick.decode.device", "a1", "a", 0.5, 1.0),
        span(SPAN, "a2", "a", 1.0, 4.0),
        span("serve.tick.emit", "a3", "a", 4.0, 5.0),
        span(SPAN, "a4", "a", 8.0, 9.0),
        span("serve.tick", "b", None, 20.0, 24.0),
        span("serve.tick.decode.device", "b1", "b", 20.0, 21.0),
    ]
    return [s for s in spans if with_collect or s.name != SPAN]


def test_the_entry_is_the_issues_and_names_the_reader_that_was_there():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert bench["per_layer"][-1] == {
        "name": NAME, "unit": "ms", "better": "higher",
        "source": "program_span", "layer": "serving loop",
        "moves": "serve_tokens_per_s",
        "workloads": ["cerebras-gpt-1.3b.chat",
                      "cerebras-gpt-1.3b.decode-heavy"]}
    spec = json.load(open(os.path.join(ROOT, "benchmark", "metrics",
                                       NAME + ".json")))
    assert spec["reader"] == "benchmark.readers.program_span:read"
    assert spec["params"] == {"span": SPAN, "per": "serve.tick",
                              "stat": "mean"}


@pytest.mark.parametrize("with_collect, want", [(True, 2000.0),
                                                (False, None)])
def test_summed_by_tick_and_none_without_the_span(with_collect, want):
    # (3 + 1) in one tick and nought in the other, over two ticks
    got = program_span.read({"program_spans": ticks(with_collect)}, None,
                            params(), {})
    assert got == want
    # the fetch is the device's time, not the host's: it leaves the tick's
    # own work with the other spans named *.device
    host = program_span.read(
        {"program_spans": ticks(with_collect)}, None,
        {"span": "serve.tick", "minus": ".device", "stat": "mean"}, {})
    assert host == (4250.0 if with_collect else 6250.0)


@pytest.mark.parametrize("workload", CELLS)
def test_the_traced_line_of_a_started_engine_carries_it(root, workload):
    untraced = run_tiny(workload, root=root)
    traced = run_tiny(workload, seed=2**31 + 11, trace=True, root=root)
    assert NAME not in untraced["metrics"]
    got = traced["metrics"][NAME]
    assert got["unit"] == "ms" and got["value"] > 0
    assert traced["correct"] and traced["failed"] == 0


def test_a_program_without_the_span_leaves_the_metric_out(root, monkeypatch):
    """What the parent commit gives: every other span, and not this one."""
    real = program_span.since
    monkeypatch.setattr(
        program_span, "since",
        lambda spans, after: [s for s in real(spans, after)
                              if s.name != SPAN])
    traced = run_tiny(CELLS[1], seed=2**31 + 12, trace=True, root=root)
    assert NAME not in traced["metrics"]
    assert traced["metrics"]["serve.tick_ms"]["value"] > 0
    assert traced["correct"]
