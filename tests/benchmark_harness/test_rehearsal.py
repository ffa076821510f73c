"""Both runners end to end on the CPU at a tiny size: the last line's keys,
what counts as failed, and that cells, configurations and metrics are found
as files."""

import json
import os
import shutil

import pytest

from conftest import TINY, run_tiny

KEYS = ["correct", "attempted", "failed", "metrics", "device"]
CELLS = ["tiny-bert.pretrain", "tiny-gpt2.chat", "tiny-gpt2.closed"]


@pytest.mark.parametrize("workload", CELLS)
def test_untraced_line(tiny_lines, workload):
    line, _ = tiny_lines(workload)
    assert list(line) == KEYS + ["compared"]
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    bench = json.load(open(os.path.join(TINY, "BENCHMARK.json")))
    want = {m["name"] for m in bench["end_to_end"]
            if "workloads" not in m or workload in m["workloads"]}
    assert set(line["metrics"]) == want
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    json.dumps(line)


@pytest.mark.parametrize("workload", CELLS)
def test_traced_line(tiny_lines, workload):
    _, line = tiny_lines(workload)
    assert list(line) == KEYS + ["breakdown", "compared"]
    assert line["correct"] is True, line["compared"]
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    # no chip, no device plane: shares of a peak are left out, never 0
    for name in line["metrics"]:
        assert "mfu" not in name and "roofline" not in name \
            and "idle" not in name
    assert any(n.endswith("_ms") for n in line["metrics"])


@pytest.mark.parametrize("workload", CELLS)
def test_compared_numbers_stand_beside_their_limits(tiny_lines, workload):
    for line in tiny_lines(workload):
        assert line["compared"]
        for c in line["compared"].values():
            assert set(c) == {"value", "limit"}
            assert c["value"] <= c["limit"]


def test_same_seed_same_inputs():
    from benchmark import traffic
    p = json.load(open(os.path.join(
        TINY, "benchmark", "workloads", "tiny-gpt2.chat.json")))["traffic"]
    big = 2 ** 31 + 12345
    a = traffic.poisson(p, big, 211, 3.0).timed
    b = traffic.poisson(p, big, 211, 3.0).timed
    c = traffic.poisson(p, big + 1, 211, 3.0).timed
    assert a and all(x.due_s < y.due_s < 3.0 for x, y in zip(a, a[1:]))
    assert [r.due_s for r in a] == [r.due_s for r in b] == \
        [r.due_s for r in c]
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in c]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, c))
    m = json.load(open(os.path.join(
        TINY, "benchmark", "workloads", "tiny-bert.pretrain.json")))
    x = traffic.mlm_batch(m["traffic"], big, 3, 512)
    y = traffic.mlm_batch(m["traffic"], big, 3, 512)
    z = traffic.mlm_batch(m["traffic"], big, 4, 512)
    assert all((x[k] == y[k]).all() for k in x)
    assert (x["input_ids"] != z["input_ids"]).any()
    assert ((x["mlm_labels"] >= 0).sum(axis=1) == 19).all()
    rows = {tuple(r) for r in x["input_ids"]}
    assert len(rows) == len(x["input_ids"])


def test_a_closed_loop_follows_each_finished_request():
    from benchmark import traffic
    p = json.load(open(os.path.join(
        TINY, "benchmark", "workloads", "tiny-gpt2.closed.json")))["traffic"]
    mix = traffic.closed(p, 7, 211, 1.0)
    assert [r.client for r in mix.start] == list(range(p["clients"]))
    assert not mix.timed and all(r.due_s is None for r in mix.start)
    nxt = mix.after(mix.start[2])
    assert nxt.client == 2 and nxt.index == p["clients"]
    again = traffic.closed(p, 8, 211, 1.0)
    assert [len(r.prompt) for r in again.start] == \
        [len(r.prompt) for r in mix.start]
    assert traffic.poisson(json.load(open(os.path.join(
        TINY, "benchmark", "workloads", "tiny-gpt2.chat.json")))["traffic"],
        7, 211, 1.0).after(mix.start[0]) is None


@pytest.mark.parametrize("spec", [{"dist": "constant", "value": 3},
                                  {"dist": "zipf", "min": 1, "max": 2}])
def test_an_unknown_distribution_is_an_error(spec):
    import numpy as np
    from benchmark import traffic
    with pytest.raises(ValueError):
        traffic.draw(spec, np.random.default_rng(0), 4)


def test_live_tokens_are_whole_pages_averaged_over_the_window():
    from benchmark.runners import serve
    # one request: prompt 10, tokens at 1, 2, 3 s; pages of 8; window 0-4 s
    stamps, plen = {7: [1.0, 2.0, 3.0]}, {7: 10}
    # holds 16 tokens (2 pages) from 1 to 3 s: 32 token-seconds over 4 s
    assert serve.live_tokens(stamps, plen, 8, 0.0, 4.0) == 8.0
    # clipped to the window
    assert serve.live_tokens(stamps, plen, 8, 1.5, 2.5) == 16.0
    assert serve.live_tokens({}, {}, 8, 0.0, 4.0) == 0.0


def test_a_rejected_request_counts_as_failed():
    line = run_tiny("tiny-gpt2.reject", seed=3, seconds=1.5)
    assert line["attempted"] > 0
    assert 0 < line["failed"] < line["attempted"]
    assert line["correct"] is True     # what was served was served right


def test_new_files_are_found_with_no_edit(tmp_path, monkeypatch):
    """A configuration, a workload, its generator and a per-layer metric
    dropped in as new files (and named in BENCHMARK.json) run; no file that
    was there is touched."""
    root = str(tmp_path / "root")
    shutil.copytree(TINY, root)
    before = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            before[p] = open(p, "rb").read()
    data = os.path.join(root, "benchmark")
    cfg = json.load(open(os.path.join(data, "configs", "tiny-gpt2.json")))
    cfg["n_layer"] = 1
    json.dump(cfg, open(os.path.join(data, "configs", "one-layer.json"),
                        "w"))
    wl = json.load(open(os.path.join(data, "workloads",
                                     "tiny-gpt2.closed.json")))
    wl["traffic"]["clients"] = 2
    wl["generator"] = "extra_traffic:pairs"
    (tmp_path / "extra_traffic.py").write_text(
        "from benchmark import traffic\n"
        "def pairs(params, seed, vocab, horizon_s):\n"
        "    mix = traffic.closed(params, seed, vocab, horizon_s)\n"
        "    assert len(mix.start) == 2\n"
        "    return mix\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    json.dump(wl, open(os.path.join(data, "workloads", "one-layer.pair.json"),
                       "w"))
    json.dump({"reader": "benchmark.readers.stage_share:read",
               "params": {"stage": "decode"}},
              open(os.path.join(data, "metrics", "serve.decode_share.json"),
                   "w"))
    bench_path = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(bench_path))
    bench["configs"].append({"name": "one-layer", "source": "test",
                             "file": "benchmark/configs/one-layer.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "one-layer.pair",
                               "config": "one-layer", "traffic": "pair",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append("one-layer.pair")
    bench["per_layer"].append({
        "name": "serve.decode_share", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "serving loop",
        "moves": "serve_tokens_per_s", "workloads": ["one-layer.pair"]})
    json.dump(bench, open(bench_path, "w"))
    line = run_tiny("one-layer.pair", seed=9, seconds=0.5, trace=True,
                    root=root)
    assert line["correct"] is True
    assert 0 < line["metrics"]["serve.decode_share"]["value"] <= 100
    for p, content in before.items():
        if p != bench_path:
            assert open(p, "rb").read() == content
