"""The Kimi-Linear cell rehearsed on the CPU at a tiny size: the tiny
configuration and cell (``data_kimi/``) and the repo's own per-layer
entries and metric files laid over a temporary copy of the tiny benchmark,
one run that reads correct, one with half of each batch dropped underneath
that does not."""

import json
import os
import shutil

import pytest

from conftest import HERE, ROOT, TINY, run_tiny

CELL = "tiny-kimi.pretrain"
OVER = os.path.join(HERE, "data_kimi")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = shutil.copytree(TINY, str(tmp_path_factory.mktemp("kimi") / "r"))
    shutil.copytree(os.path.join(OVER, "benchmark"),
                    os.path.join(root, "benchmark"), dirs_exist_ok=True)
    add = json.load(open(os.path.join(OVER, "entries.json")))
    path = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["configs"].append(add["config"])
    bench["workloads"].append(add["workload"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in add["appended_to"] and "workloads" in m:
            m["workloads"].append(CELL)
    ours = {m["name"]: m for m in json.load(open(os.path.join(
        ROOT, "BENCHMARK.json")))["per_layer"]}
    have = {m["name"] for m in bench["per_layer"]}
    for name in add["per_layer"] + add["appended_to"]:
        if name in ours and name not in have:
            bench["per_layer"].append(dict(ours[name], workloads=[CELL]))
        src = os.path.join(ROOT, "benchmark", "metrics", name + ".json")
        if os.path.exists(src):
            shutil.copy(src, os.path.join(root, "benchmark", "metrics"))
    json.dump(bench, open(path, "w"))
    return root


@pytest.fixture(scope="module")
def traced(root):
    return run_tiny(CELL, seed=2**31 + 11, trace=True, root=root)


def test_the_cell_reads_correct_and_counts_its_routing(traced):
    assert traced["correct"] is True, traced["compared"]
    assert set(traced["compared"]) == {"first_loss_gap", "loss_gap",
                                       "grad_norm_gap", "change_norm_gap"}
    assert traced["failed"] == 0 and traced["attempted"] > 0
    got = traced["metrics"]
    # 4 of 16 experts held, 4 chosen a token: a quarter of the pairs if
    # the routing were uniform; random weights come near
    assert 10.0 < got["moe.held_assignment_share"]["value"] < 45.0
    assert got["moe.expert_load_max_over_mean"]["value"] >= 1.0
    assert got["train.host_step_ms"]["value"] > 0
    assert got["train.dispatch_ms"]["value"] > 0
    # no chip, no device plane: shares of a peak are left out, never 0
    assert not [n for n in got if "roofline" in n or "mfu" in n]


def test_the_counters_reach_the_registry(traced):
    from hetu_tpu.obs import get_registry
    text = get_registry().render_prometheus()
    assert 'hetu_moe_assignments_total{where="held"}' in text
    assert 'hetu_moe_assignments_total{where="absent"}' in text
    assert "hetu_moe_expert_load_max_over_mean" in text


def test_half_of_each_batch_dropped_is_not_correct(monkeypatch, root):
    from benchmark.adapters import kimi_linear as adapter
    real = adapter.System.step
    monkeypatch.setattr(adapter.System, "step", lambda self, b, k: real(
        self, {n: v[:len(v) // 2] for n, v in b.items()}, k))
    line = run_tiny(CELL, seed=2**31 + 11, root=root)
    assert line["correct"] is False
    c = line["compared"]["grad_norm_gap"]
    assert c["value"] > c["limit"]
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
