"""The DeepSeek-V2 cell rehearsed on the CPU at a tiny size: the tiny
configuration and cell (``data_deepseek/``), the repo's own entries and
metric files of the metrics the cell is listed under, and the family's four
per-layer entries laid over a temporary copy of the tiny benchmark; one
traced run that reads correct, one with the rotary left out of the program
that does not.

The four entries are the repo's own, the last four of ``BENCHMARK.json``'s
per-layer list, and the cell stands on seven lists that were there
(``data_deepseek/entries.json`` names both, as ``data_kimi/`` does).
``test_collect_wait.py`` held ``serve.collect_wait_ms`` to be that list's
last entry with the two Cerebras cells alone, which no entry added since can
leave true; what else it held of the entry is held here (``PERF.md`` section
7, ``tests/conftest.py``)."""

import json
import os
import shutil

import pytest

from conftest import HERE, ROOT, TINY, run_tiny

CELL = "tiny-deepseek.serve"
OVER = os.path.join(HERE, "data_deepseek")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = shutil.copytree(TINY, str(tmp_path_factory.mktemp("ds") / "r"))
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
    return run_tiny(CELL, seed=2**31 + 13, seconds=1.5, trace=True,
                    root=root)


@pytest.mark.parametrize("name, layer, source", [
    ("mfu.serve.deepseek_v2", "whole step", "host_clock"),
    ("mla_decode_roofline", "kernels", "device_trace"),
    ("moe_serve_experts_roofline", "kernels", "device_trace"),
    ("serve.latent_memory_share", "kv cache", "host_clock")])
def test_the_repos_benchmark_has_the_familys_entry(name, layer, source):
    """Each of the four is in ``BENCHMARK.json`` for the one cell, is what
    the harness loads for that cell and for no other, and names the
    family's reader."""
    from benchmark import harness
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry == {
        "name": name, "unit": "%", "better": "higher", "source": source,
        "layer": layer, "moves": "serve_tokens_per_s",
        "workloads": ["deepseek-v2-lite.long-context"]}
    for cell in (w["name"] for w in bench["workloads"]):
        loaded = {m["name"]: spec for m, spec in
                  harness.load_cell(ROOT, cell).per_layer}
        assert (name in loaded) == (cell == entry["workloads"][0])
        if name in loaded:
            assert loaded[name]["reader"].startswith(
                "benchmark.readers.deepseek_v2:")


def test_what_was_there_changed_by_the_cells_name_alone():
    """The parent's entries, in their places, but for this cell's name at
    the end of seven lists; the family's four entries after them."""
    add = json.load(open(os.path.join(OVER, "entries.json")))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = "deepseek-v2-lite.long-context"
    listed = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if cell in m.get("workloads", [])]
    assert listed == ["serve_tokens_per_s"] + [
        m["name"] for m in bench["per_layer"]
        if m["name"] in add["appended_to"]] + add["per_layer"]
    assert set(listed) == set(add["appended_to"] + add["per_layer"])
    assert [m["name"] for m in bench["per_layer"][-4:]] == add["per_layer"]
    wait = bench["per_layer"][-5]
    assert wait == {
        "name": "serve.collect_wait_ms", "unit": "ms", "better": "higher",
        "source": "program_span", "layer": "serving loop",
        "moves": "serve_tokens_per_s",
        "workloads": ["cerebras-gpt-1.3b.chat",
                      "cerebras-gpt-1.3b.decode-heavy", cell]}
    for name in add["appended_to"]:
        (m,) = [m for m in bench["end_to_end"] + bench["per_layer"]
                if m["name"] == name]
        assert m["workloads"][-1] == cell and m["workloads"].count(cell) == 1


def test_the_cell_reads_correct(traced):
    assert traced["correct"] is True, traced["compared"]
    assert set(traced["compared"]) == {
        "logit_gap_max", "answers_of_wrong_length",
        "tokens_outside_vocabulary"}
    assert traced["failed"] == 0 and traced["attempted"] > 0


def test_the_traced_line_has_the_cells_metrics(traced):
    got = traced["metrics"]
    for name in ("serve.tick_ms", "serve.tick_host_ms",
                 "serve.decode_build_ms", "serve.emit_ms",
                 "serve.collect_wait_ms"):
        assert got[name]["value"] >= 0, name
    # weights and latents of a tiny model are nothing of 16 GB, but the
    # share is there and counts latents: 40 values a token a layer
    assert 0 < got["serve.latent_memory_share"]["value"] < 1e-2
    # no chip, no device plane: shares of a peak are left out, never 0
    assert not [n for n in got if "roofline" in n or "mfu" in n]


def test_the_adapter_saw_every_program_with_its_routing(traced):
    from benchmark.adapters import deepseek_v2 as adapter
    ran = adapter.SEEN.programs
    kinds = {kind for _, kind, _ in ran}
    assert kinds == {"prefill", "decode"}
    for _, kind, info in ran:
        r = info["routing"]
        # two expert layers, every expert held: no pair is absent
        assert r["held"] == r["assignments"] > 0
        assert 0 < r["experts_hit"] <= 2 * 16
        if kind == "decode":
            assert info["context_tokens"] >= info["rows"] > 0
        else:
            assert r["assignments"] == 2 * 4 * info["bucket"]


def test_the_readers_count_by_the_family(traced, monkeypatch):
    """On a chip the shares would be read; here their work is: the
    family's own count of what the adapter saw, not the runner's."""
    from benchmark import counts_deepseek_v2 as counts
    from benchmark.adapters import deepseek_v2 as adapter
    from benchmark.readers import deepseek_v2 as reader
    seen = adapter.SEEN
    first, last = seen.programs[0][0], seen.programs[-1][0]
    facts = {"spans": {"tick": [(first, last)]}, "window_s": last - first,
             "chips": 1, "on_chip": True}
    peaks = {"flops_bf16": 197e12, "hbm_bytes": 16e9}
    share = reader.mfu(facts, None, {}, peaks)
    prefills = [i["prompt_len"] for _, k, i in seen.programs
                if k == "prefill"]
    assert share > 100.0 * counts.serve_flops(seen.cfg, prefills) / (
        facts["window_s"] * 197e12) > 0


def test_counters_and_cache_spec_reach_the_registry(traced):
    from hetu_tpu.obs import get_registry
    text = get_registry().render_prometheus()
    assert 'hetu_moe_assignments_total{where="held"}' in text
    assert "hetu_serve_cache_token_bytes" in text


def test_the_rotary_left_out_of_the_program_is_not_correct(monkeypatch,
                                                           root):
    from hetu_tpu.layers import mla
    monkeypatch.setattr(mla, "rotate_pairs", lambda x, positions, rope: x)
    line = run_tiny(CELL, seed=2**31 + 13, seconds=1.0, root=root)
    assert line["correct"] is False
    c = line["compared"]["logit_gap_max"]
    assert c["value"] > c["limit"]
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
