"""The Trinity cell rehearsed on the CPU at a tiny size: the tiny
configuration and cell (``data_trinity/``: two groups of layers, 8 of 16
experts held, the mixed generator), the repo's own entries and metric files
of the metrics the cell is listed under, and the family's five per-layer
entries laid over a temporary copy of the tiny benchmark; one traced run
that reads correct, one with the window left out of the program that does
not.

What is held of ``BENCHMARK.json`` here names no position from the end of a
list: entries added after these stand after them (``test_collect_wait.py``
and ``test_deepseek_rehearsal.py`` each held the list to end where their PR
left it; ``tests/conftest.py``, ``PERF.md`` section 7)."""

import json
import os
import shutil

import pytest

from conftest import HERE, ROOT, TINY, run_tiny

CELL = "tiny-trinity.mixed"
REAL = "trinity-mini.mixed-lengths"
OVER = os.path.join(HERE, "data_trinity")
ADD = json.load(open(os.path.join(OVER, "entries.json")))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = shutil.copytree(TINY, str(tmp_path_factory.mktemp("tr") / "r"))
    shutil.copytree(os.path.join(OVER, "benchmark"),
                    os.path.join(root, "benchmark"), dirs_exist_ok=True)
    path = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["configs"].append(ADD["config"])
    bench["workloads"].append(ADD["workload"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ADD["appended_to"] and "workloads" in m:
            m["workloads"].append(CELL)
    ours = {m["name"]: m for m in json.load(open(os.path.join(
        ROOT, "BENCHMARK.json")))["per_layer"]}
    have = {m["name"] for m in bench["per_layer"]}
    for name in ADD["per_layer"] + ADD["appended_to"]:
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
    ("mfu.serve.afmoe", "whole step", "host_clock"),
    ("gqa_paged_decode_roofline", "kernels", "device_trace"),
    ("window_flash_roofline", "kernels", "device_trace"),
    ("serve.grouped_memory_share", "kv cache", "host_clock"),
    ("afmoe_experts_roofline", "kernels", "device_trace")])
def test_the_repos_benchmark_has_the_familys_entry(name, layer, source):
    from benchmark import harness
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry == {
        "name": name, "unit": "%", "better": "higher", "source": source,
        "layer": layer, "moves": "serve_tokens_per_s", "workloads": [REAL]}
    for cell in (w["name"] for w in bench["workloads"]):
        loaded = {m["name"]: spec for m, spec in
                  harness.load_cell(ROOT, cell).per_layer}
        assert (name in loaded) == (cell == REAL)
        if name in loaded:
            assert loaded[name]["reader"].startswith(
                "benchmark.readers.afmoe:")


def test_the_cell_stands_on_seven_lists_that_were_there():
    """The cell's name once on each of seven lists, after the names that
    were there; the family's five entries after every entry of an earlier
    PR, the issue's four in its order and the experts' roofline (asked for
    in review) after them."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    listed = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if REAL in m.get("workloads", [])]
    assert set(listed) == set(ADD["appended_to"] + ADD["per_layer"])
    names = [m["name"] for m in bench["per_layer"]]
    at = [names.index(n) for n in ADD["per_layer"]]
    assert at == sorted(at) and at[0] > names.index(
        "serve.latent_memory_share")
    for name in ADD["appended_to"]:
        (m,) = [m for m in bench["end_to_end"] + bench["per_layer"]
                if m["name"] == name]
        assert m["workloads"].count(REAL) == 1
        assert m["workloads"].index(REAL) > m["workloads"].index(
            "deepseek-v2-lite.long-context")
    for n in ("mfu.serve", "paged_decode_roofline",
              "serve.live_memory_share"):     # the runner's dense counts
        assert REAL not in names and REAL not in bench["per_layer"][
            names.index(n)]["workloads"]


def test_what_the_deepseek_rehearsal_held_of_the_entries_before_these():
    """``test_deepseek_rehearsal.py`` held DeepSeek's four to be the list's
    last four and its cell each list's last name; what it held beyond the
    places still stands."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    names = [m["name"] for m in bench["per_layer"]]
    four = ["mfu.serve.deepseek_v2", "mla_decode_roofline",
            "moe_serve_experts_roofline", "serve.latent_memory_share"]
    at = names.index(four[0])
    assert names[at:at + 4] == four
    assert names[at - 1] == "serve.collect_wait_ms"
    assert bench["per_layer"][at - 1]["workloads"][:3] == [
        "cerebras-gpt-1.3b.chat", "cerebras-gpt-1.3b.decode-heavy",
        "deepseek-v2-lite.long-context"]


def test_the_configuration_holds_the_catalogs_values():
    cfg = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "trinity-mini.json")))
    (entry,) = [c for c in json.load(open(os.path.join(
        ROOT, "BENCHMARK.json")))["configs"] if c["name"] == "trinity-mini"]
    assert entry["reduced"] == ["num_hidden_layers", "layer_types",
                                "num_dense_layers", "num_experts",
                                "vocab_size"]
    assert cfg["published"] == {
        "num_hidden_layers": 32, "num_dense_layers": 2, "num_experts": 128,
        "vocab_size": 200192,
        "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 8}
    s, f = "sliding_attention", "full_attention"
    assert cfg["layer_types"] == [s, s, f, s, s, s, f, s, s]
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"],
            cfg["num_experts"], cfg["vocab_size"]) == (9, 1, 32, 50048)
    for key, value in {
            "hidden_size": 2048, "intermediate_size": 6144, "head_dim": 128,
            "num_attention_heads": 32, "num_key_value_heads": 4,
            "sliding_window": 2048, "moe_intermediate_size": 1024,
            "num_experts_per_tok": 8, "num_shared_experts": 1,
            "route_scale": 2.826, "score_func": "sigmoid",
            "route_norm": True, "mup_enabled": True, "rope_theta": 10000,
            "rms_norm_eps": 1e-05, "global_attn_every_n_layers": 4,
            "num_experts_published": 128}.items():
        assert cfg[key] == value, key
    assert cfg["held_experts"] == list(range(32))
    assert "four chips share each layer" in cfg["deployment"]
    from benchmark.reference import afmoe as ref
    assert ref.weight_bytes(cfg) == 4_302_623_744        # 4.30 GB
    wl = json.load(open(os.path.join(ROOT, "benchmark", "workloads",
                                     REAL + ".json")))
    assert wl["engine"]["num_pages"] == {"window": 1 + 64 * 17,
                                         "full": 1 + 64 * 108}
    assert wl["engine"]["max_seq_len"] == 108 * 128 == 12288 + 1536
    # what is judged, and the limit between its two readings (PERF.md
    # section 6: the program 0.015 at most, the float8 control 0.42 or more)
    assert cfg["judged_router_margin"] == 0.008
    assert wl["limits"] == {"logit_gap_max": 0.1}


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
    # weights and pages of a tiny model are nothing of 16 GB, but the
    # share is there and counts both groups
    assert 0 < got["serve.grouped_memory_share"]["value"] < 1e-1
    # no chip, no device plane: shares of a peak are left out, never 0
    assert not [n for n in got if "roofline" in n or "mfu" in n]


def test_the_adapter_saw_every_program_and_both_groups(traced):
    from benchmark.adapters import afmoe as adapter
    seen = adapter.SEEN
    assert {kind for _, kind, _ in seen.programs} == {"prefill", "decode"}
    for _, kind, info in seen.programs:
        r = info["routing"]
        # four expert layers, 8 of 16 experts held: some pairs are absent
        assert 0 < r["held"] < r["assignments"]
        if kind == "decode":
            assert len(info["contexts"]) == info["rows"] > 0
        else:
            assert r["assignments"] == 4 * 4 * info["bucket"]
    assert set(seen.pool["groups"]) == {"window", "full"}
    assert seen.held and all(set(h) == {"window", "full"}
                             for _, h in seen.held)
    # a long request holds more in the full group than a ring allows
    assert max(h["full"] for _, h in seen.held) > 3 * 3


def test_the_readers_count_by_the_family(traced):
    """On a chip the shares would be read; here their work is: the
    family's own count of what the adapter saw, not the runner's."""
    from benchmark import counts_afmoe as counts
    from benchmark.adapters import afmoe as adapter
    from benchmark.readers import afmoe as reader
    seen = adapter.SEEN
    first, last = seen.programs[0][0], seen.programs[-1][0]
    facts = {"spans": {"tick": [(first, last)]}, "window_s": last - first,
             "chips": 1, "on_chip": True}
    peaks = {"flops_bf16": 197e12, "hbm_bytes": 16e9}
    share = reader.mfu(facts, None, {}, peaks)
    prefills = [i["prompt_len"] for _, k, i in seen.programs
                if k == "prefill"]
    assert share > 100.0 * counts.serve_flops(seen.cfg, prefills, [], 0) / (
        facts["window_s"] * 197e12) > 0
    assert reader.roofline(facts, None, {"work": "window_flash"},
                           peaks) is None         # nothing traced
    assert reader.grouped_memory_share(facts, None, {}, peaks) > 0


def test_the_experts_work_is_the_programs_own_counters(traced, monkeypatch):
    """``afmoe_experts_roofline``: rows and experts hit as the programs'
    routing counters have them, prefill and decode, into the count that the
    other expert families use."""
    from benchmark import counts_afmoe as counts
    from benchmark.adapters import afmoe as adapter
    from benchmark.readers import afmoe as reader
    seen = adapter.SEEN
    got = {}
    monkeypatch.setattr(
        reader.roofline_reader, "read",
        lambda facts, reduced, params, peaks: got.update(facts["kernel_work"]))
    first, last = seen.programs[0][0], seen.programs[-1][0]
    monkeypatch.setattr(reader, "_traced", lambda facts: (first, last))
    reader.roofline({}, object(), {"work": "moe_experts"}, {})
    routed = [i["routing"] for _, _, i in seen.programs if i.get("routing")]
    kinds = {k for _, k, i in seen.programs if i.get("routing")}
    assert kinds == {"prefill", "decode"}
    m = counts.dims(seen.cfg)
    assert got["moe_experts"] == counts.grouped_experts_call(
        rows=sum(r["held"] for r in routed),
        experts_hit=sum(r["experts_hit"] for r in routed),
        hidden=m["d"], width=m["width"], backward=False)
    assert got["moe_experts"][0] == 6.0 * sum(
        r["held"] for r in routed) * 64 * 32 > 0


def test_a_program_without_the_family_reads_nothing(monkeypatch):
    """What the parent's program gives these readers: no adapter, so no
    metric and no error."""
    from benchmark.readers import afmoe as reader
    monkeypatch.setattr(reader, "_seen", lambda: None)
    facts = {"spans": {"tick": [(0.0, 1.0)]}, "window_s": 1.0, "chips": 1,
             "on_chip": True}
    peaks = {"flops_bf16": 197e12, "hbm_bytes": 16e9}
    assert reader.mfu(facts, None, {}, peaks) is None
    assert reader.roofline(facts, object(), {"work": "window_flash"},
                           peaks) is None
    assert reader.grouped_memory_share(facts, None, {}, peaks) is None


def test_counters_and_cache_groups_reach_the_registry(traced):
    from hetu_tpu.obs import get_registry
    text = get_registry().render_prometheus()
    assert 'hetu_moe_assignments_total{where="held"}' in text
    assert 'hetu_serve_cache_pages{group="window",state="held"}' in text
    assert 'hetu_serve_cache_pages{group="full",state="free"}' in text


def test_the_window_left_out_of_the_program_is_not_correct(monkeypatch,
                                                           root):
    """The decode kernel told of no window attends over all that a ring
    still holds, up to a page more than the window."""
    from hetu_tpu.ops.pallas import paged_decode
    plain = paged_decode.paged_decode_attention
    monkeypatch.setattr(
        paged_decode, "paged_decode_attention",
        lambda *a, window=None, **kw: plain(*a, window=None, **kw))
    line = run_tiny(CELL, seed=2**31 + 13, seconds=1.0, root=root)
    assert line["correct"] is False
    c = line["compared"]["logit_gap_max"]
    assert c["value"] > c["limit"]
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
