"""The new kernels' patterns against the event texts of one decode step and
one long prefill of the Trinity configuration, as the v5e compiler names
them (``benchmark/testdata/trinity_decode_events.txt`` and
``trinity_prefill_events.txt``: the Pallas calls and a few fusions of my AOT
compiles of the two programs at the cell's shapes, each text cut to its
first 2,500 characters): ``gqa_paged_decode_roofline`` and
``window_flash_roofline`` select disjoint events, each its own kernel's, and
none of the grouped product's, which ``afmoe_experts_roofline`` selects as
``moe_serve_experts_roofline`` does (the one kernel, counted by the family
whose cell reports it); on ``cerebras_decode_events.txt``
``paged_decode_roofline`` still finds its 24 a step and the new patterns
nothing."""

import json
import os

import pytest

from test_deepseek_events import ROOT, _params, np, trace

STEPS = {"decode": "trinity_decode_events.txt",
         "prefill": "trinity_prefill_events.txt",
         "cerebras": "cerebras_decode_events.txt",
         "deepseek": "deepseek_decode_events.txt"}
METRICS = ["gqa_paged_decode_roofline", "window_flash_roofline",
           "moe_serve_experts_roofline", "paged_decode_roofline",
           "mla_decode_roofline", "flash_roofline"]
# (step, metric) -> (events selected, the kernels they are)
WANT = {("decode", "gqa_paged_decode_roofline"): (9, {"gqa_paged_decode"}),
        ("decode", "window_flash_roofline"): (0, set()),
        ("decode", "moe_serve_experts_roofline"): (24, {"gmm"}),
        ("decode", "afmoe_experts_roofline"): (24, {"gmm"}),
        ("decode", "paged_decode_roofline"): (0, set()),
        ("prefill", "gqa_paged_decode_roofline"): (0, set()),
        ("prefill", "window_flash_roofline"): (
            9, {"flash_window", "flash_grouped"}),
        ("prefill", "moe_serve_experts_roofline"): (24, {"gmm"}),
        ("prefill", "afmoe_experts_roofline"): (24, {"gmm"}),
        ("prefill", "flash_roofline"): (0, set()),
        ("cerebras", "gqa_paged_decode_roofline"): (0, set()),
        ("cerebras", "window_flash_roofline"): (0, set()),
        ("cerebras", "afmoe_experts_roofline"): (0, set()),
        ("cerebras", "paged_decode_roofline"): (24, {"_paged_decode_impl"}),
        ("deepseek", "gqa_paged_decode_roofline"): (0, set()),
        ("deepseek", "window_flash_roofline"): (0, set())}


def _hits(step, metric) -> set:
    names = open(os.path.join(ROOT, "benchmark", "testdata",
                              STEPS[step])).read().splitlines()
    p, got = _params(metric), set()
    for name in names:
        one = trace.Reduced(0.0, 2000.0, [trace.DeviceOps(
            0, [name], np.array([0]), np.array([10.0]),
            np.array([1010.0]))], {})
        if one.kernel_seconds(all_of=p.get("all_of", ()),
                              any_of=p.get("any_of", ()))[1]:
            got.add(name)
    return got


@pytest.mark.parametrize("step,metric", sorted(WANT))
def test_a_pattern_finds_its_own_kernel_and_no_other(step, metric):
    n, kernels = WANT[step, metric]
    hits = _hits(step, metric)
    assert len(hits) == n
    assert {t[1:].split(" ")[0].split(".")[0] for t in hits} == kernels


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_the_patterns_select_disjoint_events(step):
    seen = set()
    for metric in METRICS:
        hits = _hits(step, metric)
        assert not hits & seen, metric
        seen |= hits


@pytest.mark.parametrize("step", ["decode", "prefill", "deepseek"])
def test_the_experts_metric_selects_what_the_accepted_one_does(step):
    assert _hits(step, "afmoe_experts_roofline") == _hits(
        step, "moe_serve_experts_roofline")
    assert not _hits(step, "afmoe_experts_roofline") & (
        _hits(step, "gqa_paged_decode_roofline")
        | _hits(step, "window_flash_roofline"))


def test_a_decode_step_has_a_call_a_layer_and_a_prefill_a_call_a_kind():
    """Seven window layers and two full layers: nine paged calls a decode
    step over head-major pools of 4 KV heads; a prefill's nine flash calls
    are seven with the window and two without."""
    decode = open(os.path.join(ROOT, "benchmark", "testdata",
                               STEPS["decode"])).read()
    assert decode.count("%gqa_paged_decode") == 9
    assert "bf16[7,1089,4,128,128]" in decode      # the window group
    assert "bf16[2,6913,4,128,128]" in decode      # the full group
    prefill = open(os.path.join(ROOT, "benchmark", "testdata",
                                STEPS["prefill"])).read().splitlines()
    starts = [t[1:].split(" ")[0].split(".")[0] for t in prefill]
    assert starts.count("flash_window") == 7
    assert starts.count("flash_grouped") == 2
    params = json.load(open(os.path.join(
        ROOT, "benchmark", "metrics", "window_flash_roofline.json")))
    assert params["params"]["work"] == "window_flash"
