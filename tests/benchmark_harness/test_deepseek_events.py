"""The serving kernels' patterns against the event texts of one decode step
of each serving configuration, as the v5e compiler names them
(``benchmark/testdata/deepseek_decode_events.txt`` and
``cerebras_decode_events.txt``: the Pallas calls and a few fusions of the
compiled step, which is what the profiler's ``XLA Ops`` line shows):
``mla_decode_roofline``, ``moe_serve_experts_roofline`` and
``paged_decode_roofline`` select disjoint events, each its own kernel's,
and the last still finds its 24 a Cerebras step."""

import json
import os

import numpy as np
import pytest

from benchmark import trace
from conftest import ROOT

STEPS = {"deepseek": "deepseek_decode_events.txt",
         "cerebras": "cerebras_decode_events.txt"}
METRICS = ["mla_decode_roofline", "moe_serve_experts_roofline",
           "paged_decode_roofline"]
# (step, metric) -> (events selected, the kernel they are)
WANT = {("deepseek", "mla_decode_roofline"): (6, "paged_mla_decode"),
        ("deepseek", "moe_serve_experts_roofline"): (15, "gmm"),
        ("deepseek", "paged_decode_roofline"): (0, None),
        ("cerebras", "mla_decode_roofline"): (0, None),
        ("cerebras", "moe_serve_experts_roofline"): (0, None),
        ("cerebras", "paged_decode_roofline"): (24, "_paged_decode_impl")}


def _params(metric):
    return json.load(open(os.path.join(
        ROOT, "benchmark", "metrics", metric + ".json")))["params"]


def _hits(step, metric) -> set:
    """The texts of the step's events that the metric's pattern selects."""
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
    n, kernel = WANT[step, metric]
    hits = _hits(step, metric)
    assert len(hits) == n
    assert {t[1:].split(" ")[0].split(".")[0] for t in hits} == (
        {kernel} if kernel else set())


@pytest.mark.parametrize("step", sorted(STEPS))
def test_the_three_select_disjoint_events(step):
    seen = set()
    for metric in METRICS:
        hits = _hits(step, metric)
        assert not hits & seen, metric
        seen |= hits
    # the fused sampler is a Pallas call of both steps and is nobody's
    assert not [t for t in seen if "lm_head_sample" in t]


def test_a_latent_page_is_no_five_dimensional_pool():
    """What keeps ``paged_decode_roofline`` off the new step whatever its
    kernels are called: the latent pool has four dimensions."""
    text = open(os.path.join(ROOT, "benchmark", "testdata",
                             STEPS["deepseek"])).read()
    assert "bf16[6,3841,576,128]" in text
    assert "bf16[24,513,64,16,128]" in open(os.path.join(
        ROOT, "benchmark", "testdata", STEPS["cerebras"])).read()
