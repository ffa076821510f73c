"""The new cell's kernel patterns against the event texts of its own step
(``benchmark/testdata/kimi_step_events.txt``: the Pallas calls and a few
fusions of the step as the v5e compiler names them, which is what the
profiler's ``XLA Ops`` line shows): each roofline's pattern finds its own
kernel's events and no other's, and ``flash_roofline``'s, which this cell
does not report, finds none of them."""

import itertools
import json
import os

import numpy as np
import pytest

from benchmark import trace
from conftest import ROOT

EVENTS = os.path.join(ROOT, "benchmark", "testdata", "kimi_step_events.txt")
OURS = {"kda_roofline": {"kda_scan_fwd", "kda_scan_bwd"},
        "kda_scan_roofline": {"kda_scan_fwd", "kda_scan_bwd"},
        "mla_attention_roofline": {"flash_fwd", "flash_bwd_dq",
                                   "flash_bwd_dkv"},
        "moe_experts_roofline": {"gmm", "tgmm"}}
# kda_scan_roofline reads one stage of what kda_roofline reads
APART = sorted(set(OURS) - {"kda_scan_roofline"})
OTHERS = ["flash_roofline", "paged_decode_roofline"]


def _params(metric):
    return json.load(open(os.path.join(
        ROOT, "benchmark", "metrics", metric + ".json")))["params"]


@pytest.fixture(scope="module")
def step():
    names = open(EVENTS).read().splitlines()
    ids = np.arange(len(names))
    start = 10.0 + 2000.0 * ids.astype(np.float64)
    ops = trace.DeviceOps(0, names, ids, start, start + 1000.0)
    return trace.Reduced(0.0, float(start[-1] + 1000.0), [ops], {})


def _hits(step, metric):
    """The texts of the events that the metric's pattern selects."""
    p = _params(metric)
    got = set()
    for i, name in enumerate(step.devices[0].names):
        one = trace.Reduced(step.t0, step.t1, [trace.DeviceOps(
            0, [name], np.array([0]), step.devices[0].start[i:i + 1],
            step.devices[0].end[i:i + 1])], {})
        if one.kernel_seconds(all_of=p.get("all_of", ()),
                              any_of=p.get("any_of", ()))[1]:
            got.add(name)
    return got


@pytest.mark.parametrize("metric", sorted(OURS))
def test_a_pattern_finds_its_own_kernels(step, metric):
    kernels = {trace.short_name(t).split(" ")[0] for t in _hits(step, metric)}
    assert kernels == OURS[metric]


@pytest.mark.parametrize("a,b", list(itertools.combinations(
    APART + OTHERS, 2)))
def test_patterns_select_disjoint_events(step, a, b):
    assert not _hits(step, a) & _hits(step, b)


@pytest.mark.parametrize("metric", OTHERS)
def test_the_other_cells_rooflines_find_nothing_here(step, metric):
    assert not _hits(step, metric)


def test_every_pallas_call_of_the_step_is_some_metrics(step):
    pallas = {t for t in step.devices[0].names
              if 'custom_call_target="tpu_custom_call"' in t}
    assert pallas and pallas == set().union(*(_hits(step, m) for m in OURS))
    assert len(step.devices[0].names) > len(pallas)     # fusions are none's
    # by name: kda_roofline's loops are told by what they hold, above


def test_kda_roofline_takes_the_loops_that_hold_its_kernels(step):
    """The head passes' two loops (forward, backward) hold the scan kernels
    and the within-chunk fusions; the expert layer's loop holds a grouped
    matmul and no KDA kernel; ``%while.301`` is a get-tuple-element that
    only bears a loop's name."""
    from benchmark.readers import kimi_linear as reader
    names = step.devices[0].names
    at = {}
    def put(prefix, start, end):
        for i, n in enumerate(names):
            if n.startswith(prefix) and i not in at:
                at[i] = (start, end)
                return
        raise AssertionError(prefix)
    put("%while.402 = ", 0, 1000)              # forward head passes
    put("%multiply_reduce_fusion", 10, 400)    # within chunks
    put("%kda_scan_fwd", 400, 500)
    put("%kda_scan_fwd", 900, 1000)
    put("%while.410 = ", 2000, 3500)           # backward head passes
    put("%kda_scan_bwd", 3000, 3400)
    put("%while.386 = ", 4000, 5000)           # the experts' walk
    put("%gmm", 4100, 4200)
    put("%while.301 = ", 5000, 5001)
    put("%kda_scan_bwd", 6000, 6100)           # a kernel under no loop
    put("%fusion", 7000, 7500)
    ids = np.array(sorted(at))
    ops = trace.DeviceOps(0, names, ids,
                          np.array([at[i][0] for i in ids], np.float64) * 1e9,
                          np.array([at[i][1] for i in ids], np.float64) * 1e9)
    red = trace.Reduced(0.0, 8000e9, [ops], {})
    p = _params("kda_roofline")
    seconds, events = reader.held_seconds(red, p["all_of"], p["any_of"],
                                          p["enclosing"])
    assert events == 6                         # two loops, four kernels
    assert seconds == pytest.approx(1000 + 1500 + 100)
    p = _params("kda_scan_roofline")
    assert "enclosing" not in p
    assert red.kernel_seconds(p["all_of"], p["any_of"]) == (
        pytest.approx(100 + 100 + 400 + 100), 4)


def test_the_cell_is_not_on_flash_rooflines_list():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = "kimi-linear-48b-a3b.pretrain-s8192"
    lists = {m["name"]: m.get("workloads", []) for m in bench["per_layer"]}
    assert cell not in lists["flash_roofline"]
    assert all(lists[m] == [cell] for m in OURS)
