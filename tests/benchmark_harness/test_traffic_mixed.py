"""``benchmark.traffic_mixed.closed_mixed``: the classes' shares, the same
sizes for every ``--seed``, and the key the runner's ``warm_up`` reads."""

import json
import os

import numpy as np
import pytest

from benchmark import traffic, traffic_mixed
from conftest import ROOT

CELL = json.load(open(os.path.join(
    ROOT, "benchmark", "workloads", "trinity-mini.mixed-lengths.json")))
PARAMS = CELL["traffic"]


def test_the_classes_shares_over_4096_draws():
    kind, prompt, answer = traffic_mixed.mixed_sizes(PARAMS)
    assert len(kind) == traffic.CLOSED_SIZES == 4096
    share_long = float(np.mean(kind == 1))
    assert abs(share_long - 0.25) < 0.02          # a quarter are long
    short, long = prompt[kind == 0], prompt[kind == 1]
    assert short.min() >= 128 and short.max() <= 1024
    assert long.min() >= 8192 and long.max() <= 12288
    assert abs(short.mean() - 576) < 30 and abs(long.mean() - 10240) < 150
    assert answer.min() >= 512 and answer.max() <= 1536
    # every request fits the engine: the longest prompt and answer
    assert prompt.max() + answer.max() <= CELL["engine"]["max_seq_len"]
    assert prompt.max() <= max(CELL["engine"]["prompt_buckets"])


@pytest.mark.parametrize("seed", [1, 2**31 + 5, 3200000101])
def test_every_seed_offers_the_same_sizes_and_its_own_ids(seed):
    a = traffic_mixed.closed_mixed(PARAMS, seed, 50048, 55.0)
    b = traffic_mixed.closed_mixed(PARAMS, 7, 50048, 55.0)
    assert len(a.start) == 64 and not a.timed
    assert [len(r.prompt) for r in a.start] == [len(r.prompt)
                                                for r in b.start]
    assert [r.max_new for r in a.start] == [r.max_new for r in b.start]
    assert [r.client for r in a.start] == list(range(64))
    assert any((x.prompt != y.prompt).any()
               for x, y in zip(a.start, b.start))
    assert all(0 <= r.prompt.min() and r.prompt.max() < 50048
               for r in a.start)
    nxt, other = a.after(a.start[3]), b.after(b.start[3])
    assert nxt.index == 64 and nxt.client == 3 and nxt.due_s is None
    assert len(nxt.prompt) == len(other.prompt)
    again = traffic_mixed.closed_mixed(PARAMS, seed, 50048, 55.0)
    assert all((x.prompt == y.prompt).all()
               for x, y in zip(a.start, again.start))


def test_warm_ups_key_spans_every_class_and_picks_all_six_buckets():
    lo, hi = PARAMS["prompt_tokens"]["min"], PARAMS["prompt_tokens"]["max"]
    assert (lo, hi) == (128, 12288)
    warmed, prev = [], 0
    for b in CELL["engine"]["prompt_buckets"]:     # the runner's rule
        if prev < hi and b >= lo:
            warmed.append(b)
        prev = b
    assert warmed == [512, 1024, 9216, 10240, 11264, 12288]
    assert CELL["generator"] == "benchmark.traffic_mixed:closed_mixed"


@pytest.mark.parametrize("change,what", [
    ({"classes": [dict(PARAMS["classes"][0], share=0.5),
                  PARAMS["classes"][1]]}, "add up"),
    ({"prompt_tokens": {"min": 128, "max": 4096}}, "outside the mix")])
def test_a_mix_that_does_not_add_up_is_refused(change, what):
    with pytest.raises(ValueError, match=what):
        traffic_mixed.mixed_sizes(dict(PARAMS, **change))
