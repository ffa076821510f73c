"""The trace reduction against one small recorded v5e trace (three BERT
steps at a small size, cut to the lines that the reduction reads), and the
serving kernels' patterns against the event texts of a recorded serving
trace."""

import os

import numpy as np
import pytest

from benchmark import trace

DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmark", "testdata")
TRAIN = os.path.join(DATA, "bert_small.xplane.pb")
SERVE_EVENTS = os.path.join(DATA, "serve_pallas_events.txt")
PALLAS = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def train():
    return trace.reduce_trace(TRAIN)


@pytest.fixture(scope="module")
def serve():
    """The Pallas events of two decode ticks of a two-layer server, by the
    texts that the v5e trace gave them, 1 us each."""
    names = open(SERVE_EVENTS).read().splitlines()
    ids = np.array(list(range(len(names))) * 2)
    start = 10.0 + 2000.0 * np.arange(len(ids), dtype=np.float64)
    ops = trace.DeviceOps(0, names, ids, start, start + 1000.0)
    return trace.Reduced(0.0, float(start[-1] + 1000.0), [ops], {})


@pytest.mark.parametrize("starts,ends,want", [
    ([0, 10], [5, 15], 10.0), ([0, 2], [5, 3], 5.0), ([0, 5], [5, 9], 9.0),
    ([3, 0], [4, 1], 2.0), ([0], [0], 0.0), ([], [], 0.0)])
def test_union(starts, ends, want):
    assert trace.union_seconds(np.array(starts, float),
                               np.array(ends, float)) == want


@pytest.mark.parametrize("text,want", [
    ("%fusion.12 = bf16[8,128]{1,0:T(8,128)} fusion(x)", "fusion bf16[8,128]"),
    ("%copy-done.3 = f32[4]{0} copy-done(y)", "copy-done f32[4]"),
    ("%jvp__.2 = (bf16[4,16,512,64]{3,2,1,0}, f32[4]) custom-call(a)",
     "jvp__ bf16[4,16,512,64]"),
    ("plain", "plain")])
def test_short_name(text, want):
    assert trace.short_name(text) == want


def test_window_without_a_window_span_is_the_device_events_hull(train):
    # the recorded trace predates bench.window: first to last device event
    assert 0.01 < train.window_s < 0.1
    assert len(train.devices) == 1


def test_train_busy_is_a_union_not_a_sum(train):
    d = train.devices[0]
    summed = float((d.end - d.start).sum()) / 1e9
    assert 0 < train.busy_s <= train.window_s
    assert train.busy_s <= summed + 1e-9
    assert 0.0 <= train.idle_share < 1.0


def test_flash_kernels_found_by_target_and_name(train):
    secs, n = train.kernel_seconds(all_of=[PALLAS],
                                   any_of=[r"^%(transpose_)?jvp_"])
    # 3 traced steps x 2 layers x (1 forward + 1 backward) kernels
    assert n == 12
    assert 0 < secs < train.busy_s
    fwd, nf = train.kernel_seconds(all_of=[PALLAS], any_of=[r"^%jvp_"])
    assert nf == 6 and 0 < fwd < secs


def test_no_match_reads_nothing(train):
    assert train.kernel_seconds(all_of=["no such kernel"]) == (0.0, 0)


def test_paged_decode_kernel_apart_from_the_sampler(serve):
    pool = [r"^%_paged_decode_impl.*bf16\[\d+,\d+,\d+,\d+,\d+\]"]
    secs, n = serve.kernel_seconds(all_of=[PALLAS], any_of=pool)
    every, n_all = serve.kernel_seconds(all_of=[PALLAS])
    assert n == 4 and secs == pytest.approx(4e-6)   # two layers a tick
    assert n_all == 6 and every > secs   # the fused sampler is Pallas too


def test_top_ops_and_gaps(train):
    top = train.top_ops(10)
    assert 0 < len(top) <= 10
    assert all(isinstance(n, str) and s > 0 for n, s in top)
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    gaps = train.idle_gaps(("bench.train_step",), 10)
    assert gaps and len(gaps) <= 10
    idle = train.window_s - train.busy_s
    assert abs(sum(s for _, s in gaps) - idle) < 1e-6
    assert {w for w, _ in gaps} <= {"inside bench.train_step",
                                   "between bench.train_step"}


def test_window_from_spans():
    red = trace.reduce_trace(TRAIN, window_from="bench.train_step")
    spans = red.host_spans["bench.train_step"]
    assert len(spans) == 3
    assert red.t0 == spans[0][0] and red.t1 == max(b for _, b in spans)
    for d in red.devices:
        assert (d.start >= red.t0).all() and (d.end <= red.t1).all()
