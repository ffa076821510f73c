"""No serving dispatch walks the model's tree in Python.

The engine flattens the model once, when it gets it, and feeds the step
programs its leaves, which jit flattens in C++; the compile seam
(``obs.compile.InstrumentedJit``) dispatches through jit's own C++ cache
and keys only the calls that cache misses.  So in a warmed engine a tick
runs no ``Module`` flatten at all, and each site's slow calls stand at
its compiles however many ticks run.  One case per served model."""

import sys

import numpy as np
import pytest

from hetu_tpu import obs
from hetu_tpu.core import set_random_seed
from hetu_tpu.core import module as module_mod
from hetu_tpu.models.gpt import GPT, GPTConfig
from hetu_tpu.serve import ServingEngine

pytestmark = pytest.mark.serve

SITES = ("serve.prefill_step", "serve.paged_decode", "serve.sample")
FLATTENS = {module_mod._flatten_module.__code__,
            module_mod._flatten_module_with_keys.__code__}


def gpt():
    set_random_seed(0)
    return GPT(GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                         num_heads=2, max_seq_len=64)), 97


def deepseek_v2():
    from benchmark.adapters import deepseek_v2 as adapter
    from test_deepseek_v2 import SEED, TINY
    return adapter.build_model(TINY, SEED), TINY["vocab_size"]


def afmoe():
    from benchmark.adapters import afmoe as adapter
    from test_afmoe import SEED, TINY
    return adapter.build_model(TINY, SEED), TINY["vocab_size"]


class CountFlattens:
    """Python flattens of a ``Module`` node on this thread while active:
    the registered flatten is Python, so a profile hook sees each call,
    from jit's C++ flatten too."""

    def __init__(self):
        self.n = 0

    def _hook(self, frame, event, arg):
        if event == "call" and frame.f_code in FLATTENS:
            self.n += 1

    def __enter__(self):
        sys.setprofile(self._hook)
        return self

    def __exit__(self, *exc):
        sys.setprofile(None)


@pytest.mark.parametrize("build", [gpt, deepseek_v2, afmoe],
                         ids=["gpt", "deepseek_v2", "afmoe"])
def test_a_warmed_engine_walks_no_tree_and_misses_only_to_compile(build):
    model, vocab = build()
    rng = np.random.default_rng(3)
    reg = obs.get_registry()
    before = reg.snapshot()
    eng = ServingEngine(model, num_slots=3, page_size=8, max_seq_len=64,
                        prompt_buckets=(16,), sampling="greedy", seed=5)
    warm = eng.submit(rng.integers(0, vocab, 9), 3)
    eng.run_until_idle()
    assert warm.status == "completed"
    compiled = {f.site: f.compile_count for f in
                (eng._step_fn, eng._paged_step_fn, eng._sample_fn)}
    assert compiled == dict.fromkeys(SITES, 1)

    steps = eng.stats()["lookahead"]["steps"]["in_turn"]
    handles = [eng.submit(rng.integers(0, vocab, n), 16) for n in (5, 12)]
    with CountFlattens() as walks:
        for _ in range(20):
            eng.step()
    assert [h.status for h in handles] == ["completed"] * 2
    assert eng.stats()["lookahead"]["steps"]["in_turn"] - steps >= 15
    assert walks.n == 0
    # the engine's own loop feeds a step dispatched ahead the last step's
    # tokens, a device output: the same programs, no miss
    eng.start()
    try:
        ahead = [eng.submit(rng.integers(0, vocab, 7), 12) for _ in range(2)]
        assert all(h.wait(120) for h in ahead)
    finally:
        eng.stop()
    assert eng.stats()["lookahead"]["steps"]["ahead"] > 0
    report = eng.stats()["compile"]
    d = reg.delta(reg.snapshot(), before)
    for site in SITES:
        assert report[site]["programs"] == compiled[site]
        assert report[site]["slow_calls"] == compiled[site]
        assert d[f'hetu_compile_slow_calls_total{{site="{site}"}}'] \
            == d[f'hetu_compile_total{{site="{site}"}}'] == compiled[site]


def test_the_count_sees_a_module_handed_to_jit():
    """The witness of the test above: a ``Module`` argument is walked by
    jit's flatten on every call, and the hook counts it."""
    import jax
    model, _ = gpt()
    f = jax.jit(lambda m: m.wte)
    f(model)
    with CountFlattens() as walks:
        f(model)
    assert walks.n > 0
