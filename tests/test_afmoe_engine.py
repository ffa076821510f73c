"""(e) AFMoE (Trinity) through ``ServingEngine`` at a tiny size on the CPU:
streams judged as the serving runner judges them, same-seed replay bitwise,
zero gather views, the engine's own loop, and the models that were there
stating one group."""

import numpy as np
import pytest

from hetu_tpu.serve import ServingEngine, kv_cache
from test_afmoe import (ENGINE, LIMIT, PAGE, SEED, TINY, WINDOW,
                        model, prompts)  # noqa: F401  (model: the fixture)

pytestmark = pytest.mark.pallas

def serve(model, lengths=(5, 16, 23, 40), new=12, **kw):
    eng = ServingEngine(model, **{**ENGINE, **kw})
    ps = prompts(lengths)
    handles = [eng.submit(p, new) for p in ps]
    eng.run_until_idle()
    assert all(h.status == "completed" for h in handles)
    return eng, ps, handles


def test_streams_judged_as_the_runner_judges_them(model):
    from benchmark.runners.serve import served_gaps
    kv_cache.reset_gather_view_count()
    eng, ps, handles = serve(model)
    assert kv_cache.gather_view_count() == 0      # no program gathers
    sample = [(p, np.asarray(h.tokens)) for p, h in zip(ps, handles)]
    gaps = served_gaps(TINY, SEED, sample, pad_to=64, rank=1)
    assert max(g.max() for g in gaps) <= LIMIT
    cache = eng.stats()["cache"]
    assert set(cache["groups"]) == {"window", "full"}
    assert cache["groups"]["window"]["layers"] == 4
    assert cache["groups"]["window"]["pages_per_seq"] == WINDOW // PAGE + 1
    assert cache["pool_bytes"] == eng.pool.nbytes == sum(
        g["pages"] * PAGE * g["layers"] * 2 * 2 * 16 * 4
        for g in cache["groups"].values())
    assert len(eng.pool.arrays) == 4               # k and v a group, once


def test_same_seed_replay_is_bitwise(model):
    a = [h.tokens for h in serve(model, sampling="top_k", top_k=3)[2]]
    b = [h.tokens for h in serve(model, sampling="top_k", top_k=3)[2]]
    assert a == b


def test_the_loop_that_runs_itself_serves_the_same_streams(model):
    want = [h.tokens for h in serve(model)[2]]
    seen = []
    eng = ServingEngine(model, **ENGINE)
    eng.on_program = lambda kind, info: seen.append((kind, info))
    eng.start()
    try:
        handles = [eng.submit(p, 12) for p in prompts((5, 16, 23, 40))]
        assert all(h.wait(120) for h in handles)
    finally:
        eng.stop()
    assert [h.tokens for h in handles] == want
    assert eng.stats()["lookahead"]["steps"]["ahead"] > 0
    steps = [info for kind, info in seen if kind == "decode"]
    assert [k for k, _ in seen].count("prefill") == 4 and steps
    assert all(info["routing"]["held"] > 0 for _, info in seen)
    assert all(sum(s["contexts"]) == s["context_tokens"]
               and len(s["contexts"]) == s["rows"] for s in steps)


def test_the_models_that_were_there_state_one_group():
    from hetu_tpu.models import GPT, GPTConfig
    gpt = GPT(GPTConfig(vocab_size=64, hidden_size=16, num_layers=2,
                        num_heads=2, max_seq_len=32))
    spec = gpt.cache_spec()
    assert spec.groups == (spec,) and spec.plain_kv and spec.name == "all"
    eng = ServingEngine(gpt, num_slots=2, page_size=4, max_seq_len=32,
                        prompt_buckets=(8,))
    assert isinstance(eng.pool, kv_cache.KVCachePool)
    assert list(eng.pool.by_group()) == ["all"]
    h = eng.submit([1, 2, 3], 4)
    eng.run_until_idle()
    assert h.status == "completed" and eng.pool.k.ndim == 5


