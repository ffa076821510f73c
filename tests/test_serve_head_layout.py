"""The decode program reads the vocabulary projection where the model keeps
it: ``head()`` hands the engine the array as stored and its vocabulary axis,
the fused sampler streams it from there, and nothing of the head's size is
transposed or padded inside a decode step.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_tpu.core import set_random_seed
from hetu_tpu.models.gpt import GPT, GPTConfig
from hetu_tpu.serve import ServingEngine

pytestmark = pytest.mark.serve

# a vocabulary that is no multiple of any block (97 is prime), so that the
# old path would have padded it, and tied or not
ENGINE = dict(num_slots=3, page_size=8, max_seq_len=64, prompt_buckets=(8, 16),
              seed=7)
PROMPTS = ([5, 6, 7], [9, 9], [3, 4, 5, 6, 7], [11, 12, 13, 14], [40, 41])
BUDGETS = (9, 5, 12, 7, 10)


def tiny_gpt(tied: bool) -> GPT:
    set_random_seed(0)
    return GPT(GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                         num_heads=2, max_seq_len=64, tie_embeddings=tied))


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold (a
    jitted call's, a kernel's body)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


def decode_jaxpr(eng):
    """The engine's decode step as the loop calls it, traced."""
    rows = ENGINE["num_slots"]

    def i32(*shape):
        return jnp.zeros(shape, jnp.int32)

    return jax.make_jaxpr(eng._paged_decode_impl)(
        eng.model, *eng.pool.arrays, eng.pool.table_shapes(rows), i32(rows),
        i32(rows, 1), i32(rows), i32(rows), i32(rows))


@pytest.mark.parametrize("sampling", ["greedy", "temperature", "top_k"])
@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_decode_program_neither_transposes_nor_pads_the_head(tied, sampling):
    model = tiny_gpt(tied)
    weight, vocab_axis = model.head()
    assert vocab_axis == (0 if tied else 1)
    assert weight.shape[vocab_axis] == 97
    # as stored: the very array of the model, not a view of it
    assert weight is (model.wte.weight if tied else model.lm_head)
    eng = ServingEngine(model, sampling=sampling, top_k=4, **ENGINE)
    assert eng._fused_sampling
    eqns = list(_equations(decode_jaxpr(eng).jaxpr))
    assert any(e.primitive.name == "pallas_call"
               and e.params["name"] == "lm_head_sample" for e in eqns)
    relaid = [(e.primitive.name, v.aval.shape) for e in eqns
              if e.primitive.name in ("pad", "transpose")
              for v in e.invars[:1]
              if int(np.prod(v.aval.shape)) >= weight.size]
    assert relaid == []


@pytest.mark.parametrize("sampling", ["greedy", "top_k"])
@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_fused_and_unfused_sampling_serve_the_same_streams(tied, sampling):
    """The fused sampler over the stored table and ``last @ head`` through
    the row samplers read the same logits: the same tokens, request by
    request."""
    model = tiny_gpt(tied)

    def serve(fused):
        eng = ServingEngine(model, sampling=sampling, top_k=4,
                            temperature=1.3, fused_sampling=fused, **ENGINE)
        assert eng._fused_sampling is fused
        hs = [eng.submit(p, n) for p, n in zip(PROMPTS, BUDGETS)]
        eng.run_until_idle()
        assert all(h.status == "completed" for h in hs)
        return [tuple(h.tokens) for h in hs]

    fused = serve(True)
    assert fused == serve(False)
    assert [len(t) for t in fused] == list(BUDGETS)
