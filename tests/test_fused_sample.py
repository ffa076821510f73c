"""Fused LM-head sampling (ops/pallas/lm_head.py lm_head_sample_pallas):
bitwise parity with the seeded samplers in ops/random.py on the same
logits, determinism/diversity properties under the engine's per-(request,
position) key derivation, and mode edge cases (T<=0 collapse, top-k
clamping, vocab padding).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_tpu.ops.pallas.lm_head import lm_head_sample_pallas
from hetu_tpu.ops.random import (greedy_sample, temperature_sample,
                                 top_k_sample)

pytestmark = pytest.mark.pallas


def _setup(N=6, E=16, V=300, seed=0):
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.standard_normal((N, E)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((E, V)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((V,)), jnp.float32)
    return h, w, b, h @ w + b


# the vocabulary on either axis of the weight, and a vocabulary that is a
# multiple of the block (256 = 2 x 128) or leaves a last tile of 44 columns
# (300) or of one (257): beyond the edge the interpreter hands the kernel
# NaNs, so a mask that missed a column would show
layouts = pytest.mark.parametrize("vocab_axis", [1, 0])
vocabs = pytest.mark.parametrize("V", [300, 257, 256])


def _stored(w, vocab_axis):
    """The ``(E, V)`` projection as a model would hold it with its
    vocabulary on ``vocab_axis``, and the keywords that say so."""
    return (w if vocab_axis else jnp.asarray(w.T)), dict(
        vocab_axis=vocab_axis, block_v=128, interpret=True)


def _keys(N, seed=7):
    base = jax.random.PRNGKey(seed)
    return jax.vmap(lambda i: jax.random.fold_in(base, i))(jnp.arange(N))


@layouts
@vocabs
def test_greedy_matches_argmax(V, vocab_axis):
    h, w, b, logits = _setup(V=V)
    w, kw = _stored(w, vocab_axis)
    out = lm_head_sample_pallas(h, w, bias=b, mode="greedy", **kw)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(greedy_sample(logits)))
    assert out.dtype == jnp.int32


@layouts
@vocabs
def test_temperature_matches_seeded_sampler_bitwise(V, vocab_axis):
    """Property (the engine's reproducibility contract): the fused draw
    reuses the categorical's own gumbel field, so it equals
    ``temperature_sample(logits, T, key)`` bit for bit per row."""
    h, w, b, logits = _setup(V=V)
    w, kw = _stored(w, vocab_axis)
    keys = _keys(h.shape[0])
    for T in (0.7, 1.0, 2.5):
        out = lm_head_sample_pallas(h, w, bias=b, mode="temperature",
                                    temperature=T, keys=keys, **kw)
        ref = jax.vmap(
            lambda lg, kk: temperature_sample(lg, T, key=kk))(logits, keys)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@layouts
@vocabs
def test_top_k_matches_seeded_sampler_bitwise(V, vocab_axis):
    h, w, b, logits = _setup(V=V)
    w, kw = _stored(w, vocab_axis)
    keys = _keys(h.shape[0])
    for k, T in ((1, 1.0), (5, 1.3), (17, 0.6)):
        out = lm_head_sample_pallas(h, w, bias=b, mode="top_k", top_k=k,
                                    temperature=T, keys=keys, **kw)
        ref = jax.vmap(
            lambda lg, kk: top_k_sample(lg, k, T, key=kk))(logits, keys)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@vocabs
@pytest.mark.parametrize("mode", ["greedy", "temperature", "top_k"])
def test_both_layouts_of_one_table_give_the_same_tokens(mode, V):
    """One table read as ``(E, V)`` and as ``(V, E)``: the same products in
    the same precision (bfloat16 operands, as the engine's), so the same
    tokens, with no bias given."""
    h, w, _, _ = _setup(V=V)
    h, w = h.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    keys = _keys(h.shape[0])
    out = []
    for vocab_axis in (1, 0):
        stored, kw = _stored(w, vocab_axis)
        out.append(lm_head_sample_pallas(h, stored, mode=mode, top_k=5,
                                         temperature=0.9, keys=keys, **kw))
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(out[1]))
    assert ((np.asarray(out[0]) >= 0) & (np.asarray(out[0]) < V)).all()


def test_determinism_and_key_sensitivity():
    """Same keys -> bitwise same tokens; across 8 seeds the draws must
    not collapse to one stream (the determinism is key-derived, not an
    accident of the kernel ignoring the noise)."""
    h, w, b, _ = _setup(N=4, V=33)
    draws = {}
    for seed in range(8):
        keys = _keys(4, seed)
        a = lm_head_sample_pallas(h, w, bias=b, mode="temperature",
                                  temperature=2.0, keys=keys,
                                  interpret=True)
        bb = lm_head_sample_pallas(h, w, bias=b, mode="temperature",
                                   temperature=2.0, keys=keys,
                                   interpret=True)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(bb))
        draws[seed] = tuple(np.asarray(a))
    assert len(set(draws.values())) > 1


def test_zero_temperature_collapses_to_greedy():
    h, w, b, logits = _setup(N=3)
    keys = _keys(3)
    for mode in ("temperature", "top_k"):
        out = lm_head_sample_pallas(h, w, bias=b, mode=mode, top_k=4,
                                    temperature=0.0, keys=keys,
                                    interpret=True)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(greedy_sample(logits)))


def test_top_k_clamps_to_vocab_and_small_vocab_padding():
    """k >= vocab degrades to full-distribution temperature sampling
    (top_k_sample's own clamp), across a vocab that needs lane padding."""
    h, w, b, logits = _setup(N=4, V=9)
    keys = _keys(4)
    out = lm_head_sample_pallas(h, w, bias=b, mode="top_k", top_k=9,
                                temperature=1.0, keys=keys, interpret=True)
    ref = jax.vmap(
        lambda lg, kk: top_k_sample(lg, 999, 1.0, key=kk))(logits, keys)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert ((np.asarray(out) >= 0) & (np.asarray(out) < 9)).all()


def test_validation():
    h, w, b, _ = _setup(N=2)
    with pytest.raises(ValueError, match="sampling mode"):
        lm_head_sample_pallas(h, w, mode="nucleus", interpret=True)
    with pytest.raises(ValueError, match="keys"):
        lm_head_sample_pallas(h, w, mode="temperature", interpret=True)
    with pytest.raises(ValueError, match="top_k"):
        lm_head_sample_pallas(h, w, mode="top_k", top_k=300,
                              keys=_keys(2), interpret=True)
    with pytest.raises(ValueError, match="vocab_axis"):
        lm_head_sample_pallas(h, w, vocab_axis=2, interpret=True)
