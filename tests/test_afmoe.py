"""AFMoE (Trinity) on the serving path at a tiny size on the CPU: (a) prefill
then decode through the two groups of layers against the reference's full
forward; (d) the router and the shares of the expert layer; (g) the faults
that must read not correct.  The kernels with grouped heads and a window
are in ``test_grouped_kernels.py``, the streams through ``ServingEngine`` in
``test_afmoe_engine.py``, the allocator and the fleet's features over a
grouped pool in ``test_grouped_cache.py`` (three files, so that the suite's
workers share them)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.adapters import afmoe as adapter
from benchmark.reference import afmoe as ref
from hetu_tpu.layers import SigmoidRouter
from hetu_tpu.layers.attention import rotate_halves
from hetu_tpu.layers.moe import HeldExpertsMoE
from hetu_tpu.serve import kv_cache

pytestmark = pytest.mark.pallas

S, F = "sliding_attention", "full_attention"
TINY = {
    "family": "afmoe", "dtype": "float32", "hidden_size": 64,
    "num_hidden_layers": 5, "layer_types": [S, S, F, S, S],
    "num_dense_layers": 1, "intermediate_size": 96,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "sliding_window": 8, "rope_theta": 10000, "num_experts": 16,
    "num_experts_published": 16, "held_experts": list(range(16)),
    "num_experts_per_tok": 4, "moe_intermediate_size": 32,
    "num_shared_experts": 1, "route_scale": 2.826, "mup_enabled": True,
    "rms_norm_eps": 1e-5, "max_position_embeddings": 512,
    "vocab_size": 256, "initializer_range": 0.2}
SEED = 11
PAGE, WINDOW = 4, 8
ENGINE = dict(num_slots=3, page_size=PAGE, max_seq_len=64,
              prompt_buckets=(8, 16, 48), sampling="greedy")
LIMIT = 5e-4            # the tiny cell's logit_gap_max


@pytest.fixture(scope="module")
def model():
    return adapter.build_model(TINY, SEED)


@pytest.fixture(scope="module")
def weights():
    return ref.to_float32(ref.init_weights(TINY, ref.C.seed_key(SEED)))


def prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TINY["vocab_size"], n) for n in lengths]


# ----------------------------- (a) prefill, then decode, through two groups

@jax.jit
def _prefill(model, cache, tables, tokens, lengths):
    logits, cache, _ = model.prefill(cache, tables, None, tokens, lengths)
    return logits, cache


@jax.jit
def _decode(model, cache, tables, lengths, tokens):
    hidden, cache, _ = model.decode(cache, tables, lengths, tokens)
    return hidden @ model.head()[0], cache


def _poison_what_left_the_window(pool, length):
    """NaN over the positions before the window of a sequence of ``length +
    1`` tokens that its ring's oldest page still holds."""
    win = pool.groups["window"]
    gone = length + 1 - WINDOW                  # positions 0 .. gone - 1
    if gone <= 0 or (gone - 1) // PAGE <= length // PAGE - win.pages_per_seq:
        return                                  # none, or none resident
    page = win.table(0).pages[((gone - 1) // PAGE) % win.pages_per_seq]
    upto = (gone - 1) % PAGE + 1
    win.commit(*(a.at[:, page, :, :upto].set(jnp.nan) for a in win.arrays))


def _served_logits(model, prompt, new, bucket, poison):
    """Logits of the prompt's last position and of ``new`` decoded
    positions, fed the program's own greedy tokens, on a pool of three
    slots whose other pages (with ``poison``, and whatever a ring's oldest
    page still holds of positions that fell out of the window) are NaN."""
    pool = kv_cache.make_pool(model.cache_spec(), num_slots=3,
                              page_size=PAGE, max_seq_len=64)
    pool.commit(*(jnp.full_like(a, jnp.nan if poison else 0.0)
                  for a in pool.arrays))
    n = len(prompt)
    pool.alloc(0, n)
    tokens = np.zeros((1, bucket), np.int32)
    tokens[0, :n] = prompt
    logits, cache = _prefill(model, pool.arrays, pool.gather_indices([0]),
                             jnp.asarray(tokens), jnp.asarray([n], jnp.int32))
    pool.commit(*cache)
    out, seq = [np.asarray(logits[0])], list(prompt)
    for _ in range(new):
        seq.append(int(np.argmax(out[-1])))
        length = len(seq) - 1
        pool.ensure(0, length + 1)
        if poison:
            _poison_what_left_the_window(pool, length)
        logits, cache = _decode(
            model, pool.arrays, pool.gather_indices([0, None, None]),
            jnp.asarray([length, 0, 0], jnp.int32),
            jnp.asarray([[seq[-1]], [0], [0]], jnp.int32))
        pool.commit(*cache)
        out.append(np.asarray(logits[0]))
    assert all(len(g.table(0).pages) <= g.pages_per_seq
               for g in pool.groups.values())
    return seq, np.stack(out)


@pytest.mark.parametrize("n,bucket", [
    (3, 8), (4, 8), (5, 8), (8, 8), (9, 16), (12, 16), (13, 16), (31, 48),
    (40, 48)], ids=lambda v: str(v))
@pytest.mark.parametrize("poison", [False, True], ids=["clean", "nan"])
def test_prefill_then_decode_matches_the_full_forward(model, weights, n,
                                                      bucket, poison):
    """Prompts shorter than the window, equal to it and several times it,
    at and beside page edges; twelve decoded tokens take every one past the
    ring's first turn."""
    prompt = prompts((n,), seed=n)[0]
    seq, got = _served_logits(model, prompt, 12, bucket, poison)
    want = ref.logits_at(weights, jnp.asarray(seq),
                         jnp.arange(n - 1, len(seq)), cfg=TINY)
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-4)


def test_the_whole_sequence_at_once_matches_too(model, weights):
    tok = prompts((40,), seed=3)[0]
    got = model(jnp.asarray(tok)[None])[0]
    want = ref.logits_at(weights, jnp.asarray(tok), jnp.arange(40), cfg=TINY)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


def test_rotary_turns_the_halves():
    x = jnp.asarray(np.random.default_rng(0).standard_normal((5, 3, 8)),
                    jnp.float32)
    got = rotate_halves(x, jnp.arange(5)[:, None], 10000.0)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.rope(x, 10000.0)), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(x[0]))


# ------------------------------------------- (d) the router and the shares

def test_sigmoid_router_against_a_hand_worked_case():
    """Scores 0.9, 0.8, 0.6, 0.5 and four lower: the two largest are
    chosen, normalised over the chosen and scaled: 2.826 x 0.9 / 1.7 and
    2.826 x 0.8 / 1.7."""
    s = np.asarray([0.5, 0.9, 0.1, 0.8, 0.2, 0.6, 0.3, 0.4])
    r = SigmoidRouter(8, 8, 2, scale=2.826)
    r.w = jnp.eye(8, dtype=jnp.float32)
    x = jnp.asarray(np.log(s / (1 - s))[None], jnp.float32)   # the logits
    chosen, weight = r(x)
    assert sorted(np.asarray(chosen[0]).tolist()) == [1, 3]
    by = dict(zip(np.asarray(chosen[0]).tolist(),
                  np.asarray(weight[0]).tolist()))
    assert by[1] == pytest.approx(2.826 * 0.9 / 1.7, rel=1e-5)
    assert by[3] == pytest.approx(2.826 * 0.8 / 1.7, rel=1e-5)
    assert sum(by.values()) == pytest.approx(2.826, rel=1e-5)


def test_four_shares_of_four_experts_add_up_to_the_uncut_layer(weights):
    """Four chips' shares, four experts each, and the shared expert once,
    against the reference's layer with every expert held."""
    p = "blocks.1.ffn."
    x = jnp.asarray(np.random.default_rng(1).standard_normal((10, 64)),
                    jnp.float32)
    want = np.asarray(ref.moe(x, weights, "blocks.1.", TINY, ref.C.mm))

    def layer(held, shared):
        moe = HeldExpertsMoE(64, 32, 16, held, top_k=4, scale=2.826,
                             shared_hidden=32 if shared else 0,
                             router="sigmoid", interpret=True)
        moe.router.w = weights[p + "router.w"]
        for n in ("w_gate", "w_up", "w_down"):
            setattr(moe.experts, n,
                    weights[p + "experts." + n][jnp.asarray(held)])
            if shared:
                setattr(moe.shared, n, weights[p + "shared." + n])
        return moe

    total, pairs = 0.0, 0
    for chip in range(4):
        y, stats = layer(tuple(range(4 * chip, 4 * chip + 4)),
                         chip == 0).infer(x)
        total, pairs = total + y, pairs + int(stats["held"])
    assert pairs == 10 * 4                         # no pair dropped
    np.testing.assert_allclose(np.asarray(total), want, atol=2e-5)
    # and the reference given one chip's share computes that share
    cut = dict(TINY, num_experts=4, held_experts=[0, 1, 2, 3])
    w_cut = {n: (a[:4] if ".experts." in n else a)
             for n, a in weights.items()}
    share, _ = layer((0, 1, 2, 3), True).infer(x)
    np.testing.assert_allclose(
        np.asarray(share), np.asarray(ref.moe(x, w_cut, "blocks.1.", cut,
                                              ref.C.mm)), atol=2e-5)


# ------------------------- what the reference judges: settled choices only

def test_held_margin_against_a_hand_worked_case():
    """Scores 0.9 and 0.8 chosen of eight, 0.6 the best left out.  Held 0
    to 3: expert 3 (0.8, chosen) is 0.2 above the best left out, the
    nearest of them to changing sides.  Held 4 to 7: expert 5 (0.6, left
    out) is 0.2 below the least chosen.  Held 2 and 4: 0.6 and 0.7 below;
    that 5 and 3 are close moves no held expert."""
    cfg = dict(TINY, num_experts_per_tok=2)
    s = jnp.asarray([[0.5, 0.9, 0.1, 0.8, 0.2, 0.6, 0.3, 0.4]], jnp.float32)
    for held, want in (([0, 1, 2, 3], 0.2), ([4, 5, 6, 7], 0.2),
                       ([2, 4], 0.6), ([1], 0.3)):
        got = ref.held_margin(s, dict(cfg, held_experts=held))
        assert float(got[0]) == pytest.approx(want, abs=1e-6)


def test_the_reference_gives_an_unsettled_position_one_logit(weights):
    """With ``judged_router_margin`` the plain float32 reference blanks the
    positions at which an expert layer's margin is no wider, and no other;
    a fault or a lower precision in the program's place is never
    blanked."""
    tok = jnp.asarray(prompts((40,), seed=3)[0])
    pos = jnp.arange(40)
    plain = np.asarray(ref.logits_at(weights, tok, pos, cfg=TINY))
    margins = []
    ref.hidden_states(weights, tok, cfg=TINY, margins=margins)
    assert len(margins) == 4                     # one an expert layer
    least = np.min(np.stack([np.asarray(m) for m in margins]), axis=0)
    at = float(np.sort(least)[10])               # blanks eleven of forty
    cfg = dict(TINY, judged_router_margin=at)
    got = np.asarray(ref.logits_at(weights, tok, pos, cfg=cfg))
    blank = least <= at
    assert blank.sum() == 11
    assert not got[blank].any()
    np.testing.assert_array_equal(got[~blank], plain[~blank])
    for kw in ({"fault": "no_gate"}, {"precision": "bfloat16"}):
        np.testing.assert_array_equal(
            np.asarray(ref.logits_at(weights, tok, pos, cfg=cfg, **kw)),
            np.asarray(ref.logits_at(weights, tok, pos, cfg=TINY, **kw)))


def test_a_blanked_position_reads_no_gap_whatever_was_served(weights):
    from benchmark.runners.serve import served_gaps
    prompt = prompts((24,), seed=5)[0]
    served = np.random.default_rng(5).integers(0, TINY["vocab_size"], 8)
    every = dict(TINY, judged_router_margin=1.0)     # no margin is wider
    (gaps,) = served_gaps(every, SEED, [(prompt, served)], pad_to=48)
    assert not gaps.any()
    (gaps,) = served_gaps(TINY, SEED, [(prompt, served)], pad_to=48)
    assert gaps.max() > 1.0                          # random tokens


# ------------------------------------- (g) what must read not correct

@pytest.mark.parametrize("fault", ["no_window", "rope_on_full", "no_gate"])
def test_the_reference_with_a_mechanism_wrong_reads_not_correct(fault):
    from benchmark.tools.afmoe_faults import fault_gaps
    gaps = fault_gaps(TINY, SEED, prompts((40, 33), seed=9), 8, rank=1,
                      fault=fault)
    assert max(g.max() for g in gaps) > 100 * LIMIT


def test_the_plain_reference_in_its_own_place_reads_nought():
    from benchmark.tools.afmoe_faults import fault_gaps
    same = fault_gaps(TINY, SEED, prompts((40,), seed=9), 8, rank=1)
    assert max(g.max() for g in same) == 0.0
    with pytest.raises(ValueError, match="unknown fault"):
        ref.hidden_states({}, jnp.zeros(3, jnp.int32), cfg=TINY,
                          fault="no_such")


def test_the_reference_one_precision_lower_reads_not_correct():
    from benchmark.reference.common import LOWER
    from benchmark.tools.afmoe_faults import fault_gaps
    gaps = fault_gaps(TINY, SEED, prompts((40, 33), seed=9), 32, rank=3,
                      control=LOWER[TINY["dtype"]])
    assert max(g.max() for g in gaps) > LIMIT
