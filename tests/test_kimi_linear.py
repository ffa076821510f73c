"""Kimi-Linear's layers and model against the plain float32 reference
(``benchmark/reference/kimi_linear.py``) at a tiny size on the CPU: the
chunked KDA form against the token-by-token recurrence, the latent
attention layer and the expert layer that is told its share.  Three steps
of ``Trainer.step`` by the numbers that decide a cell's ``correct`` are in
``test_kimi_linear_training.py``, a file of its own so that the two run on
two workers."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.adapters import kimi_linear as adapter
from benchmark.reference import common as C
from benchmark.reference import kimi_linear as ref

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "benchmark_harness", "data_kimi", "benchmark")
CFG = json.load(open(os.path.join(DATA, "configs", "tiny-kimi.json")))
MM = functools.partial(C.mm, precision="float32")
EIN = functools.partial(C.einsum, precision="float32")


def rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


@pytest.fixture(autouse=True)
def _exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


# -- (a) the chunked form against the recurrence ---------------------------

def _kda_inputs(seq, decay, seed=0, b=2, h=2, d=16):
    ks = jax.random.split(jax.random.key(seed), 6)
    q = ref.l2_norm(jax.random.normal(ks[0], (b, seq, h, d)))
    k = ref.l2_norm(jax.random.normal(ks[1], (b, seq, h, d)))
    v = jax.random.normal(ks[2], (b, seq, h, d))
    g = -decay * jnp.exp(jax.random.uniform(ks[3], (b, seq, h, d),
                                            minval=-6.0, maxval=1.0))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, seq, h)))
    return (q, k, v, g, beta), jax.random.normal(ks[5], (b, seq, h, d))


# a multiple of the chunk, one that is not, one shorter than a chunk, and a
# decay of up to e^-80 a token, which a factorised form could not hold
@pytest.mark.parametrize("seq,decay", [(128, 1.0), (100, 1.0), (40, 1.0),
                                       (64, 30.0)])
def test_chunked_kda_is_the_recurrence(seq, decay):
    from hetu_tpu.ops.pallas import chunk_kda
    args, do = _kda_inputs(seq, decay)
    t = lambda a: jnp.swapaxes(a, 1, 2)

    def program(*a):
        return t(chunk_kda(*(t(x) for x in a), chunk=64))

    def reference(*a):
        return ref.kda_recurrence(*a, 16 ** -0.5, EIN)

    assert rel(program(*args), reference(*args)) < 1e-4
    grads = [jax.grad(lambda *a, f=f: jnp.sum(f(*a) * do),
                      argnums=(0, 1, 2, 3, 4))(*args)
             for f in (program, reference)]
    for name, got, want in zip("q k v g beta".split(), *grads):
        assert rel(got, want) < 1e-4, name


# -- flash attention at two widths ------------------------------------------

# one block pair (the one-kernel backward), several kv blocks (the fused
# backward), and more than _MAX_DQ_PARTIALS of them (the two-kernel
# backward, the path 8,192 tokens take)
@pytest.mark.parametrize("seq,block", [(128, 128), (256, 128), (1280, 128)])
def test_flash_reads_the_value_width_from_v(seq, block):
    from hetu_tpu.layers.attention import dot_product_attention
    from hetu_tpu.ops.pallas import flash_attention_bhsd
    ks = jax.random.split(jax.random.key(seq), 4)
    q = jax.random.normal(ks[0], (1, 2, seq, 24))
    k = jax.random.normal(ks[1], (1, 2, seq, 24))
    v = jax.random.normal(ks[2], (1, 2, seq, 16))
    do = jax.random.normal(ks[3], (1, 2, seq, 16))
    t = lambda a: jnp.swapaxes(a, 1, 2)

    def flash(q, k, v):
        return flash_attention_bhsd(q, k, v, causal=True, block_q=block,
                                    block_k=block)

    def plain(q, k, v):
        return t(dot_product_attention(t(q), t(k), t(v), causal=True))

    assert flash(q, k, v).shape == (1, 2, seq, 16)
    assert rel(flash(q, k, v), plain(q, k, v)) < 1e-5
    grads = [jax.grad(lambda *a, f=f: jnp.sum(f(*a) * do),
                      argnums=(0, 1, 2))(q, k, v) for f in (flash, plain)]
    for name, got, want in zip("qkv", *grads):
        assert rel(got, want) < 1e-4, name


# -- (b), (c), (d): single layers from the reference's weights ---------------

@pytest.fixture(scope="module")
def weights():
    return ref.init_weights(CFG, 7)


@pytest.fixture(scope="module")
def model(weights):
    return adapter.fill(jax.eval_shape(
        lambda: adapter.KimiLinear(adapter._program_config(CFG))), weights)


def _hidden(seed=1, rows=2, seq=128):
    return jax.random.normal(jax.random.key(seed),
                             (rows, seq, CFG["hidden_size"]))


@pytest.mark.parametrize("flash", [True, False])
def test_mla_layer_is_the_reference(model, weights, flash):
    x = _hidden()
    mla = model.blocks[1].mix
    if not flash:
        mla = mla.replace(attn_fn=None)
    m = ref.dims(CFG)
    want = lambda x: ref.mla_layer(x, weights, "blocks.1.mix.", m,
                                   CFG["rms_norm_eps"], MM, EIN)
    assert rel(mla(x), want(x)) < 1e-5
    assert rel(jax.grad(lambda x: jnp.sum(jnp.sin(mla(x))))(x),
               jax.grad(lambda x: jnp.sum(jnp.sin(want(x))))(x)) < 1e-4


def test_kda_layer_is_the_reference(model, weights):
    x = _hidden(seq=100)
    want = ref.kda_layer(x, weights, "blocks.0.mix.", ref.dims(CFG),
                         CFG["rms_norm_eps"], MM, EIN)
    assert rel(model.blocks[0].mix(x), want) < 1e-4


def _share(moe, held, experts):
    """The program's layer holding ``held``, with those experts' weights."""
    return moe.replace(held=tuple(held), experts=moe.experts.replace(
        **experts))


def _all_experts(seed=3):
    """Weights of all 16 experts, as four shares of four."""
    d, f = CFG["hidden_size"], CFG["moe_intermediate_size"]
    ks = jax.random.split(jax.random.key(seed), 3)
    return {"w_gate": 0.1 * jax.random.normal(ks[0], (16, d, f)),
            "w_up": 0.1 * jax.random.normal(ks[1], (16, d, f)),
            "w_down": 0.1 * jax.random.normal(ks[2], (16, f, d))}


def test_the_shares_add_up_to_the_uncut_layer(model, weights):
    x = _hidden(seed=4)
    every = _all_experts()
    moe = model.blocks[1].ffn
    flat = x.reshape(-1, x.shape[-1])
    total = moe.shared(flat)              # the shared expert counted once
    held_pairs = 0
    for r in range(4):
        held = range(4 * r, 4 * r + 4)
        part, stats = _share(moe, held, {
            n: w[4 * r:4 * r + 4] for n, w in every.items()}).routed(flat)
        total = total + part
        held_pairs += int(stats["held"])
        assert int(stats["assignments"]) == x.shape[0] * x.shape[1] * 4
    # every (token, choice) pair fell on exactly one share
    assert held_pairs == x.shape[0] * x.shape[1] * 4
    want = ref.moe_layer(x, weights, "blocks.1.ffn.", ref.dims(CFG), CFG,
                         MM, held=list(range(16)), experts=every)
    assert rel(total.reshape(x.shape), want) < 1e-5


def test_no_token_is_dropped_when_one_expert_takes_half(model, weights):
    """Routing skewed so that held expert 5 is among the chosen of every
    token and takes far more rows than the others: the layer's part still
    equals the masked dense product, to the last token."""
    x = _hidden(seed=5)
    moe = model.blocks[1].ffn
    w = dict(weights)
    skew = jnp.zeros((16,)).at[5].set(10.0)
    moe = moe.replace(router=moe.router.replace(bias=skew))
    w["blocks.1.ffn.router.bias"] = skew
    got, stats = moe(x)
    want = ref.moe_layer(x, w, "blocks.1.ffn.", ref.dims(CFG), CFG, MM)
    assert rel(got, want) < 1e-5
    tokens = x.shape[0] * x.shape[1]
    chosen, _ = ref.route(x, w["blocks.1.ffn.router.w"], skew, ref.dims(CFG),
                          1.0, MM)
    on_held = int(jnp.sum((chosen >= 4) & (chosen < 8)))
    assert int(jnp.sum(chosen == 5)) == tokens
    assert int(stats["held"]) == on_held >= tokens
    assert float(stats["load_max_over_mean"]) > 1.5
    # and the gradient reaches every token
    dx = jax.grad(lambda x: jnp.sum(jnp.sin(moe(x)[0])))(x)
    dw = jax.grad(lambda x: jnp.sum(jnp.sin(ref.moe_layer(
        x, w, "blocks.1.ffn.", ref.dims(CFG), CFG, MM))))(x)
    assert rel(dx, dw) < 1e-4


def test_the_expert_layer_refuses_a_share_it_cannot_hold():
    from hetu_tpu.layers import HeldExpertsMoE
    with pytest.raises(ValueError):
        HeldExpertsMoE(8, 8, 16, (3, 3), top_k=2)
    with pytest.raises(ValueError):
        HeldExpertsMoE(8, 8, 16, (16,), top_k=2)


def test_decay_spares_vectors_and_not_matrices():
    from hetu_tpu.optim import AdamWOptimizer
    p = {"m": jnp.ones((2, 2)), "v": jnp.ones((2,))}
    g = jax.tree_util.tree_map(jnp.zeros_like, p)
    for min_ndim, want_v in ((0, 0.9), (2, 1.0)):
        opt = AdamWOptimizer(1.0, weight_decay=0.1, decay_min_ndim=min_ndim)
        new, _ = opt.update(g, opt.init(p), p)
        assert np.allclose(new["m"], 0.9) and np.allclose(new["v"], want_v)


def test_the_counts_add_up_to_the_published_shape():
    """The model's count at the benchmark's configuration: 770 MFLOP a
    token forward at 8,192, of it KDA 43%, MLA 18%."""
    from benchmark import counts_kimi_linear as counts
    big = json.load(open(os.path.join(
        os.path.dirname(DATA), "..", "..", "..", "benchmark", "configs",
        "kimi-linear-48b-a3b.json")))
    parts = counts.forward_flops_per_token(big, 8192)
    total = sum(parts.values())
    assert 765e6 < total < 775e6
    assert 0.42 < parts["kda"] / total < 0.44
    assert 0.17 < parts["mla"] / total < 0.19
    params = sum(int(np.prod(s)) for s, _ in ref.shapes(big).values())
    assert 600e6 < params < 604e6
