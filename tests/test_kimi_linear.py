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


# -- the within-chunk stage: the kernels against the plain float32 form ------

# What ``hetu_tpu/ops/pallas/kda.py`` ran as XLA operations before the stage
# became kernels (``ops/pallas/kda_chunk.py``), kept here as the plain form
# they are held to: the pair-by-pair sub-block diagonal, row substitution
# inside 16 x 16 blocks, merges two by two, JAX's own differentiation.

_HI = jax.lax.Precision.HIGHEST
_SUB = 16


def _diagonal_grams(x, k, G):
    """Pair by pair inside each sub-block.  x: [..., X, n, sub, d];
    k, G: [..., n, sub, d].  Returns [..., X, n, sub(t), sub(s)] with
    ``sum_d x_t k_s exp(G_t - G_s)`` for s <= t and 0 above: one reduction
    over d of the [sub, sub, d] products, which XLA does not materialise
    for the forward; the exponent is clamped at 0 above the diagonal, where
    it is masked anyway."""
    sub = k.shape[-2]
    decay = jnp.exp(jnp.minimum(G[..., :, None, :] - G[..., None, :, :],
                                0.0))
    grams = jnp.sum(x[..., :, None, :] * (k[..., None, :, :] * decay
                                          )[..., None, :, :, :, :], axis=-1)
    return jnp.where(jnp.tril(jnp.ones((sub, sub), bool)), grams, 0.0)


def _decayed_grams(x, k, G):
    """``M[x]_ts = sum_d x_t[d] k_s[d] exp(G_t[d] - G_s[d])`` for s <= t, 0
    above the diagonal.  x: [..., X, C, d] (X operands share k and G);
    k, G: [..., C, d].  Returns [..., X, C, C]."""
    C, d = k.shape[-2:]
    n = C // _SUB
    lead = k.shape[:-2]
    xs = x.reshape(x.shape[:-2] + (n, _SUB, d))
    ks, Gs = (a.reshape(lead + (n, _SUB, d)) for a in (k, G))
    diag = _diagonal_grams(xs, ks, Gs)
    rows = []
    for i in range(n):
        parts = []
        if i:
            # both factors are normalised at the cumulative log just before
            # row block i, so both exponents are <= 0
            ref = Gs[..., i - 1, _SUB - 1, :]
            xr = xs[..., i, :, :] * jnp.exp(
                Gs[..., i, :, :] - ref[..., None, :])[..., None, :, :]
            kc = (ks[..., :i, :, :] * jnp.exp(
                ref[..., None, None, :] - Gs[..., :i, :, :])
                  ).reshape(lead + (i * _SUB, d))
            parts.append(jnp.einsum("...xtd,...sd->...xts", xr, kc,
                                    precision=_HI))
        parts.append(diag[..., i, :, :])
        if i < n - 1:
            parts.append(jnp.zeros(diag.shape[:-3]
                                   + (_SUB, (n - 1 - i) * _SUB), diag.dtype))
        rows.append(jnp.concatenate(parts, axis=-1))
    return jnp.concatenate(rows, axis=-2)


def _inv_unit_lower(L):
    """Inverse of a batch of unit lower triangular matrices [..., n, n]:
    forward substitution in blocks of ``_SUB`` rows, merged two by two:
    ``[[A, 0], [B, D]]^-1 = [[A^-1, 0], [-D^-1 B A^-1, D^-1]]``."""
    n = L.shape[-1]
    if n <= _SUB:
        eye = jnp.eye(n, dtype=L.dtype)
        rows = []
        for r in range(n):
            row = jnp.broadcast_to(eye[r], L.shape[:-2] + (n,))
            if r:
                row = row - jnp.einsum("...c,...cn->...n", L[..., r, :r],
                                       jnp.stack(rows, axis=-2),
                                       precision=_HI)
            rows.append(row)
        return jnp.stack(rows, axis=-2)
    h = n // 2
    a = _inv_unit_lower(L[..., :h, :h])
    d = _inv_unit_lower(L[..., h:, h:])
    low = -jnp.einsum("...ij,...jk,...kl->...il", d, L[..., h:, :h], a,
                      precision=_HI)
    top = jnp.concatenate([a, jnp.zeros_like(low.swapaxes(-1, -2))], axis=-1)
    return jnp.concatenate([top, jnp.concatenate([low, d], axis=-1)],
                           axis=-2)


def _within_chunks(q, k, v, g, beta, scale, out_dtype):
    """q, k, g: [B, H, N, C, d_k]; v: [B, H, N, C, d_v]; beta: [B, H, N, C];
    all float32.  Returns what the scan takes: (qg, kd, wk, wv, p, gamma)."""
    G = jnp.cumsum(g, axis=-2)
    last = G[..., -1:, :]
    decay = jnp.exp(G)
    C = k.shape[-2]
    grams = _decayed_grams(jnp.stack([k, q * scale], axis=-3), k, G)
    strict = jnp.tril(jnp.ones((C, C), bool), -1)
    A = jnp.where(strict, grams[..., 0, :, :] * beta[..., None], 0.0)
    T = _inv_unit_lower(A + jnp.eye(C, dtype=A.dtype))
    bk = beta[..., None] * k * decay
    bv = beta[..., None] * v
    wk = jnp.einsum("...ts,...sd->...td", T, bk, precision=_HI)
    wv = jnp.einsum("...ts,...sd->...td", T, bv, precision=_HI)
    cast = lambda a: a.astype(out_dtype)
    return (cast(q * scale * decay), cast(k * jnp.exp(last - G)), cast(wk),
            cast(wv), cast(grams[..., 1, :, :]), jnp.exp(last))


def _stage_inputs(heads, chunks, chunk, width, decay, dtype, seed=0):
    """Inputs in the kernels' layout [heads, chunks, chunk, ...] and random
    cotangents of the six outputs."""
    (q, k, v, g, beta), _ = _kda_inputs(chunks * chunk, decay, seed=seed,
                                        b=heads, h=1, d=width)
    lay = lambda a: a.reshape((heads, chunks, chunk) + a.shape[3:])
    args = tuple(lay(a).astype(dtype) for a in (q, k, v)) + (
        lay(g), lay(beta))
    ks = jax.random.split(jax.random.key(seed + 1), 6)
    shapes = [args[0].shape] * 4 + [(heads, chunks, chunk, chunk),
                                    (heads, chunks, 1, width)]
    cots = tuple(jax.random.normal(kk, sh).astype(dtype)
                 for kk, sh in zip(ks, shapes))
    return args, cots[:5] + (cots[5].astype(jnp.float32),)


def _stage_pair(chunk, width, dtype):
    """The kernels and the plain form as functions of the same arguments
    with outputs of the same shapes."""
    from hetu_tpu.ops.pallas.kda_chunk import within_chunks
    scale = width ** -0.5

    def kernels(q, k, v, g, beta):
        return within_chunks(q, k, v, g, beta[:, :, None, :], scale, dtype,
                             True)

    def plain(q, k, v, g, beta):
        f32 = lambda a: a.astype(jnp.float32)
        out = _within_chunks(f32(q), f32(k), f32(v), g, beta, scale, dtype)
        return out[:5] + (out[5].reshape(out[5].shape[:2] + (1, width)),)

    return kernels, plain


# the chip's shape in both types, the tests' width, and a strong decay
STAGE_CASES = [(64, 128, jnp.bfloat16, 1.0), (64, 128, jnp.float32, 1.0),
               (64, 16, jnp.float32, 1.0), (32, 16, jnp.float32, 30.0)]
STAGE_IDS = ["64-128-bf16", "64-128-f32", "64-16-f32", "32-16-f32-strong"]


@pytest.mark.parametrize("chunk,width,dtype,decay", STAGE_CASES,
                         ids=STAGE_IDS)
def test_the_stage_kernel_is_the_plain_form(chunk, width, dtype, decay):
    args, _ = _stage_inputs(2, 3, chunk, width, decay, dtype)
    kernels, plain = _stage_pair(chunk, width, dtype)
    tol = 1e-2 if dtype == jnp.bfloat16 else 1e-4
    names = "qg kd wk wv p gamma".split()
    for name, got, want in zip(names, kernels(*args), plain(*args)):
        assert got.dtype == want.dtype and got.shape == want.shape, name
        assert rel(got.astype(jnp.float32), want.astype(jnp.float32)) < tol, \
            name


@pytest.mark.parametrize("chunk,width,dtype,decay", STAGE_CASES,
                         ids=STAGE_IDS)
def test_the_stage_backward_kernel_is_the_plain_forms_vjp(chunk, width,
                                                          dtype, decay):
    args, cots = _stage_inputs(2, 3, chunk, width, decay, dtype, seed=2)
    kernels, plain = _stage_pair(chunk, width, dtype)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    grads = [jax.vjp(f, *args)[1](cots) for f in (kernels, plain)]
    for name, got, want in zip("q k v g beta".split(), *grads):
        assert got.dtype == want.dtype and got.shape == want.shape, name
        assert rel(got.astype(jnp.float32), want.astype(jnp.float32)) < tol, \
            name


# the strong decay beyond one chunk: a multiple of the chunk and one that is
# not, values and all five gradients
@pytest.mark.parametrize("seq", [256, 200])
def test_chunked_kda_holds_a_strong_decay_over_chunks(seq):
    test_chunked_kda_is_the_recurrence(seq, 30.0)


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it, a kernel's
    own body left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def test_every_exponential_of_chunked_kda_lives_in_a_kernel():
    from hetu_tpu.ops.pallas import chunk_kda
    args, do = _kda_inputs(128, 1.0)
    t = lambda a: jnp.swapaxes(a, 1, 2)
    loss = lambda *a: jnp.sum(chunk_kda(*(t(x) for x in a), chunk=64) * t(do))
    eqns = list(_equations(jax.make_jaxpr(jax.grad(
        loss, argnums=(0, 1, 2, 3, 4)))(*args).jaxpr))
    calls = [e.params["name"] for e in eqns
             if e.primitive.name == "pallas_call"]
    assert sorted(set(calls)) == ["kda_chunk_bwd", "kda_chunk_fwd",
                                  "kda_scan_bwd", "kda_scan_fwd"]
    assert all(name.startswith("kda_") for name in calls)
    assert not [e for e in eqns if e.primitive.name in ("exp", "exp2")]


# Mosaic's lowering of the two kernels at the cell's shape, for the v5e and
# without a device (libtpu's AOT topology): what the interpreter cannot show
# (a store Mosaic refuses, a step that does not fit VMEM).  Its own process,
# once for both: a process that has loaded libtpu compiles for the CPU
# several times slower afterwards (tests/test_mem.py).
_V5E_STAGE_KERNELS = """
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
from hetu_tpu.ops.pallas import kda_chunk
v5e = SingleDeviceSharding(topologies.get_topology_desc(
    platform="tpu", topology_name="v5e:2x2").devices[0])
BH, N, C, d = 64, 128, 64, 128
bf, f32 = jnp.bfloat16, jnp.float32
S = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
ins = [S((BH, N, C, d), bf)] * 3 + [S((BH, N, C, d), f32),
                                    S((BH, N, 1, C), f32)]
cots = [S((BH, N, C, d), bf)] * 4 + [S((BH, N, C, C), bf),
                                     S((BH, N, 1, d), f32)]
fwd = lambda *a: kda_chunk._call_fwd(*a, d ** -0.5, bf, False, save=True)
bwd = lambda *a: kda_chunk._call_bwd(*a[:6], a[6:], d ** -0.5, False)
for name, f, args in (("fwd", fwd, ins),
                      ("bwd", bwd, ins + [S((BH, N, C, 2 * C), f32)] + cots)):
    text = jax.jit(f).lower(*args).compile().as_text()
    print("COMPILED", name, "kda_chunk_" + name in text)
"""


@pytest.fixture(scope="module")
def v5e_stage_kernels():
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    run = subprocess.run(
        [sys.executable, "-c", _V5E_STAGE_KERNELS], cwd=root,
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled",
                 PYTHONPATH=root))
    assert run.returncode == 0, run.stderr[-3000:]
    return dict(ln.split()[1:] for ln in run.stdout.splitlines()
                if ln.startswith("COMPILED"))


@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
def test_the_stage_kernels_compile_for_the_v5e(v5e_stage_kernels, kernel):
    assert v5e_stage_kernels[kernel] == "True"


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
