"""Layer/model shape & behavior tests; BERT/GPT vs reference semantics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu.core import set_random_seed
from hetu_tpu.layers import (
    BatchNorm2d,
    Dropout,
    LayerNorm,
    Linear,
    MultiHeadAttention,
    Sequential,
    TransformerBlock,
)
from hetu_tpu.layers.attention import dot_product_attention
from hetu_tpu.models import (
    GPT,
    BertForPreTraining,
    LeNet,
    MLP,
    bert_base,
    gpt2_small,
    resnet18,
)


def setup_module():
    set_random_seed(0)


def test_linear_sequential():
    m = Sequential(Linear(8, 16), Linear(16, 4))
    y = m(jnp.ones((2, 8)))
    assert y.shape == (2, 4)


def test_attention_causal_masks_future():
    attn = MultiHeadAttention(16, 4, causal=True)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, 6, 16)), jnp.float32)
    y1 = attn(x)
    # perturb the last position: outputs at earlier positions must not change
    x2 = x.at[0, -1].add(10.0)
    y2 = attn(x2)
    np.testing.assert_allclose(y1[0, :-1], y2[0, :-1], atol=1e-5)
    assert not np.allclose(y1[0, -1], y2[0, -1])


def test_attention_oracle():
    """dot_product_attention vs explicit numpy softmax attention."""
    rng = np.random.default_rng(0)
    q = rng.standard_normal((1, 3, 2, 4)).astype(np.float32)
    k = rng.standard_normal((1, 5, 2, 4)).astype(np.float32)
    v = rng.standard_normal((1, 5, 2, 4)).astype(np.float32)
    out = dot_product_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    # numpy oracle
    logits = np.einsum("bqhd,bkhd->bhqk", q, k) / 2.0
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    expect = np.einsum("bhqk,bkhd->bqhd", p, v)
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-4, atol=1e-5)


def test_batchnorm_state_threading():
    bn = BatchNorm2d(3)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((4, 5, 5, 3)), jnp.float32)
    y, bn2 = bn(x, training=True)
    assert not np.allclose(bn2.running_mean, bn.running_mean)
    # eval mode: unchanged state, uses running stats
    y_eval, bn3 = bn2(x, training=False)
    np.testing.assert_array_equal(bn3.running_mean, bn2.running_mean)


# slow tier (r5 re-tier): resnet is bench config 1 + alexnet forward stays fast
@pytest.mark.slow
def test_resnet18_forward_and_state():
    m = resnet18(num_classes=10)
    x = jnp.ones((2, 32, 32, 3))
    logits, m2 = m(x, training=True)
    assert logits.shape == (2, 10)
    assert not np.allclose(m2.stem_bn.running_mean, m.stem_bn.running_mean)
    logits_eval, _ = m2(x, training=False)
    assert logits_eval.shape == (2, 10)


def test_lenet_mlp():
    assert LeNet()(jnp.ones((2, 28, 28, 1))).shape == (2, 10)
    assert MLP((16, 8, 4))(jnp.ones((3, 16))).shape == (3, 4)


# slow tier (r5 re-tier): BERT torch-parity oracle gates this in the slow tier; mlm-mask semantics stay fast
@pytest.mark.slow
def test_bert_tiny_forward_and_loss():
    cfg = bert_base(vocab_size=100, hidden_size=32, num_layers=2, num_heads=2,
                    max_position_embeddings=16)
    model = BertForPreTraining(cfg)
    b, s = 2, 8
    ids = jnp.ones((b, s), jnp.int32)
    mlm_logits, nsp_logits = model(ids)
    assert mlm_logits.shape == (b, s, 100)
    assert nsp_logits.shape == (b, 2)
    labels = jnp.full((b, s), -1, jnp.int32).at[:, 2].set(5)
    loss, aux = model.loss(ids, jnp.zeros_like(ids), jnp.ones((b, s)), labels,
                           jnp.zeros((b,), jnp.int32))
    assert np.isfinite(float(loss))
    # loss ≈ log(vocab) + log(2) at init
    assert 2.0 < float(loss) < 12.0


# slow tier (r5 budget, 1-core box): BERT torch-parity oracle (slow) gates mlm masking; forward/loss canaries stay fast
@pytest.mark.slow
def test_bert_mlm_ignores_unmasked():
    cfg = bert_base(vocab_size=50, hidden_size=16, num_layers=1, num_heads=2,
                    max_position_embeddings=8)
    model = BertForPreTraining(cfg)
    ids = jnp.ones((1, 4), jnp.int32)
    all_ignored = jnp.full((1, 4), -1, jnp.int32)
    loss, aux = model.loss(ids, None, None, all_ignored, jnp.zeros((1,), jnp.int32))
    np.testing.assert_allclose(float(aux["mlm_loss"]), 0.0, atol=1e-6)


def test_gpt_loss_decreases():
    cfg = gpt2_small(vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
                     max_seq_len=16)
    model = GPT(cfg)
    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, (4, 12)), jnp.int32
    )
    from hetu_tpu.optim import AdamOptimizer

    opt = AdamOptimizer(1e-2)
    state = opt.init(model)

    @jax.jit
    def step(model, state):
        loss, g = jax.value_and_grad(lambda m: m.loss(ids))(model)
        model, state = opt.update(g, state, model)
        return model, state, loss

    losses = []
    for _ in range(10):
        model, state, loss = step(model, state)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_bert_downstream_heads():
    import jax
    from hetu_tpu.core import set_random_seed
    from hetu_tpu.models import (BertForMaskedLM,
                                 BertForNextSentencePrediction,
                                 BertForSequenceClassification, bert_base)

    set_random_seed(0)
    cfg = bert_base(num_layers=1, hidden_size=32, num_heads=2, vocab_size=100,
                    max_position_embeddings=16)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, 100, (2, 8)), jnp.int32)
    tt = jnp.zeros((2, 8), jnp.int32)

    mlm = BertForMaskedLM(cfg)
    assert mlm(ids, tt).shape == (2, 8, 100)
    labels = jnp.where(jnp.arange(8)[None] < 2, ids, -1)
    loss, aux = mlm.loss(ids, tt, None, labels)
    assert np.isfinite(float(loss))

    nsp = BertForNextSentencePrediction(cfg)
    assert nsp(ids, tt).shape == (2, 2)

    cls = BertForSequenceClassification(cfg, num_labels=3)
    logits = cls(ids, tt)
    assert logits.shape == (2, 3)
    loss, aux = cls.loss(ids, tt, None, jnp.asarray([0, 2]),
                         key=jax.random.key(0))
    assert np.isfinite(float(loss)) and 0.0 <= float(aux["accuracy"]) <= 1.0


def test_transformer_block_custom_plain_mlp():
    """mlp= override with a plain (x)->y FFN (no training kwarg)."""
    from hetu_tpu.core import set_random_seed
    from hetu_tpu.layers import TransformerBlock
    from hetu_tpu.layers.transformer import TransformerMLP

    set_random_seed(0)
    blk = TransformerBlock(16, 2, mlp=TransformerMLP(16, 48))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 4, 16)),
                    jnp.float32)
    y = blk(x)
    assert y.shape == x.shape and np.isfinite(np.asarray(y)).all()


@pytest.mark.slow
def test_gpt_streamed_head_matches_materialized():
    """streamed_head_chunk: loss and gradients (incl. the tied-embedding
    weight reached through the head transpose) equal the materialized
    path."""
    from hetu_tpu.core import set_random_seed
    from hetu_tpu.models import GPT, GPTConfig

    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, 300, (4, 24)), jnp.int32)
    models = []
    for chunk in (0, 128):
        set_random_seed(0)
        models.append(GPT(GPTConfig(
            vocab_size=300, hidden_size=32, num_layers=1, num_heads=2,
            max_seq_len=32, streamed_head_chunk=chunk)))
    m_ref, m_str = models
    np.testing.assert_allclose(float(m_str.loss(ids, training=False)),
                               float(m_ref.loss(ids, training=False)),
                               rtol=1e-5)
    g_ref = jax.grad(lambda m: m.loss(ids, training=False))(m_ref)
    g_str = jax.grad(lambda m: m.loss(ids, training=False))(m_str)
    np.testing.assert_allclose(np.asarray(g_str.wte.weight),
                               np.asarray(g_ref.wte.weight),
                               rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(g_str.blocks[0].mlp.w_in),
                               np.asarray(g_ref.blocks[0].mlp.w_in),
                               rtol=2e-4, atol=1e-6)


@pytest.mark.slow
def test_bert_streamed_mlm_head_matches_materialized():
    """BertConfig.streamed_head_chunk: loss and gradients (tied embedding
    reached through the decoder transpose, plus the decoder bias) equal
    the materialized MLM head."""
    from hetu_tpu.core import set_random_seed

    rng = np.random.default_rng(0)
    B, S, V = 4, 16, 211
    ids = jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)
    lab = jnp.asarray(np.where(rng.random((B, S)) < 0.3,
                               rng.integers(0, V, (B, S)), -1), jnp.int32)
    nsp = jnp.asarray(rng.integers(0, 2, (B,)), jnp.int32)
    models = []
    for chunk in (0, 64):
        set_random_seed(0)
        # 1 layer: head equivalence needs the head, not transformer depth
        cfg = bert_base(vocab_size=V, hidden_size=32, num_layers=1,
                        num_heads=2, max_position_embeddings=S,
                        streamed_head_chunk=chunk)
        models.append(BertForPreTraining(cfg))
    m_ref, m_str = models

    def loss(m):
        return m.loss(ids, None, None, lab, nsp, training=False)[0]

    np.testing.assert_allclose(float(loss(m_str)), float(loss(m_ref)),
                               rtol=1e-5)
    g_ref = jax.grad(loss)(m_ref)
    g_str = jax.grad(loss)(m_str)
    for get, name in (
            (lambda g: g.bert.embeddings.word.weight, "tied embedding"),
            (lambda g: g.heads.decoder_bias, "decoder bias"),
            (lambda g: g.heads.transform.w, "transform"),
            (lambda g: g.heads.nsp.w, "nsp head")):
        np.testing.assert_allclose(np.asarray(get(g_str)),
                                   np.asarray(get(g_ref)),
                                   rtol=3e-4, atol=1e-6, err_msg=name)


# fused_ln=False stays the fast-tier canary; the fused composition pays a
# second interpret-mode kernel compile and rides the slow tier
@pytest.mark.parametrize("fused_ln", [
    False, pytest.param(True, marks=pytest.mark.slow)])
def test_bert_remat_is_exact(fused_ln):
    """BertConfig(remat=True) must be numerically IDENTICAL (jax.checkpoint
    recomputes, never approximates) — it only trades backward FLOPs for
    activation memory (the seq-512 batch-cap knob, bench probes it).
    Composed with fused_ln too: checkpoint wraps the Pallas custom-vjp
    block without disturbing it."""
    import jax

    from hetu_tpu.models import BertForPreTraining, bert_base

    def build(remat):
        set_random_seed(0)
        return BertForPreTraining(bert_base(
            num_layers=2, hidden_size=64, num_heads=2, vocab_size=200,
            max_position_embeddings=32, remat=remat, fused_ln=fused_ln))

    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, 200, (2, 16)), jnp.int32)
    tt = jnp.zeros((2, 16), jnp.int32)
    lab = jnp.asarray(rng.integers(0, 200, (2, 16)), jnp.int32)
    nsp = jnp.zeros((2,), jnp.int32)
    key = jax.random.key(0)

    def loss(m):
        return m.loss(ids, tt, None, lab, nsp, key=key, training=True)[0]

    l0, g0 = jax.jit(jax.value_and_grad(loss))(build(False))  # eager: 25 s
    l1, g1 = jax.jit(jax.value_and_grad(loss))(build(True))
    assert float(l0) == float(l1)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# slow tier: remat exactness compiles each model twice; the BERT
# canary covers the maybe_remat mechanism in the fast tier
@pytest.mark.slow
def test_gpt_remat_is_exact():
    """GPTConfig(remat=True): same bit-exactness contract as BERT's."""
    import jax

    from hetu_tpu.models.gpt import GPT, GPTConfig

    def build(remat):
        set_random_seed(0)
        return GPT(GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                             num_heads=4, max_seq_len=32, dropout_rate=0.1,
                             remat=remat))

    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, 128, (2, 16)), jnp.int32)
    key = jax.random.key(1)
    loss = lambda m: m.loss(ids, key=key, training=True)  # noqa: E731
    l0, g0 = jax.value_and_grad(loss)(build(False))
    l1, g1 = jax.value_and_grad(loss)(build(True))
    assert float(l0) == float(l1)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# slow tier: remat exactness compiles each model twice; the BERT
# canary covers the maybe_remat mechanism in the fast tier
@pytest.mark.slow
def test_t5_remat_is_exact():
    """T5Config(remat=True): same recompute-only contract.  Not bit-exact
    like BERT/GPT — the relative-position bias is shared ACROSS blocks, so
    its gradient accumulates in a different order under checkpoint; equal
    to tight fp32 tolerance."""
    import jax

    from hetu_tpu.models.t5 import T5Config, T5ForConditionalGeneration

    def build(remat):
        set_random_seed(0)
        return T5ForConditionalGeneration(T5Config(
            vocab_size=128, d_model=32, d_kv=8, d_ff=64, num_layers=2,
            num_heads=4, remat=remat))

    rng = np.random.default_rng(0)
    src = jnp.asarray(rng.integers(0, 128, (2, 12)), jnp.int32)
    tgt = jnp.asarray(rng.integers(0, 128, (2, 10)), jnp.int32)
    lab = jnp.asarray(rng.integers(0, 128, (2, 10)), jnp.int32)
    key = jax.random.key(1)

    def loss(m):
        out = m.loss(src, tgt, lab, key=key, training=True)
        return out[0] if isinstance(out, tuple) else out

    l0, g0 = jax.value_and_grad(loss)(build(False))
    l1, g1 = jax.value_and_grad(loss)(build(True))
    assert float(l0) == float(l1)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


# slow tier: remat exactness compiles each model twice; the BERT
# canary covers the maybe_remat mechanism in the fast tier
@pytest.mark.slow
def test_vit_remat_is_exact():
    """ViTConfig(remat=True): same bit-exactness contract."""
    import jax

    from hetu_tpu.models.vit import ViT, ViTConfig

    def build(remat):
        set_random_seed(0)
        return ViT(ViTConfig(image_size=16, patch_size=4, hidden_size=32,
                             num_layers=2, num_heads=4, num_classes=5,
                             dropout_rate=0.1, remat=remat))

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 16, 16, 3)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 5, (2,)), jnp.int32)
    key = jax.random.key(2)

    def loss(m):
        out = m.loss(x, y, key=key, training=True)
        return out[0] if isinstance(out, tuple) else out

    l0, g0 = jax.value_and_grad(loss)(build(False))
    l1, g1 = jax.value_and_grad(loss)(build(True))
    assert float(l0) == float(l1)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# slow tier: remat exactness compiles each model twice; the BERT
# canary covers the maybe_remat mechanism in the fast tier
@pytest.mark.slow
def test_swin_remat_is_exact():
    """SwinConfig(remat=True): bit-exactness across the windowed stages."""
    import jax

    from hetu_tpu.models.swin import Swin, SwinConfig

    def build(remat):
        set_random_seed(0)
        return Swin(SwinConfig(image_size=32, patch_size=4, embed_dim=16,
                               depths=(1, 1), num_heads=(2, 2),
                               window_size=4, num_classes=5, remat=remat))

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 32, 32, 3)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 5, (2,)), jnp.int32)
    loss = lambda m: m.loss(x, y, training=False)[0]  # noqa: E731
    l0, g0 = jax.value_and_grad(loss)(build(False))
    l1, g1 = jax.value_and_grad(loss)(build(True))
    assert float(l0) == float(l1)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
