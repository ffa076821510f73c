"""Staged host-embedding bridge tests.

The staged bridge (pull outside jit -> rows leaf -> push grads after the
step) must be numerically identical to the io_callback bridge — same pulls,
same pushes, same server-side optimizer applications — it only moves the
host<->device boundary outside the compiled program (needed on backends
without host-callback support).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.core import set_random_seed
from hetu_tpu.exec import Trainer
from hetu_tpu.models.ctr import CTRConfig, WideDeep
from hetu_tpu.optim import AdamOptimizer


def make_batches(n_steps, batch, rng):
    out = []
    for _ in range(n_steps):
        out.append({
            "dense": jnp.asarray(rng.normal(size=(batch, 13)), jnp.float32),
            "sparse": jnp.asarray(
                rng.integers(0, 500, (batch, 26)), jnp.int32),
            "label": jnp.asarray(
                rng.integers(0, 2, (batch,)), jnp.float32),
        })
    return out


def run_mode(bridge, batches, cache=0):
    set_random_seed(0)
    cfg = CTRConfig(vocab=500, embed_dim=8, embedding="host",
                    host_optimizer="sgd", host_lr=0.05,
                    cache_capacity=cache, host_bridge=bridge)
    model = WideDeep(cfg)
    trainer = Trainer(
        model, AdamOptimizer(1e-3),
        lambda m, b, k: m.loss(b["dense"], b["sparse"], b["label"]))
    losses = []
    for b in batches:
        for m_ in trainer.staged_modules():
            m_.stage(b["sparse"])
        losses.append(float(trainer.step(b)["loss"]))
    # final table contents for a fixed key set
    emb = trainer.model.embed
    emb.flush()
    rows = emb.table.pull(np.arange(500, dtype=np.int64))
    return losses, rows


def test_staged_matches_callback_bridge():
    rng = np.random.default_rng(0)
    batches = make_batches(6, 64, rng)
    l_cb, rows_cb = run_mode("callback", batches)
    l_st, rows_st = run_mode("staged", batches)
    np.testing.assert_allclose(l_st, l_cb, rtol=1e-5)
    np.testing.assert_allclose(rows_st, rows_cb, rtol=1e-5, atol=1e-7)


def test_staged_with_cache():
    rng = np.random.default_rng(1)
    batches = make_batches(6, 64, rng)
    l_nc, rows_nc = run_mode("staged", batches, cache=0)
    l_c, rows_c = run_mode("staged", batches, cache=500)
    # full-capacity cache with flush: numerically identical to uncached
    np.testing.assert_allclose(l_c, l_nc, rtol=1e-5)
    np.testing.assert_allclose(rows_c, rows_nc, rtol=1e-4, atol=1e-6)


def test_staged_trains():
    rng = np.random.default_rng(2)
    # learnable correlation: label from one sparse id's parity
    batches = []
    for _ in range(20):
        sparse = rng.integers(0, 100, (64, 26))
        label = (sparse[:, 0] % 2).astype(np.float32)
        batches.append({
            "dense": jnp.asarray(rng.normal(size=(64, 13)), jnp.float32),
            "sparse": jnp.asarray(sparse, jnp.int32),
            "label": jnp.asarray(label),
        })
    set_random_seed(0)
    cfg = CTRConfig(vocab=100, embed_dim=8, embedding="host",
                    host_optimizer="adagrad", host_lr=0.2,
                    host_bridge="staged")
    model = WideDeep(cfg)
    trainer = Trainer(
        model, AdamOptimizer(3e-3),
        lambda m, b, k: m.loss(b["dense"], b["sparse"], b["label"]))
    losses = []
    for epoch in range(5):  # several passes over the 20 batches
        for b in batches:
            for m_ in trainer.staged_modules():
                m_.stage(b["sparse"])
            losses.append(float(trainer.step(b)["loss"]))
    assert losses[-1] < losses[0] * 0.7, (losses[0], losses[-1])


def test_push_before_stage_raises():
    from hetu_tpu.embed import StagedHostEmbedding
    set_random_seed(0)
    emb = StagedHostEmbedding(10, 4)
    with pytest.raises(RuntimeError):
        emb.push_grads(np.zeros((2, 4), np.float32))


def test_staged_prefetch_overlap():
    """Prefetched stage == synchronous stage (cache path)."""
    rng = np.random.default_rng(7)
    batches = make_batches(5, 64, rng)

    def run(prefetch):
        set_random_seed(0)
        cfg = CTRConfig(vocab=500, embed_dim=8, embedding="host",
                        host_optimizer="sgd", host_lr=0.05,
                        cache_capacity=500, host_bridge="staged")
        model = WideDeep(cfg)
        trainer = Trainer(
            model, AdamOptimizer(1e-3),
            lambda m, b, k: m.loss(b["dense"], b["sparse"], b["label"]))
        losses = []
        for i, b in enumerate(batches):
            for m_ in trainer.staged_modules():
                m_.stage(b["sparse"])
            losses.append(float(trainer.step(b)["loss"]))
            # prefetch AFTER the step's push so the comparison with the
            # synchronous path is deterministic (prefetching before the
            # push is allowed — bounded staleness — but racy to test)
            if prefetch and i + 1 < len(batches):
                for m_ in trainer.staged_modules():
                    m_.prefetch(batches[i + 1]["sparse"])
        return losses

    np.testing.assert_allclose(run(True), run(False), rtol=1e-5)


def test_async_push_trains_and_flushes():
    """ASP-style async pushes (reference PS default bsp=-1): training
    converges, pushes apply in FIFO order, and flush_pushes() is a
    barrier after which the host table reflects every queued push."""
    import jax
    import jax.numpy as jnp

    from hetu_tpu.core import set_random_seed
    from hetu_tpu.embed import StagedHostEmbedding

    set_random_seed(0)
    # bare (uncached) tables must refuse async pushes: the engine's
    # lockless pull would race the worker thread's writes
    with pytest.raises(ValueError):
        StagedHostEmbedding(64, 8, optimizer="sgd", lr=1.0,
                            async_push=True)
    emb = StagedHostEmbedding(64, 8, optimizer="sgd", lr=1.0,
                              cache_capacity=64, async_push=True)
    ids = np.arange(8, dtype=np.int64)
    emb.stage(ids)
    before = np.asarray(emb.rows).copy()
    g = jnp.ones((8, 8), jnp.float32)
    emb.push_grads(g)          # queued, applies on the worker
    emb.flush_pushes()         # barrier
    emb.stage(ids)
    after = np.asarray(emb.rows)
    # sgd lr=1.0: rows must have moved by exactly -1 * grad
    np.testing.assert_allclose(after, before - 1.0, atol=1e-5)

    # a full little training loop converges
    set_random_seed(0)
    from hetu_tpu.exec import Trainer
    from hetu_tpu.models import CTRConfig, WideDeep
    from hetu_tpu.optim import AdamOptimizer
    cfg = CTRConfig(vocab=500, embed_dim=8, embedding="host",
                    host_bridge="staged", host_optimizer="adagrad",
                    host_lr=0.1, cache_capacity=512, host_async_push=True)
    model = WideDeep(cfg)
    trainer = Trainer(model, AdamOptimizer(1e-2),
                      lambda m, b, k: m.loss(b["dense"], b["sparse"],
                                             b["label"]))
    rng = np.random.default_rng(0)
    b = {"dense": jnp.asarray(rng.normal(size=(64, 13)), jnp.float32),
         "sparse": jnp.asarray(rng.integers(0, 500, (64, 26)), jnp.int32),
         "label": jnp.asarray(rng.integers(0, 2, (64,)), jnp.float32)}
    losses = []
    for _ in range(12):
        for m_ in trainer.staged_modules():
            m_.stage(b["sparse"])
        losses.append(float(trainer.step(b)["loss"]))
    for m_ in trainer.staged_modules():
        m_.flush_pushes()
    assert losses[-1] < losses[0] * 0.9, losses
