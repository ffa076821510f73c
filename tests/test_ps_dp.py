"""PS-mode dense data parallelism (embed/ps_dp.py over the TCP PS).

Reference: comm_mode='PS' — grads pushed to the server, SERVER applies the
optimizer, workers pull; consistency via the bsp flag (ASP/BSP/SSP).
Multi-process tests follow the reference's worker+server process pattern
(tests/pstests/) using local subprocesses.
"""

import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.core import set_random_seed
from hetu_tpu.core.module import Module
from hetu_tpu.embed.net import EmbeddingServer
from hetu_tpu.embed.ps_dp import PSDataParallel
from hetu_tpu.layers import Linear
from hetu_tpu.ops import mse_loss


class Reg(Module):
    def __init__(self):
        self.fc1 = Linear(8, 16)
        self.fc2 = Linear(16, 1)

    def loss(self, x, y):
        import jax.numpy as jnp
        pred = self.fc2(jnp.tanh(self.fc1(x)))[:, 0]
        return mse_loss(pred, y).mean()


def _data(n=64, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 8)).astype(np.float32)
    w = rng.normal(size=(8,)).astype(np.float32)
    y = x @ w + 0.1 * rng.normal(size=n).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(y)


def test_single_worker_converges():
    with EmbeddingServer() as srv:
        set_random_seed(0)
        model = Reg()
        ps = PSDataParallel(
            model, lambda m, b, k: (m.loss(b["x"], b["y"]), {}),
            [f"127.0.0.1:{srv.port}"], optimizer="sgd", lr=0.05, chunk=16)
        x, y = _data()
        losses = [float(ps.step({"x": x, "y": y})["loss"]) for _ in range(60)]
        assert losses[-1] < 0.3 * losses[0]


def test_leaf_chunking_roundtrip():
    """Odd-shaped leaves survive the chunk/pad mapping bit-exactly."""
    from hetu_tpu.embed.ps_dp import _LeafTable

    with EmbeddingServer() as srv:
        leaf = jnp.asarray(
            np.random.default_rng(0).normal(size=(5, 7)).astype(np.float32))
        t = _LeafTable(f"127.0.0.1:{srv.port}", 42, leaf, chunk=4,
                       optimizer="sgd", lr=0.1, weight_decay=0.0)
        t.init(leaf)
        np.testing.assert_array_equal(np.asarray(t.pull()), np.asarray(leaf))


@pytest.mark.parametrize("mode,staleness", [("bsp", 0), ("ssp", 2)])
@pytest.mark.slow
def test_two_worker_processes(mode, staleness, tmp_path):
    """Two OS-process workers train against one PS server; both converge and
    end on the SAME server-held parameters."""
    with EmbeddingServer() as srv:
        script = textwrap.dedent(f"""
            import sys
            sys.path.insert(0, {repr(os.getcwd())})
            import numpy as np, jax.numpy as jnp
            from hetu_tpu.core import set_random_seed
            from tests.test_ps_dp import Reg, _data
            from hetu_tpu.embed.ps_dp import PSDataParallel

            worker = int(sys.argv[1])
            set_random_seed(0)  # same init on every worker
            model = Reg()
            ps = PSDataParallel(
                model, lambda m, b, k: (m.loss(b["x"], b["y"]), {{}}),
                ["127.0.0.1:{srv.port}"], optimizer="sgd", lr=0.02,
                worker=worker, world=2, mode={mode!r},
                staleness={staleness}, chunk=16, group_id=77)
            x, y = _data(seed=worker)  # different shards per worker
            losses = [float(ps.step({{"x": x, "y": y}})["loss"])
                      for _ in range(40)]
            w = np.asarray(ps.model.fc2.w).ravel()
            print("RESULT", losses[0], losses[-1], float(np.sum(w)))
        """)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        procs = [subprocess.Popen([sys.executable, "-c", script, str(w)],
                                  stdout=subprocess.PIPE,
                                  stderr=subprocess.STDOUT, text=True,
                                  env=env, cwd=os.getcwd())
                 for w in range(2)]
        outs = []
        for p in procs:
            out, _ = p.communicate(timeout=240)
            assert p.returncode == 0, out
            outs.append(out)
        results = []
        for out in outs:
            line = next(l for l in out.splitlines() if l.startswith("RESULT"))
            results.append([float(v) for v in line.split()[1:]])
        for l0, l1, _w in results:
            assert l1 < l0  # both workers' loss dropped
        # both ended on the same PS-held weights (final pull after last sync
        # may differ by at most the in-flight pushes under SSP; BSP exact)
        if mode == "bsp":
            np.testing.assert_allclose(results[0][2], results[1][2],
                                       rtol=1e-4)


@pytest.mark.slow
def test_bsp_lockstep_under_straggler(tmp_path):
    """BSP means both workers compute every round on the SAME parameters.

    Regression: with a single post-push barrier, a fast worker could pull,
    compute, and push its round-k+1 gradients while a slow worker was still
    pulling round-k parameters — the slow worker then pulled a mix.  A
    deliberately slow worker (sleep before its pull) makes that race near
    certain; the per-step pulled-parameter digests must still agree."""
    with EmbeddingServer() as srv:
        script = textwrap.dedent(f"""
            import sys, time
            sys.path.insert(0, {repr(os.getcwd())})
            import numpy as np, jax
            from hetu_tpu.core import set_random_seed
            from tests.test_ps_dp import Reg, _data
            from hetu_tpu.embed.ps_dp import PSDataParallel

            worker = int(sys.argv[1])
            set_random_seed(0)
            model = Reg()
            ps = PSDataParallel(
                model, lambda m, b, k: (m.loss(b["x"], b["y"]), {{}}),
                ["127.0.0.1:{srv.port}"], optimizer="sgd", lr=0.02,
                worker=worker, world=2, mode="bsp", chunk=16, group_id=78)
            if worker == 1:  # straggle between the push barrier and the pull
                orig = ps._refresh
                def slow_refresh():
                    time.sleep(0.1)
                    orig()
                ps._refresh = slow_refresh
            x, y = _data(seed=worker)
            digests = []
            for _ in range(8):
                ps.step({{"x": x, "y": y}})
                leaves = jax.tree_util.tree_leaves(ps.model)
                digests.append(float(sum(float(np.sum(np.asarray(l)))
                                         for l in leaves)))
            print("DIGESTS", " ".join(f"{{d!r}}" for d in digests))
        """)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        procs = [subprocess.Popen([sys.executable, "-c", script, str(w)],
                                  stdout=subprocess.PIPE,
                                  stderr=subprocess.STDOUT, text=True,
                                  env=env, cwd=os.getcwd())
                 for w in range(2)]
        outs = []
        for p in procs:
            out, _ = p.communicate(timeout=240)
            assert p.returncode == 0, out
            outs.append(out)
        digests = []
        for out in outs:
            line = next(l for l in out.splitlines()
                        if l.startswith("DIGESTS"))
            digests.append([float(v) for v in line.split()[1:]])
        assert digests[0] == digests[1], (
            "workers pulled different parameters within a BSP round:\n"
            f"{digests[0]}\n{digests[1]}")


def test_large_leaf_segmented_transfer():
    """Leaves above the server's per-frame cap move in segments
    (regression: a 23M-float embedding leaf must survive init/push/pull)."""
    import hetu_tpu.embed.ps_dp as psdp

    old = psdp._MAX_FLOATS_PER_REQ
    psdp._MAX_FLOATS_PER_REQ = 256  # force many segments without big arrays
    try:
        with EmbeddingServer() as srv:
            leaf = jnp.asarray(np.random.default_rng(0).normal(
                size=(40, 33)).astype(np.float32))
            t = psdp._LeafTable(f"127.0.0.1:{srv.port}", 9, leaf, chunk=33,
                                optimizer="sgd", lr=1.0, weight_decay=0.0)
            assert t._rows_per_req < t.rows  # actually segmented
            t.init(leaf)
            np.testing.assert_array_equal(np.asarray(t.pull()),
                                          np.asarray(leaf))
            g = np.ones((40, 33), np.float32)
            t.push_grad(jnp.asarray(g))
            np.testing.assert_allclose(np.asarray(t.pull()),
                                       np.asarray(leaf) - 1.0, rtol=1e-6)
    finally:
        psdp._MAX_FLOATS_PER_REQ = old


@pytest.mark.slow
def test_hybrid_mode_across_processes():
    """The reference's Hybrid comm mode across real processes
    (tests/hybrid_wdl_adult.sh): dense parameters data-parallel via a
    cross-process gradient allreduce, sparse embeddings through a SHARED
    network PS (server-side optimizer, ASP) — both workers converge and
    agree on the dense parameters."""
    import textwrap
    from hetu_tpu.launch import simulate_workers

    with EmbeddingServer() as srv:
        script = textwrap.dedent(f"""
            import sys
            sys.path.insert(0, {repr(os.getcwd())})
            import hetu_tpu.launch as L
            L.initialize()
            import jax, jax.numpy as jnp, numpy as np
            from jax.experimental import multihost_utils
            import hetu_tpu as ht
            from hetu_tpu.core.module import Module, trainable_mask
            from hetu_tpu.embed.net import RemoteHostEmbedding
            from hetu_tpu.layers import Linear
            from hetu_tpu.ops import binary_cross_entropy_with_logits
            from hetu_tpu.optim import SGDOptimizer

            pid = jax.process_index()
            ht.set_random_seed(0)  # identical dense init on both workers

            class WD(Module):
                def __init__(self):
                    self.embed = RemoteHostEmbedding(
                        120, 4, servers=["127.0.0.1:{srv.port}"],
                        optimizer="sgd", lr=0.1, table_id=5)
                    self.head = Linear(4 * 3, 1)

                def loss(self, sp, y):
                    e = self.embed(sp).reshape(sp.shape[0], -1)
                    return binary_cross_entropy_with_logits(
                        self.head(e)[:, 0], y).mean()

            model = WD()
            opt = SGDOptimizer(0.05)
            state = opt.init(model)
            mask = trainable_mask(model)

            @jax.jit
            def grads_fn(m, sp, y):
                return jax.value_and_grad(lambda mm: mm.loss(sp, y))(m)

            rng = np.random.default_rng(pid)  # per-worker data shard
            sp = rng.integers(0, 120, (16, 3))
            y = (sp.sum(1) % 2).astype(np.float32)
            spj, yj = jnp.asarray(sp), jnp.asarray(y)
            losses = []
            for step in range(25):
                model.embed.stage(spj)
                loss, g = grads_fn(model, spj, yj)
                # hybrid: sparse rows-grad -> PS push (ASP, server applies);
                # dense grads -> cross-process allreduce (mean)
                model.embed.push_grads(np.asarray(g.embed.rows))
                dense_g = multihost_utils.process_allgather(
                    {{"w": g.head.w, "b": g.head.b}})
                mean_g = jax.tree_util.tree_map(
                    lambda x: jnp.mean(x, 0), dense_g)
                head_g = g.head.replace(w=mean_g["w"], b=mean_g["b"])
                g2 = g.replace(head=head_g)
                model, state = opt.update(g2, state, model, mask=mask)
                losses.append(float(loss))
            wsum = float(jnp.sum(model.head.w))
            print(f"RESULT pid={{pid}} l0={{losses[0]:.4f}} "
                  f"l1={{losses[-1]:.4f}} wsum={{wsum:.6f}}")
        """)
        outs = simulate_workers(2, script, cpu_devices_per_proc=1,
                                timeout=300.0)
    results = {}
    for out in outs:
        line = next(l for l in out.splitlines() if l.startswith("RESULT"))
        parts = dict(kv.split("=") for kv in line.split()[1:])
        results[int(parts["pid"])] = parts
    for pid in (0, 1):
        assert float(results[pid]["l1"]) < float(results[pid]["l0"]), results
    # dense params identical across workers (allreduce-DP invariant)
    assert results[0]["wsum"] == results[1]["wsum"], results
