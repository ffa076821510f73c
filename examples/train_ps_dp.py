"""PS-mode data-parallel training across worker processes
(reference comm_mode='PS': grads pushed to parameter servers, the SERVER
applies the optimizer, workers pull; bsp flag -1/0/k = ASP/BSP/SSP).

Single command spawns the server role and N local worker processes — the
reference's `heturun` worker+server pattern on one machine:

    python examples/train_ps_dp.py --workers 2 --mode bsp
    python examples/train_ps_dp.py --workers 3 --mode ssp --staleness 2
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys

sys.path.insert(0, ".")


def worker_main(args):
    import jax.numpy as jnp
    import numpy as np

    import hetu_tpu as ht
    from hetu_tpu.core.module import Module
    from hetu_tpu.embed.ps_dp import PSDataParallel
    from hetu_tpu.layers import Linear
    from hetu_tpu.ops import softmax_cross_entropy_sparse

    ht.set_random_seed(0)  # identical init everywhere; worker 0 seeds the PS

    class MLP(Module):
        def __init__(self):
            self.fc1 = Linear(32, 64)
            self.fc2 = Linear(64, 10)

        def loss(self, x, y):
            logits = self.fc2(jnp.tanh(self.fc1(x)))
            return softmax_cross_entropy_sparse(logits, y).mean()

    ps = PSDataParallel(
        MLP(), lambda m, b, k: (m.loss(b["x"], b["y"]), {}),
        [args.server], optimizer=args.optimizer, lr=args.lr,
        worker=args.worker, world=args.workers, mode=args.mode,
        staleness=args.staleness, group_id=7)

    rng = np.random.default_rng(args.worker)  # each worker's data shard
    x = rng.normal(size=(args.batch * 8, 32)).astype(np.float32)
    y = (np.abs(x.sum(1) * 3).astype(np.int64)) % 10
    for step in range(args.steps):
        lo = (step * args.batch) % (args.batch * 8)
        b = {"x": jnp.asarray(x[lo:lo + args.batch]),
             "y": jnp.asarray(y[lo:lo + args.batch])}
        m = ps.step(b)
        if step % 20 == 0 or step == args.steps - 1:
            print(f"[worker {args.worker}] step {step:4d} "
                  f"loss {float(m['loss']):.4f}", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--mode", choices=["asp", "bsp", "ssp"], default="bsp")
    ap.add_argument("--staleness", type=int, default=1)
    ap.add_argument("--optimizer", default="sgd")
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--server", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--worker", type=int, default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.worker is not None:  # child invocation
        worker_main(args)
        return

    from hetu_tpu.embed.net import EmbeddingServer

    with EmbeddingServer() as srv:
        addr = f"127.0.0.1:{srv.port}"
        print(f"parameter server on {addr}")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        procs = [
            subprocess.Popen(
                [sys.executable, __file__, "--worker", str(w),
                 "--server", addr] + [
                    f"--{k}={v}" for k, v in (
                        ("workers", args.workers), ("mode", args.mode),
                        ("staleness", args.staleness),
                        ("optimizer", args.optimizer), ("lr", args.lr),
                        ("batch", args.batch), ("steps", args.steps))],
                env=env)
            for w in range(args.workers)
        ]
        rcs = [p.wait() for p in procs]
        if any(rcs):
            sys.exit(1)


if __name__ == "__main__":
    main()
