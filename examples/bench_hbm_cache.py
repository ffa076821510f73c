"""HBM hot-row cache vs plain staged host embedding (A/B, real chip).

The north-star layout (BASELINE.md) stages hot rows to HBM; round 2
measured the HBM path LOSING because its refresh scatter was a separate
device dispatch.  Round 3 folds the refresh into
the jitted step (HBMCachedEmbedding.apply_refresh), so the comparison is
transfer-volume vs bookkeeping only.  Sweeps embed_dim and id skew:
the cache's regime (HET VLDB'22) is skewed access + large rows, where
warm steps upload O(refreshed) bytes instead of O(batch).

    python examples/bench_hbm_cache.py [--steps 30]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp
import numpy as np


def run(embedding: str, dim: int, skew: str, steps: int) -> float:
    from hetu_tpu.core import set_random_seed
    from hetu_tpu.exec import Trainer
    from hetu_tpu.models import CTRConfig, WideDeep
    from hetu_tpu.optim import AdamOptimizer

    set_random_seed(0)
    # 26k vocab: the working set fits the 65536-row caches (the CTR bench
    # regime) — at vocab >> capacity both paths just thrash the host cache
    # and the A/B measures eviction costs, not the staging layout
    batch, vocab, fields = 512, 26_000, 26
    cfg = CTRConfig(vocab=vocab, embed_dim=dim, embedding=embedding,
                    host_optimizer="adagrad", host_lr=0.05,
                    cache_capacity=65536,
                    host_bridge="staged" if embedding == "host" else "auto")
    model = WideDeep(cfg)
    trainer = Trainer(model, AdamOptimizer(1e-3),
                      lambda m, b, k: m.loss(b["dense"], b["sparse"],
                                             b["label"]))
    rng = np.random.default_rng(0)
    n_batches = 8
    if skew == "zipf":
        # zipfian per field: a small hot set covers most of the batch
        raw = rng.zipf(1.3, size=(n_batches, batch, fields))
        sparse = np.minimum(raw - 1, vocab // fields - 1).astype(np.int64)
    else:
        sparse = rng.integers(0, vocab // fields,
                              (n_batches, batch, fields)).astype(np.int64)
    sparse += np.arange(fields, dtype=np.int64) * (vocab // fields)
    dense = rng.normal(size=(n_batches, batch, 13)).astype(np.float32)
    label = rng.integers(0, 2, (n_batches, batch)).astype(np.float32)

    def step(i):
        j = i % n_batches
        b = {"dense": jnp.asarray(dense[j]),
             "sparse": jnp.asarray(sparse[j]),
             "label": jnp.asarray(label[j])}
        for m_ in trainer.staged_modules():
            m_.stage(b["sparse"])
        return trainer.step(b)

    for i in range(4):
        float(step(i)["loss"])
    chunks = []
    for rep in range(3):
        t0 = time.perf_counter()
        for i in range(steps):
            out = step(4 + rep * steps + i)
        float(out["loss"])
        chunks.append((time.perf_counter() - t0) / steps)
    return float(np.median(chunks))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    args = ap.parse_args()
    table = {}
    for skew in ("zipf", "uniform"):
        for dim in (16, 64, 256):
            row = {}
            for emb in ("host", "hbm"):
                t = run(emb, dim, skew, args.steps)
                row[emb] = round(t * 1e3, 1)
            row["hbm_speedup"] = round(row["host"] / row["hbm"], 2)
            table[f"{skew}_dim{dim}"] = row
            print(f"{skew} dim={dim}: staged {row['host']} ms  "
                  f"hbm {row['hbm']} ms  speedup {row['hbm_speedup']}x",
                  flush=True)
    print(json.dumps({"metric": "hbm_cache_ab", "table": table}))


if __name__ == "__main__":
    main()
