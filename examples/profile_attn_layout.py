"""A/B the attention operand layout at BERT-large seq 512 on one chip.

r03 finding (ROADMAP 4b): XLA materializes a ~0.15 ms relayout copy around
every flash-kernel operand and gradient (q/k/v/do/out/dq/dk/dv x 24 layers
~ 21 ms/step, ~9% of the seq-512 step) because the model computes q/k/v in
(B, S, H, D) and the kernel tiles (B, H, S, D).  The fix under test: the
MultiHeadAttention bhsd path projects q/k/v STRAIGHT into (B, H, S, D)
(einsum; the head axes are free dims of the projection dot) and contracts
the output projection straight out of it, so no transpose op exists in the
graph on either side of the kernel, forward or backward.

Timing: differenced compiled scan (Trainer.scan_steps k vs 2k) — device
time, dispatch cancels; see hetu_tpu.exec.profiler.timed_scan_diff.
"""

import sys
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
import numpy as np


def build_trainer(native: bool, *, seq=512, batch=24, use_flash=True):
    from hetu_tpu.core import set_random_seed
    from hetu_tpu.exec import Trainer
    from hetu_tpu.models import BertForPreTraining, bert_large
    from hetu_tpu.ops.pallas import flash_attn_fn
    from hetu_tpu.optim import AdamWOptimizer

    set_random_seed(0)
    cfg = bert_large(max_position_embeddings=max(512, seq),
                     dtype=jnp.bfloat16)
    model = BertForPreTraining(
        cfg, attn_fn=flash_attn_fn(native_layout=native) if use_flash
        else None)

    def loss_fn(model, b, key):
        loss, aux = model.loss(
            b["input_ids"], b["token_type"], None,
            b["mlm_labels"], b["nsp_labels"], key=key, training=True)
        return loss, {}

    trainer = Trainer(model, AdamWOptimizer(1e-4, weight_decay=0.01),
                      loss_fn)
    rng = np.random.default_rng(0)
    b = {
        "input_ids": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32),
        "token_type": jnp.zeros((batch, seq), jnp.int32),
        "mlm_labels": jnp.asarray(
            np.where(rng.random((batch, seq)) < 0.15,
                     rng.integers(0, cfg.vocab_size, (batch, seq)), -1),
            jnp.int32),
        "nsp_labels": jnp.asarray(rng.integers(0, 2, (batch,)), jnp.int32),
    }
    return trainer, b, cfg


def measure(native: bool, *, k=3, reps=4, seq=512, batch=24):
    from hetu_tpu.exec.profiler import timed_scan_diff
    trainer, b, cfg = build_trainer(native, seq=seq, batch=batch)
    t = timed_scan_diff(trainer, b, k=k, reps=reps)
    del trainer
    return t


def main():
    seq = int(sys.argv[1]) if len(sys.argv) > 1 else 512
    batch = int(sys.argv[2]) if len(sys.argv) > 2 else 24
    for native in (False, True):
        t0 = time.time()
        t = measure(native, seq=seq, batch=batch)
        print(f"native={native} seq={seq} batch={batch}: "
              f"{t['median_s']*1e3:.2f} ms/step (min {t['min_s']*1e3:.2f}, "
              f"spread {t['spread']}, dispatch {t['dispatch_ms']} ms) "
              f"[{time.time()-t0:.0f}s total]", flush=True)


if __name__ == "__main__":
    main()
