"""Which native-layout qkv projection is fastest at BERT-large seq 512?

A: plain (B,S,H,D) path (baseline, relayout copies around the kernel)
B: one 5-d einsum bsd,dkhe->kbhse + qkv[k] slices (r04 first cut)
C: three einsums bsd,dhe->bhse from weight slices
D: fused matmul to (B,S,3D) + one reshape/transpose to (3,B,H,S,D)

Differenced-scan device timing; prints ms/step per variant.
"""

import sys
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
import numpy as np

from hetu_tpu.layers.attention import MultiHeadAttention
from hetu_tpu.ops import dropout as dropout_op


def _bhsd_variant(mode):
    def call(self, x, mask=None, *, key=None, training=False):
        h, e = self.num_heads, self.head_dim
        b, s, d = x.shape
        if mode == "C":
            w4 = self.wqkv.astype(x.dtype).reshape(d, 3, h, e)
            b4 = (None if self.bqkv is None
                  else self.bqkv.astype(x.dtype).reshape(3, 1, h, 1, e))
            parts = []
            for i in range(3):
                p = jnp.einsum("bsd,dhe->bhse", x, w4[:, i])
                if b4 is not None:
                    p = p + b4[i]
                parts.append(p)
            q, k, v = parts
        elif mode == "D":
            qkv = x @ self.wqkv.astype(x.dtype)
            if self.bqkv is not None:
                qkv = qkv + self.bqkv.astype(x.dtype)
            qkv = qkv.reshape(b, s, 3, h, e).transpose(2, 0, 3, 1, 4)
            q, k, v = qkv[0], qkv[1], qkv[2]
        else:
            raise ValueError(mode)
        out = self.attn_fn(q, k, v, mask, causal=self.causal)
        if training and self.dropout_rate > 0.0 and key is not None:
            out = dropout_op(out, self.dropout_rate, key, training=True)
        y = jnp.einsum("bhse,hed->bsd",
                       out, self.wo.astype(x.dtype).reshape(h, e, d))
        if self.bo is not None:
            y = y + self.bo.astype(x.dtype)
        return y
    return call


def main():
    from hetu_tpu.exec.profiler import timed_scan_diff
    from examples.profile_attn_layout import build_trainer
    seq = int(sys.argv[1]) if len(sys.argv) > 1 else 512
    modes = sys.argv[2:] or ["A", "B", "C", "D"]
    orig = MultiHeadAttention._call_bhsd
    for mode in modes:
        if mode in ("C", "D"):
            MultiHeadAttention._call_bhsd = _bhsd_variant(mode)
        else:
            MultiHeadAttention._call_bhsd = orig
        t0 = time.time()
        trainer, b, cfg = build_trainer(native=(mode != "A"), seq=seq)
        t = timed_scan_diff(trainer, b, k=3)
        del trainer
        print(f"variant {mode}: {t['median_s']*1e3:.2f} ms/step "
              f"(min {t['min_s']*1e3:.2f}, spread {t['spread']}) "
              f"[{time.time()-t0:.0f}s]", flush=True)
    MultiHeadAttention._call_bhsd = orig


if __name__ == "__main__":
    main()
