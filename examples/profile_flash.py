"""Flash-attention kernel timing on the real chip.

Measures fwd-only and fwd+bwd wall time for the Pallas kernel vs the XLA
materialized path at the bench shapes, cancelling the per-dispatch host
cost by differencing two chained-scan lengths (see chain_timer).

    python examples/profile_flash.py [--causal] \
        [--shape B,S,H,D] [--block-q N] [--block-k N]

Prints fwd ms, bwd ms (= total - fwd), the bwd/fwd ratio, and the XLA
reference numbers for the same shape.
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def chain_timer(fn, args, reps=5, lengths=(50, 250)):
    """Seconds per call of fn, by differencing two scan lengths.

    A jitted call costs a fixed host dispatch whatever the program size
    (about 0.6 ms dispatch-plus-wait on the v5e host, PR 21 chip run),
    comparable to the kernels timed here; the difference between a
    length-L2 and a length-L1 scan of the same body cancels it.  The scan
    carry perturbs q with the output so calls stay data-dependent (no
    CSE).
    """
    def chained(length):
        def run(*xs):
            def body(carry, _):
                out = fn(*carry)
                q = carry[0] + 1e-6 * out.astype(carry[0].dtype)
                return (q,) + carry[1:], ()
            carry, _ = jax.lax.scan(body, xs, None, length=length)
            return carry[0]
        return jax.jit(run)

    def best(jfn):
        r = jfn(*args)
        np.asarray(jax.device_get(r[(0,) * r.ndim]))  # sync
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            r = jfn(*args)
            np.asarray(jax.device_get(r[(0,) * r.ndim]))
            ts.append(time.perf_counter() - t0)
        return float(np.min(ts))

    l1, l2 = lengths
    t1, t2 = best(chained(l1)), best(chained(l2))
    return max(t2 - t1, 1e-9) / (l2 - l1)


def xla_attn(q, k, v, causal):
    # the exact materialized path the kernel replaces (and falls back to)
    from hetu_tpu.layers.attention import dot_product_attention
    return dot_product_attention(q, k, v, causal=causal)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--causal", action="store_true")
    ap.add_argument("--shape", default="24,512,16,64")
    ap.add_argument("--block-q", type=int, default=None)
    ap.add_argument("--block-k", type=int, default=None)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--xla", action="store_true", help="also time XLA path")
    args = ap.parse_args()

    from hetu_tpu.ops.pallas.flash import flash_attention

    B, S, H, D = map(int, args.shape.split(","))
    dtype = jnp.dtype(args.dtype)
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, D)) * 0.5, dtype)
               for _ in range(3))

    flash = functools.partial(flash_attention, causal=args.causal,
                              block_q=args.block_q, block_k=args.block_k)

    def grad_wrap(attn):
        # all three grads, summed into one live output — argnums=(0,) would
        # let XLA dead-code-eliminate the dK/dV matmuls from non-fused paths
        g = jax.grad(lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2),
                     argnums=(0, 1, 2))
        return lambda q, k, v: sum(g(q, k, v))

    fwd = chain_timer(flash, (q, k, v))
    tot = chain_timer(grad_wrap(flash), (q, k, v))
    bwd = tot - fwd
    # attention flops (fwd): 4*B*H*S^2*D (2 matmuls), /2 if causal
    flops = 4 * B * H * S * S * D * (0.5 if args.causal else 1.0)
    print(f"flash  B{B} S{S} H{H} D{D} causal={args.causal} {args.dtype}: "
          f"fwd {fwd*1e3:.3f} ms ({flops/fwd/1e12:.1f} TF/s)  "
          f"fwd+bwd {tot*1e3:.3f} ms  bwd {bwd*1e3:.3f} ms  "
          f"ratio {bwd/fwd:.2f}")
    if args.xla:
        xf = functools.partial(xla_attn, causal=args.causal)
        fwd_x = chain_timer(xf, (q, k, v))
        tot_x = chain_timer(grad_wrap(xf), (q, k, v))
        print(f"xla    same shape: fwd {fwd_x*1e3:.3f} ms  "
              f"fwd+bwd {tot_x*1e3:.3f} ms  bwd {(tot_x-fwd_x)*1e3:.3f} ms  "
              f"ratio {(tot_x-fwd_x)/fwd_x:.2f}")


if __name__ == "__main__":
    main()
