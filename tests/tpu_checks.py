"""Compile-and-numerics checks of the Pallas kernels, on the chip.

    python tests/tpu_checks.py            # every kernel
    python tests/tpu_checks.py flash ln   # a subset (grouped_window: the
                                          # Trinity cell's two kernels)

NOT collected by pytest (tests/conftest.py pins the suite to the CPU).  Each
check sends one kernel through Mosaic with ``interpret=False`` spelled out —
so a wrong backend string cannot swap in the interpreter — at the shapes
``chip_smoke.py``'s train and serve phases use, and compares it with a plain
``jax.numpy`` float32 reference computed under
``jax.default_matmul_precision("highest")`` from the same (bf16-rounded)
inputs.  ``chip_smoke.py`` runs :func:`run_checks` as its ``kernels`` phase;
``tests/test_chip_smoke.py`` runs the same code tiny and interpreted on the
CPU.  Speed is not judged here: that is the benchmark's job.

Errors are ``max|kernel - ref| / max|ref|`` per output.  The tolerances are
for bf16 operands with float32 statistics and accumulation: 2e-2 covers one
bf16 rounding of the output (2^-8) plus the probabilities' cast to bf16
ahead of the PV matmul; backward passes round ``dS``/``t`` to bf16 once more
and get 4e-2.  Sampling must agree with ``ops/random.py`` token for token.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

FWD_TOL, BWD_TOL = 2e-2, 4e-2


def _f32(*xs):
    return tuple(x.astype(jnp.float32) for x in xs)


def _err(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-6))


def _compare(name, got, want, tol) -> dict:
    """One result row; raises when any output is outside ``tol``."""
    errs = [_err(g, w) for g, w in zip(jax.tree_util.tree_leaves(got),
                                       jax.tree_util.tree_leaves(want))]
    row = {"check": name, "err": round(max(errs), 6), "tol": tol}
    print(f"  {name}: max rel-to-max err {row['err']:.2e} (tol {tol:.0e})")
    if not max(errs) <= tol:
        raise AssertionError(f"{name}: errors {errs} exceed {tol}")
    return row


def _reference(fn, *args):
    with jax.default_matmul_precision("highest"):
        return jax.jit(fn)(*args)


def check_flash(interpret: bool, tiny: bool = False) -> list:
    """Flash attention forward and backward, native (B, H, S, D) layout."""
    from hetu_tpu.ops.pallas.flash import flash_attention_bhsd

    B, H, D = (1, 2, 64) if tiny else (2, 16, 64)
    rng = np.random.default_rng(0)
    rows = []
    for S in ((128,) if tiny else (128, 512, 2048)):
        q, k, v = (jnp.asarray(rng.standard_normal((B, H, S, D)) * 0.5,
                               jnp.bfloat16) for _ in range(3))
        for causal in (False, True):
            def ref(q, k, v):
                s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
                if causal:
                    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s,
                                  -1e30)
                return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1),
                                  v)

            def kern(q, k, v):
                return flash_attention_bhsd(q, k, v, causal=causal,
                                            interpret=interpret)

            def grads(f):
                return jax.grad(lambda q, k, v: jnp.sum(
                    f(q, k, v).astype(jnp.float32) ** 2), argnums=(0, 1, 2))

            tag = f"flash S{S} causal={int(causal)}"
            rows.append(_compare(f"{tag} fwd", jax.jit(kern)(q, k, v),
                                 _reference(ref, *_f32(q, k, v)), FWD_TOL))
            rows.append(_compare(
                f"{tag} bwd", jax.jit(grads(kern))(q, k, v),
                _reference(grads(ref), *_f32(q, k, v)), BWD_TOL))
    return rows


def check_lm_head(interpret: bool, tiny: bool = False) -> list:
    """LM-head cross entropy forward and backward at the BERT-large
    pretraining head shape (85% of the labels ignored, as MLM has them)."""
    from hetu_tpu.ops.pallas.lm_head import lm_head_cross_entropy_pallas

    N, E, V = (64, 32, 300) if tiny else (12288, 1024, 30522)
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.standard_normal((N, E)) * 0.5, jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((E, V)) * 0.1, jnp.bfloat16)
    b = jnp.asarray(rng.standard_normal((V,)) * 0.1, jnp.float32)
    y = jnp.asarray(np.where(rng.random(N) < 0.85, -1,
                             rng.integers(0, V, N)), jnp.int32)

    def ref(h, w, b):
        lg = h @ w + b
        lse = jax.scipy.special.logsumexp(lg, axis=1)
        yl = jnp.take_along_axis(lg, jnp.clip(y, 0)[:, None], 1)[:, 0]
        return jnp.where(y == -1, 0.0, lse - yl)

    def kern(h, w, b):
        return lm_head_cross_entropy_pallas(h, w, y, bias=b,
                                            interpret=interpret)

    def grads(f):
        return jax.grad(lambda h, w, b: jnp.sum(f(h, w, b)),
                        argnums=(0, 1, 2))

    hw32 = (*_f32(h, w), b)
    return [
        _compare("lm_head_ce fwd", jax.jit(kern)(h, w, b),
                 _reference(ref, *hw32), FWD_TOL),
        _compare("lm_head_ce bwd", jax.jit(grads(kern))(h, w, b),
                 _reference(grads(ref), *hw32), BWD_TOL),
    ]


def check_lm_head_sample(interpret: bool, tiny: bool = False) -> list:
    """Fused LM-head sampling against the seeded samplers of
    ``ops/random.py`` on float32 logits of the same operands: the tokens
    must be the same ones.  After the smoke's own shape come the serving
    cells' heads as their models store them: the Cerebras cell's tied table
    (vocabulary on axis 0, 50,257 rows: a last tile of 81) and the Trinity
    cell's untied head (axis 1, 50,048 columns: a last tile of 896)."""
    from hetu_tpu.ops.pallas.lm_head import lm_head_sample_pallas
    from hetu_tpu.ops.random import (greedy_sample, temperature_sample,
                                     top_k_sample)

    shapes = ([(4, 32, 300, 1), (4, 32, 257, 0)] if tiny else
              [(8, 1024, 32000, 1), (48, 2048, 50257, 0),
               (64, 2048, 50048, 1)])
    k, T = 5, 0.8
    rows = []
    for N, E, V, vocab_axis in shapes:
        rng = np.random.default_rng(0)
        h = jnp.asarray(rng.standard_normal((N, E)), jnp.bfloat16)
        w = jnp.asarray(rng.standard_normal((E, V)) * 0.05, jnp.bfloat16)
        keys = jax.vmap(lambda i: jax.random.fold_in(jax.random.key(7), i))(
            jnp.arange(N))
        logits = _reference(lambda h, w: h @ w, *_f32(h, w))
        want = {
            "greedy": greedy_sample(logits),
            "top_k": jax.vmap(lambda lg, kk: top_k_sample(lg, k, T, key=kk))(
                logits, keys),
            "temperature": jax.vmap(
                lambda lg, kk: temperature_sample(lg, T, key=kk))(logits,
                                                                  keys),
        }
        stored = w if vocab_axis else jnp.asarray(w.T)
        for mode, ref in want.items():
            name = (f"lm_head_sample {mode} {N}x{E}x{V} "
                    f"vocab_axis={vocab_axis}")
            got = jax.jit(lambda h, w, keys, mode=mode: lm_head_sample_pallas(
                h, w, vocab_axis=vocab_axis, mode=mode, top_k=k,
                temperature=T, keys=keys, interpret=interpret))(
                    h, stored, keys)
            same = bool(np.array_equal(np.asarray(got), np.asarray(ref)))
            print(f"  {name}: tokens {'match' if same else 'DIFFER'} "
                  f"{np.asarray(got)[:8].tolist()}")
            if not same:
                raise AssertionError(
                    f"{name}: {np.asarray(got).tolist()} != "
                    f"{np.asarray(ref).tolist()}")
            rows.append({"check": name, "err": 0.0, "tol": 0.0})
    return rows


def check_fused_ln(interpret: bool, tiny: bool = False) -> list:
    """Fused residual + dropout + LayerNorm forward and backward at the
    BERT-large hidden width, against the composed ops in float32 (the
    dropout mask is a function of the key and the index alone)."""
    from hetu_tpu import ops
    from hetu_tpu.ops.pallas.fused_ln import fused_residual_dropout_ln

    T, D = (64, 128) if tiny else (96 * 128, 1024)
    rng = np.random.default_rng(0)
    x, y = (jnp.asarray(rng.standard_normal((T, D)), jnp.bfloat16)
            for _ in range(2))
    scale = jnp.asarray(1.0 + 0.1 * rng.standard_normal(D), jnp.float32)
    bias = jnp.asarray(0.1 * rng.standard_normal(D), jnp.float32)
    key, rate = jax.random.key(3), 0.1

    def ref(x, y, scale, bias):
        return ops.layer_norm(x + ops.dropout(y, rate, key), scale, bias)

    def kern(x, y, scale, bias):
        return fused_residual_dropout_ln(x, y, scale, bias, rate=rate,
                                         key=key, interpret=interpret)

    def grads(f):
        return jax.grad(lambda *a: jnp.sum(f(*a).astype(jnp.float32) ** 2),
                        argnums=(0, 1, 2, 3))

    a32 = (*_f32(x, y), scale, bias)
    return [
        _compare("fused_ln fwd", jax.jit(kern)(x, y, scale, bias),
                 _reference(ref, *a32), FWD_TOL),
        _compare("fused_ln bwd", jax.jit(grads(kern))(x, y, scale, bias),
                 _reference(grads(ref), *a32), BWD_TOL),
    ]


def _us_a_call(kernel, q, *args, chain: int = 24) -> float:
    """Time a call of ``kernel(q, *args)`` inside one program that makes
    ``chain`` calls in turn, each fed the last one's output (as a decode
    step's layers are, so what a call shares with the others is
    computed once); the median of five runs, in us a call.  Printed beside
    a check, never judged."""
    @jax.jit
    def calls(q, *args):
        for _ in range(chain):
            q = kernel(q, *args)
        return q

    jax.block_until_ready(calls(q, *args))
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(10):
            out = calls(q, *args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / (10 * chain) * 1e6)
    return float(np.median(times))


def check_paged_decode(interpret: bool, tiny: bool = False) -> list:
    """Paged decode attention over ragged lengths, with the serve phase's
    page size and the engine's default one, and at the serving cells' own
    shape (16 heads of 128, pages of 64, the stacked five-dimensional pool
    with a static ``layer``) with a bf16 and a float32 pool, against masked
    softmax attention over the gathered pages in float32.  Two cases take
    the Cerebras cells' mixes over 48 rows of 20-entry tables: the long
    decodes' 32 to 1,152 tokens a row, and the chat cell's 13 rows of 40 to
    700 beside 35 idle slots (one token of the scratch page, as the engine
    hands the kernel an idle slot); on the chip each prints its time a
    call beside the one before the walk over live steps (356 and 143 us,
    the cells' traced means)."""
    from hetu_tpu.ops.pallas.paged_decode import paged_decode_attention

    # (batch, heads, head_dim, max_len, page, layers of a stacked pool,
    # the pool's and the query's dtype, lengths: None for random ones with
    # a full and a one-token row, "long" or "chat" for a cell's mix)
    bf16, f32 = jnp.bfloat16, jnp.float32
    cases = ([(2, 2, 64, 32, 8, None, bf16, None),
              (2, 2, 64, 32, 8, 2, bf16, None),
              (2, 2, 64, 32, 8, 2, f32, None), (6, 2, 64, 32, 8, 2, bf16,
                                                "chat")] if tiny else
             [(8, 16, 64, 2048, 64, None, bf16, None),
              (8, 16, 64, 2048, 16, None, bf16, None),
              (8, 16, 128, 1280, 64, 3, bf16, None),
              (8, 16, 128, 1280, 64, 3, f32, None),
              (48, 16, 128, 1280, 64, 3, bf16, "long"),
              (48, 16, 128, 1280, 64, 3, bf16, "chat")])
    before = {"long": 356, "chat": 143}
    rows = []
    for B, H, D, max_len, page, layers, dtype, mix in cases:
        rng = np.random.default_rng(page)
        n_pages = max_len // page
        lens = np.asarray(rng.integers(1, max_len + 1, B), np.int32)
        lens[0], lens[-1] = max_len, 1   # a full row and a one-token row
        idle = np.zeros(B, bool)
        if mix == "long":
            lens = np.asarray(rng.integers(32, 1153, B), np.int32)
        elif mix == "chat":
            idle = np.ones(B, bool)
            idle[rng.choice(B, max(B * 13 // 48, 1), replace=False)] = False
            lens = np.where(idle, 1, rng.integers(
                min(40, max_len), min(700, max_len) + 1, B)).astype(np.int32)
        tables = np.zeros((B, n_pages), np.int32)   # page 0: scratch
        nxt = 1
        for i, n in enumerate(lens):
            for j in range(0 if idle[i] else -(-int(n) // page)):
                tables[i, j] = nxt
                nxt += 1
        pool = (1 + B * n_pages, page, H, D)
        layer = None if layers is None else layers - 1
        if layers is not None:
            pool = (layers,) + pool
        k_pool, v_pool = (jnp.asarray(rng.standard_normal(pool), dtype)
                          for _ in range(2))
        q = jnp.asarray(rng.standard_normal((B, H, D)), dtype)
        tables, lens = jnp.asarray(tables), jnp.asarray(lens)

        def ref(q, k_pool, v_pool):
            if layer is not None:
                k_pool, v_pool = k_pool[layer], v_pool[layer]
            k = k_pool[tables].reshape(B, max_len, H, D)
            v = v_pool[tables].reshape(B, max_len, H, D)
            s = jnp.einsum("bhd,bkhd->bhk", q, k) / np.sqrt(D)
            live = jnp.arange(max_len)[None, None, :] < lens[:, None, None]
            p = jax.nn.softmax(jnp.where(live, s, -1e30), axis=-1)
            return jnp.einsum("bhk,bkhd->bhd", p, v)

        def call(q, k, v):
            return paged_decode_attention(q, k, v, tables, lens, layer=layer,
                                          interpret=interpret)
        got = jax.jit(call)(q, k_pool, v_pool)
        name = f"paged_decode page={page}"
        if layers is not None:
            name += f" heads={H}x{D} pool={len(pool)}d layer={layer}"
        if dtype is f32:
            name += " float32"
        if mix is not None:
            name += f" rows={B} {mix} ({int(idle.sum())} idle)"
        rows.append(_compare(name, got,
                             _reference(ref, *_f32(q, k_pool, v_pool)),
                             FWD_TOL))
        if mix in before and not interpret:
            print(f"    {_us_a_call(call, q, k_pool, v_pool):.1f} us a call "
                  f"({before[mix]} before the walk over live steps)")
    return rows


def check_grouped_window(interpret: bool, tiny: bool = False) -> list:
    """Grouped KV heads and a window, in both kernels, at the Trinity
    cell's shapes: flash forward (32 query heads over 4 KV heads of 128,
    4,096 positions, windows of 2,048 and none) against masked softmax over
    K and V repeated; paged decode over head-major five-dimensional pools
    (pages of 128) with no window over whole tables and with a window over
    rings of 17 pages in the order of their positions, on random rows and
    on the cell's mix of 16 long rows and 48 short ones."""
    from hetu_tpu.layers.cache import ring_order
    from hetu_tpu.ops.pallas.flash import flash_attention_bhsd
    from hetu_tpu.ops.pallas.paged_decode import paged_decode_attention

    H, KH, D, S, W, page = ((4, 2, 64, 256, 16, 8) if tiny
                            else (32, 4, 128, 4096, 2048, 128))
    rng = np.random.default_rng(7)
    rows = []
    q = jnp.asarray(rng.standard_normal((1, H, S, D)) * 0.5, jnp.bfloat16)
    k, v = (jnp.asarray(rng.standard_normal((1, KH, S, D)) * 0.5,
                        jnp.bfloat16) for _ in range(2))
    for window in (None, W):
        def ref(q, k, v):
            k, v = (jnp.repeat(a, H // KH, axis=1) for a in (k, v))
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
            at = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
            seen = at >= 0 if window is None else (at >= 0) & (at < window)
            return jnp.einsum("bhqk,bhkd->bhqd",
                              jax.nn.softmax(jnp.where(seen, s, -1e30), -1),
                              v)
        got = jax.jit(lambda q, k, v: flash_attention_bhsd(
            q, k, v, causal=True, window=window, interpret=interpret))(
            q, k, v)
        rows.append(_compare(f"flash {H}/{KH}x{D} S{S} window={window}", got,
                             _reference(ref, *_f32(q, k, v)), FWD_TOL))

    # the random rows (a full one, one just past the window, a one-token
    # one), and the cell's mix: a quarter of the rows long (10,752 tokens)
    # and the rest short (1,152), which on the chip prints its time a call
    # beside the one before the walk over live steps (1,364 us over whole
    # tables of 108 entries and 423 over rings of 17, on a v5e)
    max_len, ring = (64, W // page + 1) if tiny else (13824, W // page + 1)
    mixes = [("random", 3 if tiny else 8),
             ("cell", 8 if tiny else 64)]
    before = {None: 1364, W: 423}
    for mix, B in mixes:
        for window in (None, W):
            n_pages = max_len // page if window is None else ring
            if mix == "random":
                lens = np.asarray(rng.integers(1, max_len + 1, B), np.int32)
                lens[0], lens[1], lens[-1] = max_len, W + 1, 1
            else:
                lens = np.where(np.arange(B) < B // 4, max_len * 7 // 9,
                                max_len // 12).astype(np.int32)
            tables = (1 + np.arange(B * n_pages, dtype=np.int32)).reshape(
                B, n_pages)
            pool = (2, 1 + B * n_pages, KH, page, D)
            k_pool, v_pool = (jax.random.normal(jax.random.key(i), pool,
                                                jnp.bfloat16) for i in (1, 2))
            qd = jnp.asarray(rng.standard_normal((B, H, D)), jnp.bfloat16)
            tables, lens = jnp.asarray(tables), jnp.asarray(lens)
            first = None
            if window is not None:      # rings, oldest page first
                tables, first = ring_order(tables, lens, page)

            def ref(q, k_pool, v_pool, rows):
                # [rows, entries * page, KH, D], the query heads of a KV
                # head side by side: no K or V repeated for the groups
                t = tables[rows]
                kk, vv = (pool[1][t].swapaxes(2, 3).reshape(
                    len(rows), -1, KH, D) for pool in (k_pool, v_pool))
                pos = jnp.arange(kk.shape[1])[None, :] + (
                    0 if first is None else first[rows][:, None])
                n = lens[rows][:, None]
                live = pos < n
                if window is not None:
                    live &= pos >= n - window
                qg = q[rows].reshape(len(rows), KH, H // KH, D)
                s = jnp.einsum("bkgd,btkd->bkgt", qg, kk) / np.sqrt(D)
                p = jax.nn.softmax(jnp.where(live[:, None, None], s, -1e30),
                                   axis=-1)
                return jnp.einsum("bkgt,btkd->bkgd", p, vv).reshape(
                    len(rows), H, D)

            def call(q, k, v):
                return paged_decode_attention(
                    q, k, v, tables, lens, layer=1, window=window,
                    first_position=first, kv_heads=KH, interpret=interpret)
            got = jax.jit(call)(qd, k_pool, v_pool)
            a32 = _f32(qd, k_pool, v_pool)
            want = jnp.concatenate([
                _reference(ref, *a32, np.arange(r, min(r + 8, B)))
                for r in range(0, B, 8)])
            rows.append(_compare(
                f"paged_decode {H}/{KH}x{D} head-major page={page} "
                f"window={window} rows={B} {mix}", got, want, FWD_TOL))
            if mix == "cell" and not interpret:
                print(f"    {_us_a_call(call, qd, k_pool, v_pool):.1f} us a "
                      f"call ({before[window]} before the walk over live "
                      f"steps)")
    return rows


CHECKS = {"flash": check_flash, "lm_head": check_lm_head,
          "lm_head_sample": check_lm_head_sample, "ln": check_fused_ln,
          "paged_decode": check_paged_decode,
          "grouped_window": check_grouped_window}


def run_checks(names=None, *, interpret: bool, tiny: bool = False) -> list:
    """Run the named checks (default all); returns their result rows and
    raises on the first kernel that fails to compile or to match."""
    rows = []
    for n in names or CHECKS:
        print(f"[{n}]")
        rows += CHECKS[n](interpret, tiny)
    return rows


def main():
    from hetu_tpu.core.runtime import compile_cache, require_tpu
    print(require_tpu())
    compile_cache()
    run_checks(sys.argv[1:], interpret=False)
    print("ALL TPU CHECKS PASSED")


if __name__ == "__main__":
    main()
