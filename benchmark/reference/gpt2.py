"""The GPT-2 block's forward pass in plain float32: logits.

From Radford et al. 2019 and the Cerebras-GPT paper (arXiv:2304.03208,
which trains the GPT-2 architecture unchanged): learned token and position
embeddings; ``n_layer`` pre-LN blocks (layer norm, causal self-attention
scaled by 1/sqrt(head), residual; layer norm, GELU feed-forward,
residual); a final layer norm; logits against the tied embedding matrix.

Every value is the published configuration's: ``activation_function``
``gelu`` is the exact (erf) form, ``gelu_new`` the tanh form."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import common as C

F32 = jnp.float32
STACKED = ("ln1_g", "ln1_b", "wqkv", "bqkv", "wo", "bo", "ln2_g", "ln2_b",
           "w_in", "b_in", "w_out", "b_out")


def shapes(cfg: dict) -> dict:
    h, i, l = cfg["n_embd"], cfg["n_inner"], cfg["n_layer"]
    return {
        "wte": ((cfg["vocab_size"], h), "w"),
        "wpe": ((cfg["n_positions"], h), "w"),
        "ln1_g": ((l, h), "g"), "ln1_b": ((l, h), "lb"),
        "wqkv": ((l, h, 3 * h), "w"), "bqkv": ((l, 3 * h), "b"),
        "wo": ((l, h, h), "w"), "bo": ((l, h), "b"),
        "ln2_g": ((l, h), "g"), "ln2_b": ((l, h), "lb"),
        "w_in": ((l, h, i), "w"), "b_in": ((l, i), "b"),
        "w_out": ((l, i, h), "w"), "b_out": ((l, h), "b"),
        "lnf_g": ((h,), "g"), "lnf_b": ((h,), "lb"),
    }


def weight_bytes(cfg: dict) -> int:
    """Bytes of the weights as served: ``dtype`` for matrices and biases,
    float32 for the layer norms."""
    wide = jnp.dtype(cfg["dtype"]).itemsize
    return sum(int(np.prod(shape)) * (wide if kind in ("w", "b") else 4)
               for shape, kind in shapes(cfg).values())


def init_weights(cfg: dict, seed):
    """Every leaf from the seed, in the types they are served in
    (``dtype`` for matrices and biases, float32 for the layer norms).
    Traceable: call it under ``jax.jit``."""
    key = C.seed_key(seed) if not isinstance(seed, jax.Array) else seed
    std = float(cfg["initializer_range"])
    dtype = jnp.dtype(cfg["dtype"])
    out = {}
    for name, (shape, kind) in shapes(cfg).items():
        if kind in ("w", "b"):
            out[name] = C.normal(key, name, shape, std, dtype)
        else:
            out[name] = C.normal(key, name, shape, std, F32,
                                 mean=1.0 if kind == "g" else 0.0)
    return out


def logits_at(w, tokens, positions, *, cfg, precision="float32"):
    """float32 logits ``(len(positions), vocab)`` of the next token after
    each of ``positions`` of the sequence ``tokens`` (one sequence; what
    lies behind a position cannot reach it, so padding at the end is
    harmless)."""
    h, heads = cfg["n_embd"], cfg["n_head"]
    e = h // heads
    eps = float(cfg["layer_norm_epsilon"])
    form = C.GELU_FORMS[cfg["activation_function"]]
    mm = functools.partial(C.mm, precision=precision)
    ein = functools.partial(C.einsum, precision=precision)
    t = tokens.shape[0]
    x = w["wte"][tokens] + w["wpe"][jnp.arange(t)]
    causal = jnp.tril(jnp.ones((t, t), bool))

    def block(x, lw):
        a = C.layer_norm(x, lw["ln1_g"], lw["ln1_b"], eps)
        qkv = mm(a, lw["wqkv"]) + lw["bqkv"]
        q, k, v = (z.reshape(t, heads, e) for z in jnp.split(qkv, 3, -1))
        sc = ein("qhe,khe->hqk", q, k) / np.sqrt(e)
        p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
        ctx = ein("hqk,khe->qhe", p, v).reshape(t, h)
        x = x + mm(ctx, lw["wo"]) + lw["bo"]
        m = C.layer_norm(x, lw["ln2_g"], lw["ln2_b"], eps)
        x = x + mm(C.gelu(mm(m, lw["w_in"]) + lw["b_in"], form),
                   lw["w_out"]) + lw["b_out"]
        return x, None

    x, _ = jax.lax.scan(block, x, {n: w[n] for n in STACKED})
    x = C.layer_norm(x[positions], w["lnf_g"], w["lnf_b"], eps)
    return mm(x, w["wte"].T)


def to_float32(weights: dict) -> dict:
    return {n: a.astype(F32) for n, a in weights.items()}
