"""BERT pre-training in plain float32: loss, gradients, AdamW.

From Devlin et al. 2018 and google-research/bert ``modeling.py``: token +
position + segment embeddings and a layer norm; ``num_hidden_layers``
post-LN blocks (self-attention, residual, layer norm; GELU feed-forward,
residual, layer norm); a tanh pooler over the first token; the masked-LM
head (dense, GELU, layer norm, the tied embedding matrix and a bias) and
the next-sentence head; the loss is the mean masked-LM cross entropy over
the masked positions plus the mean next-sentence cross entropy.

Every value is the published configuration's: ``hidden_act`` ``gelu`` is
the exact form, layer norms add ``layer_norm_eps`` (1e-12) to the variance.
The optimizer is the cell's: AdamW, with no decay on the kinds of leaf
that its ``no_decay`` lists (google-research/bert ``optimization.py``
leaves out layer norms and biases).  Parameters live in the type the
weights were made in (``dtype``; layer norms float32), the moments in
float32: each update is computed in float32 and rounded to the parameter's
type when stored.

Dropout can only be compared mask for mask, so its places are a parameter,
``sites``:

- ``published``: on the embeddings, on the attention probabilities, on the
  attention output and on the feed-forward output, as the publication has
  them;
- ``program``: on the attention context, on the attention output and on
  the feed-forward output, where ``hetu_tpu/models/bert.py`` has them.  A
  cell's file says which its ``correct`` follows; PERF.md gives the reading
  of the other.

The dropout bits are the framework's documented counter hash of (key, flat
position) and not ``jax.random``: this file holds its own copy of that
definition (``dropout_bits``), and the keys are split as the program's
trainer hands them down (one a block, then three).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import common as C

F32 = jnp.float32
STACKED = ("wqkv", "bqkv", "wo", "bo", "ln1_g", "ln1_b", "w_in", "b_in",
           "w_out", "b_out", "ln2_g", "ln2_b")


def shapes(cfg: dict) -> dict:
    """name -> (shape, kind): ``w`` matrices and ``b`` biases in the
    configuration's ``dtype``, ``g``/``lb`` layer norm gains and biases in
    float32."""
    h, i, l = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["num_hidden_layers"]
    v = cfg["vocab_size"]
    return {
        "word": ((v, h), "w"), "pos": ((cfg["max_position_embeddings"], h),
                                       "w"),
        "type": ((cfg["type_vocab_size"], h), "w"),
        "emb_ln_g": ((h,), "g"), "emb_ln_b": ((h,), "lb"),
        "wqkv": ((l, h, 3 * h), "w"), "bqkv": ((l, 3 * h), "b"),
        "wo": ((l, h, h), "w"), "bo": ((l, h), "b"),
        "ln1_g": ((l, h), "g"), "ln1_b": ((l, h), "lb"),
        "w_in": ((l, h, i), "w"), "b_in": ((l, i), "b"),
        "w_out": ((l, i, h), "w"), "b_out": ((l, h), "b"),
        "ln2_g": ((l, h), "g"), "ln2_b": ((l, h), "lb"),
        "pool_w": ((h, h), "w"), "pool_b": ((h,), "b"),
        "tr_w": ((h, h), "w"), "tr_b": ((h,), "b"),
        "tr_ln_g": ((h,), "g"), "tr_ln_b": ((h,), "lb"),
        "dec_b": ((v,), "b"), "nsp_w": ((h, 2), "w"), "nsp_b": ((2,), "b"),
    }


def init_weights(cfg: dict, seed):
    """Every leaf from the seed: normal(0, ``initializer_range``) matrices
    and biases, layer norm gains 1 + normal, in the types they are trained
    in.  Traceable: call it under ``jax.jit``."""
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


def _hash_mix(x, k):
    x = x ^ k
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def dropout_bits(key, shape):
    """The framework's dropout bits (``hetu_tpu/ops/nn.py`` documents them
    as the one definition its kernels share): two murmur3 finalizer rounds
    over the flat position, folded with the two words of the key."""
    words = jax.random.key_data(key).astype(jnp.uint32).reshape(-1)
    n = int(np.prod(shape))
    x = jax.lax.iota(jnp.uint32, n)
    x = _hash_mix(x, words[0])
    x = _hash_mix(x, words[1 % words.shape[0]])
    return x.reshape(shape)


def _dropout(x, rate, key, full_shape, row0):
    """Inverted dropout of the rows ``row0 : row0 + len(x)`` of a tensor of
    ``full_shape`` whose bits are laid out over the whole batch."""
    if rate == 0.0 or key is None:
        return x
    keep = 1.0 - rate
    thresh = jnp.uint32(int(min(keep * 4294967296.0, 4294967295.0)))
    bits = dropout_bits(key, full_shape)
    bits = jax.lax.dynamic_slice_in_dim(bits, row0, x.shape[0], axis=0)
    return jnp.where(bits < thresh, x / keep, 0.0)


def partial_loss(w, batch, row0, key, *, cfg, rows, full_batch, n_masked,
                 n_rows, precision, sites):
    """This block of rows' part of the batch's loss: its masked-LM nll sum
    over ``n_masked`` plus its next-sentence nll sum over ``n_rows``."""
    h = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    e = h // heads
    eps = float(cfg["layer_norm_eps"])
    rate = float(cfg["hidden_dropout_prob"])
    p_rate = float(cfg["attention_probs_dropout_prob"])
    form = C.GELU_FORMS[cfg["hidden_act"]]
    if sites not in ("published", "program"):
        raise ValueError(f"unknown dropout sites {sites!r}")
    published = sites == "published"
    mm = functools.partial(C.mm, precision=precision)
    ein = functools.partial(C.einsum, precision=precision)
    cut = lambda a: jax.lax.dynamic_slice_in_dim(a, row0, rows, axis=0)
    ids, tt = cut(batch["input_ids"]), cut(batch["token_type"])
    labels, nsp = cut(batch["mlm_labels"]), cut(batch["nsp_labels"])
    s = ids.shape[1]
    x = w["word"][ids] + w["pos"][jnp.arange(s)][None] + w["type"][tt]
    x = C.layer_norm(x, w["emb_ln_g"], w["emb_ln_b"], eps)
    if published and key is not None:
        x = _dropout(x, rate, jax.random.fold_in(key, 0xE0B),
                     (full_batch, s, h), row0)
    layer_keys = (None if key is None else
                  jax.random.split(key, cfg["num_hidden_layers"]))

    def block(x, lw_key):
        lw, lkey = lw_key
        ka = k1 = k2 = None
        if lkey is not None:
            ka, k1, k2 = jax.random.split(lkey, 3)
        qkv = mm(x, lw["wqkv"]) + lw["bqkv"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q, k, v = (t.reshape(rows, s, heads, e) for t in (q, k, v))
        sc = ein("bqhe,bkhe->bhqk", q, k) / np.sqrt(e)
        p = jax.nn.softmax(sc, axis=-1)
        if published:
            p = _dropout(p, p_rate, ka, (full_batch, heads, s, s), row0)
        ctx = ein("bhqk,bkhe->bhqe", p, v)
        if not published:
            ctx = _dropout(ctx, rate, ka, (full_batch, heads, s, e), row0)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(rows, s, h)
        a = mm(ctx, lw["wo"]) + lw["bo"]
        a = _dropout(a, rate, k1, (full_batch, s, h), row0)
        x = C.layer_norm(x + a, lw["ln1_g"], lw["ln1_b"], eps)
        y = mm(C.gelu(mm(x, lw["w_in"]) + lw["b_in"], form),
               lw["w_out"]) + lw["b_out"]
        y = _dropout(y, rate, k2, (full_batch, s, h), row0)
        return C.layer_norm(x + y, lw["ln2_g"], lw["ln2_b"], eps), None

    stacked = {n: w[n] for n in STACKED}
    # recomputed in the backward pass, so that one layer's float32
    # activations are live at a time: same numbers, less memory
    block = jax.checkpoint(block)
    if layer_keys is None:
        x, _ = jax.lax.scan(lambda c, lw: block(c, (lw, None)), x, stacked)
    else:
        x, _ = jax.lax.scan(block, x, (stacked, layer_keys))
    pooled = jnp.tanh(mm(x[:, 0], w["pool_w"]) + w["pool_b"])
    t = C.gelu(mm(x, w["tr_w"]) + w["tr_b"], form)
    t = C.layer_norm(t, w["tr_ln_g"], w["tr_ln_b"], eps)
    logits = mm(t, w["word"].T) + w["dec_b"]
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    mlm = jnp.sum(jnp.where(labels >= 0, lse - picked, 0.0))
    nl = mm(pooled, w["nsp_w"]) + w["nsp_b"]
    nsp_nll = jax.scipy.special.logsumexp(nl, axis=-1) - \
        jnp.take_along_axis(nl, nsp[:, None], axis=-1)[:, 0]
    return mlm / n_masked + jnp.sum(nsp_nll) / n_rows


FUSED = ("wqkv", "bqkv")     # query, key and value side by side


def _leaf_norms(tree: dict) -> dict:
    """name -> the norm of the leaf: one a layer for a stacked leaf, and
    for the fused query-key-value leaves one a layer and part, since the
    publication has three parameters there (and the key's bias, which
    softmax cancels, has no gradient while the other two have)."""
    out = {}
    for name, a in tree.items():
        sq = jnp.square(a.astype(F32))
        if name in FUSED:
            out[name] = jnp.sqrt(jnp.sum(sq.reshape(
                a.shape[0], -1, 3, a.shape[-1] // 3), axis=(1, 3)))
        elif name in STACKED:
            out[name] = jnp.sqrt(jnp.sum(sq.reshape(a.shape[0], -1), axis=1))
        else:
            out[name] = jnp.sqrt(jnp.sum(sq))
    return out


class Training:
    """The reference's own training run: float32 state, one ``step`` a
    batch, gradients accumulated over blocks of ``rows`` rows so that the
    float32 activations fit."""

    def __init__(self, cfg: dict, opt: dict, weights: dict, *,
                 rows: int, precision: str = "float32",
                 sites: str = "published"):
        self.cfg, self.opt = cfg, opt
        self.rows = int(rows)
        self.precision = precision
        self.sites = sites
        kinds = {"layer_norm": ("g", "lb"), "bias": ("b",)}
        spared = {k for name in opt["no_decay"] for k in kinds[name]}
        self.decayed = {n for n, (_, kind) in shapes(cfg).items()
                        if kind not in spared}
        self.start = weights                   # as made, for the change
        self.store = {n: a.dtype for n, a in weights.items()}
        self.p = {n: jnp.array(a, dtype=F32, copy=True)
                  for n, a in weights.items()}
        self.m = {n: jnp.zeros_like(a) for n, a in self.p.items()}
        self.v = {n: jnp.zeros_like(a) for n, a in self.p.items()}
        self.t = 0
        self._grad = {}
        self._update = jax.jit(self._update_impl,
                               donate_argnums=(0, 1, 2))
        self.norms = jax.jit(_leaf_norms)
        self.diff_norms = jax.jit(lambda a, b: _leaf_norms(
            {n: a[n] - b[n].astype(F32) for n in a}))

    def _grad_fn(self, full_batch, with_key):
        k = (full_batch, with_key)
        if k not in self._grad:
            rows = min(self.rows, full_batch)

            def one(w, batch, row0, key, n_masked):
                return jax.value_and_grad(partial_loss)(
                    w, batch, row0, key if with_key else None, cfg=self.cfg,
                    rows=rows, full_batch=full_batch, n_masked=n_masked,
                    n_rows=float(full_batch), precision=self.precision,
                    sites=self.sites)

            def every(w, batch, key, n_masked):
                def body(acc, row0):
                    l, g = one(w, batch, row0, key, n_masked)
                    return (acc[0] + l, jax.tree_util.tree_map(
                        jnp.add, acc[1], g)), None
                zero = (jnp.float32(0.0),
                        jax.tree_util.tree_map(jnp.zeros_like, w))
                (loss, grad), _ = jax.lax.scan(
                    body, zero, jnp.arange(0, full_batch, rows,
                                           dtype=jnp.int32))
                return loss, grad
            self._grad[k] = jax.jit(every)
        return self._grad[k]

    def _update_impl(self, p, m, v, g, t):
        o = self.opt
        b1, b2 = o["beta1"], o["beta2"]
        lr, wd, eps = o["learning_rate"], o["weight_decay"], o["eps"]
        newp, newm, newv = {}, {}, {}
        for n in p:
            newm[n] = b1 * m[n] + (1 - b1) * g[n]
            newv[n] = b2 * v[n] + (1 - b2) * jnp.square(g[n])
            mhat = newm[n] / (1 - b1 ** t)
            vhat = newv[n] / (1 - b2 ** t)
            decay = wd if n in self.decayed else 0.0
            upd = p[n] - lr * (mhat / (jnp.sqrt(vhat) + eps) + decay * p[n])
            newp[n] = C.rounded(upd, str(self.store[n]))
        return newp, newm, newv

    def step(self, batch: dict, key) -> tuple:
        """One step on ``batch`` (numpy or device arrays).  Returns (loss,
        the first step's gradient norms by leaf or None)."""
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        b = batch["input_ids"].shape[0]
        if b % min(self.rows, b):
            raise ValueError(f"{b} rows do not split into blocks of "
                             f"{self.rows}")
        n_masked = jnp.maximum(jnp.sum(batch["mlm_labels"] >= 0), 1
                               ).astype(F32)
        kk = key if key is not None else jax.random.key(0)
        loss, grad = self._grad_fn(b, key is not None)(
            self.p, batch, kk, n_masked)
        self.t += 1
        gnorms = self.norms(grad) if self.t == 1 else None
        self.p, self.m, self.v = self._update(
            self.p, self.m, self.v, grad, jnp.float32(self.t))
        return float(loss), gnorms

    def change_norms(self) -> dict:
        """name -> norm of (parameters now - parameters at the start)."""
        return self.diff_norms(self.p, self.start)
