"""Kimi-Linear (``model_type`` ``kimi_linear``) in plain float32: loss,
gradients, AdamW.

From the published ``config.json`` of ``moonshotai/Kimi-Linear-48B-A3B-
Instruct`` and the model's published modeling code.  Pre-norm blocks,
``x = x + Mix_l(RMSNorm(x)); x = x + FFN_l(RMSNorm(x))``, a last RMSNorm and
an untied head; no positional encoding anywhere (``mla_use_nope``; KDA
carries position in its state).  Layers are numbered from 1 as published:
``Mix_l`` is KDA where l is in ``linear_attn_config.kda_layers`` and MLA
where it is in ``full_attn_layers``; ``FFN_l`` is a dense SwiGLU for
l <= ``first_k_dense_replace`` and the mixture of experts after.

KDA (Kimi Delta Attention), H heads of d_k = d_v = ``head_dim``, a token:

    q = L2norm(SiLU(conv(W_q x))_h)      k likewise     v = SiLU(conv(W_v x))_h
    g = -exp(A_log_h) softplus((W_a_up W_a_down x)_h + dt_bias_h)   in R^{d_k}
    b = sigmoid(W_b x)_h                                            a scalar
    S_t = (I - b k k^T) Diag(exp(g)) S_{t-1} + b k v^T,   S_0 = 0
    o = S_t^T q / sqrt(d_k)
    y = W_o [ sigmoid(W_g_up W_g_down x)_h * RMSNorm_{d_v}(o) ]

``conv`` is a causal depthwise convolution over time of
``short_conv_kernel_size`` taps.  The recurrence is computed as written, a
token at a time by ``lax.scan``: the chunked form lives in the program
only.

MLA, expanded, no rotary: ``[c, k_pe] = split(W_kva x)``, ``c = RMSNorm(c)``,
``[k_nope, v]_h = split((W_kvb c)_h)``, ``[q_nope, q_pe]_h = (W_q x)_h``,
``k_h = [k_nope_h, k_pe]`` with ``k_pe`` shared by the heads, causal
``softmax(q_h k_h^T / sqrt(qk_nope + qk_rope)) v_h`` with the scores
materialised a head at a time, then ``W_o``.

The experts: ``s = sigmoid(W_r x)`` over the published number of experts,
the ``num_experts_per_token`` largest of ``s + bias`` chosen, weights
``routed_scaling_factor * s / sum(chosen s)``, every held expert computed
for every token and masked, the shared expert added once.

Departures, each because the catalog's row does not say and the modeling
code does (listed under ``assumed`` in the configuration's file): the
gate's parameterisation (``A_log`` a head, ``dt_bias`` a channel, softplus,
low-rank width ``kda_gate_rank``), L2norm's and the norms' epsilons, the
selection-only correction ``bias`` (zero, not trained), the initialiser,
no auxiliary loss.  The cut: ``held_experts`` names the experts this chip
holds of the ``num_experts_published`` that the router scores; what the
absent ones would add is left out, and the vocabulary is the slice
``vocab_size``.

Faults for the readings, never for a run: ``fault="no_decay_gate"`` leaves
the decay out (``g = 0``).
"""

from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import common as C

F32 = jnp.float32
L2_EPS = 1e-6
SCAN_BLOCK = 64      # tokens between the states kept for the backward pass


def layer_kinds(cfg: dict) -> list:
    """[(mixer, ffn)] by layer: ``kda`` or ``mla``, ``dense`` or ``moe``."""
    la = cfg["linear_attn_config"]
    out = []
    for l in range(1, int(cfg["num_hidden_layers"]) + 1):
        if l in la["kda_layers"]:
            mix = "kda"
        elif l in la["full_attn_layers"]:
            mix = "mla"
        else:
            raise ValueError(f"layer {l} is in neither list of "
                             f"linear_attn_config")
        out.append((mix, "dense" if l <= int(cfg["first_k_dense_replace"])
                    else "moe"))
    return out


def dims(cfg: dict) -> dict:
    la = cfg["linear_attn_config"]
    return {
        "d": int(cfg["hidden_size"]), "v": int(cfg["vocab_size"]),
        "kh": int(la["num_heads"]), "kd": int(la["head_dim"]),
        "conv": int(la["short_conv_kernel_size"]),
        "rank": int(cfg["kda_gate_rank"]),
        "h": int(cfg["num_attention_heads"]),
        "nope": int(cfg["qk_nope_head_dim"]),
        "rope": int(cfg["qk_rope_head_dim"]),
        "vd": int(cfg["v_head_dim"]), "lat": int(cfg["kv_lora_rank"]),
        "inner": int(cfg["intermediate_size"]),
        "width": int(cfg["moe_intermediate_size"]),
        "routed": int(cfg["num_experts_published"]),
        "held": [int(e) for e in cfg["held_experts"]],
        "top": int(cfg["num_experts_per_token"]),
        "shared": int(cfg["num_shared_experts"]),
    }


def shapes(cfg: dict) -> dict:
    """name -> (shape, kind).  Names are the dotted paths of the program's
    leaves.  Kinds: ``w`` a matrix and ``conv`` a convolution's taps, in the
    configuration's ``dtype``; ``g`` a norm's gain, ``a_log`` and
    ``dt_bias`` the decay gate's own, in float32; ``state`` the router's
    correction bias, float32, zero and not trained."""
    m = dims(cfg)
    d = m["d"]
    if len(m["held"]) != int(cfg["num_experts"]):
        raise ValueError("num_experts counts the experts held here")
    out = {"embed.weight": ((m["v"], d), "w"), "head": ((d, m["v"]), "w"),
           "norm_f.scale": ((d,), "g")}
    for i, (mix, ffn) in enumerate(layer_kinds(cfg)):
        p = f"blocks.{i}."
        out[p + "norm1.scale"] = ((d,), "g")
        out[p + "norm2.scale"] = ((d,), "g")
        if mix == "kda":
            hk = m["kh"] * m["kd"]
            for n in ("wq", "wk", "wv"):
                out[p + f"mix.{n}"] = ((d, hk), "w")
            for n in ("conv_q", "conv_k", "conv_v"):
                out[p + f"mix.{n}"] = ((m["conv"], hk), "conv")
            out[p + "mix.wa_down"] = ((d, m["rank"]), "w")
            out[p + "mix.wa_up"] = ((m["rank"], hk), "w")
            out[p + "mix.a_log"] = ((m["kh"],), "a_log")
            out[p + "mix.dt_bias"] = ((hk,), "dt_bias")
            out[p + "mix.wb"] = ((d, m["kh"]), "w")
            out[p + "mix.wg_down"] = ((d, m["rank"]), "w")
            out[p + "mix.wg_up"] = ((m["rank"], hk), "w")
            out[p + "mix.o_norm.scale"] = ((m["kd"],), "g")
            out[p + "mix.wo"] = ((hk, d), "w")
        else:
            h = m["h"]
            out[p + "mix.wq"] = ((d, h * (m["nope"] + m["rope"])), "w")
            out[p + "mix.wkva"] = ((d, m["lat"] + m["rope"]), "w")
            out[p + "mix.kv_norm.scale"] = ((m["lat"],), "g")
            out[p + "mix.wkvb"] = ((m["lat"], h * (m["nope"] + m["vd"])),
                                   "w")
            out[p + "mix.wo"] = ((h * m["vd"], d), "w")
        if ffn == "dense":
            out[p + "ffn.w_gate"] = ((d, m["inner"]), "w")
            out[p + "ffn.w_up"] = ((d, m["inner"]), "w")
            out[p + "ffn.w_down"] = ((m["inner"], d), "w")
        else:
            e, f = len(m["held"]), m["width"]
            out[p + "ffn.router.w"] = ((d, m["routed"]), "w")
            out[p + "ffn.router.bias"] = ((m["routed"],), "state")
            out[p + "ffn.experts.w_gate"] = ((e, d, f), "w")
            out[p + "ffn.experts.w_up"] = ((e, d, f), "w")
            out[p + "ffn.experts.w_down"] = ((e, f, d), "w")
            fs = f * m["shared"]
            out[p + "ffn.shared.w_gate"] = ((d, fs), "w")
            out[p + "ffn.shared.w_up"] = ((d, fs), "w")
            out[p + "ffn.shared.w_down"] = ((fs, d), "w")
    return out


def _uniform(key, name: str, shape, lo: float, hi: float):
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return jax.random.uniform(k, shape, F32, lo, hi)


def init_weights(cfg: dict, seed):
    """Every leaf from the seed.  Matrices normal(0, ``initializer_range``),
    convolution taps normal(0, ``conv_initializer_range``), gains
    1 + normal, ``A_log`` the log of a uniform draw from [1, 16] and
    ``dt_bias`` the inverse softplus of a log-uniform draw from
    [1e-3, 1e-1], as the modeling code draws them.  Traceable."""
    key = C.seed_key(seed) if not isinstance(seed, jax.Array) else seed
    std = float(cfg["initializer_range"])
    dtype = jnp.dtype(cfg["dtype"])
    out = {}
    for name, (shape, kind) in shapes(cfg).items():
        if kind == "w":
            out[name] = C.normal(key, name, shape, std, dtype)
        elif kind == "conv":
            out[name] = C.normal(key, name, shape,
                                 float(cfg["conv_initializer_range"]), dtype)
        elif kind == "g":
            out[name] = C.normal(key, name, shape, std, F32, mean=1.0)
        elif kind == "a_log":
            out[name] = jnp.log(_uniform(key, name, shape, 1.0, 16.0))
        elif kind == "dt_bias":
            dt = jnp.exp(_uniform(key, name, shape, np.log(1e-3),
                                  np.log(1e-1)))
            out[name] = dt + jnp.log(-jnp.expm1(-dt))
        else:
            out[name] = jnp.zeros(shape, F32)
    return out


# -- the layers -------------------------------------------------------------

def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * gain


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + L2_EPS)


def causal_conv(x, taps):
    """Depthwise over time: ``y_t = sum_j taps[j] x_{t - (K - 1) + j}``,
    zeros before the first token.  x: [rows, seq, channels]."""
    k = taps.shape[0]
    s = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, j:j + s] * taps[j].astype(F32) for j in range(k))


def swiglu(x, w_gate, w_up, w_down, mm):
    return mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)


def kda_recurrence(q, k, v, g, beta, scale, ein):
    """The delta rule with a decay a channel, a token at a time.  q, k, g:
    [rows, seq, heads, d_k]; v: [rows, seq, heads, d_v]; beta: [rows, seq,
    heads].  Returns o: [rows, seq, heads, d_v].  The state is kept every
    ``SCAN_BLOCK`` tokens and recomputed between for the backward pass."""
    r, s, h, dk = q.shape
    dv = v.shape[-1]
    pad = -s % SCAN_BLOCK
    if pad:       # g = 0, k = 0, beta = 0: the state passes unchanged
        q, k, v, g = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for t in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))

    def token(state, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        state = jnp.exp(g_t)[..., None] * state
        u = b_t[..., None] * (v_t - ein("rhk,rhkv->rhv", k_t, state))
        state = state + ein("rhk,rhv->rhkv", k_t, u)
        return state, ein("rhk,rhkv->rhv", q_t * scale, state)

    @jax.checkpoint
    def block(state, inp):
        return jax.lax.scan(token, state, inp)

    def blocked(t):       # [rows, seq, ...] -> [blocks, SCAN_BLOCK, rows, ...]
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape((-1, SCAN_BLOCK) + t.shape[1:])

    _, o = jax.lax.scan(block, jnp.zeros((r, h, dk, dv), F32),
                        tuple(blocked(t) for t in (q, k, v, g, beta)))
    o = o.reshape((-1,) + o.shape[2:])[:s]
    return jnp.moveaxis(o, 0, 1)


def kda_layer(x, w, p, m, eps, mm, ein, fault=None):
    r, s, _ = x.shape
    h, dk = m["kh"], m["kd"]
    heads = lambda t: t.reshape(r, s, h, dk)
    act = lambda n: heads(jax.nn.silu(causal_conv(
        mm(x, w[p + "w" + n]), w[p + "conv_" + n])))
    q, k, v = l2_norm(act("q")), l2_norm(act("k")), act("v")
    gate = heads(mm(mm(x, w[p + "wa_down"]), w[p + "wa_up"])
                 + w[p + "dt_bias"])
    g = -jnp.exp(w[p + "a_log"])[:, None] * jax.nn.softplus(gate)
    if fault == "no_decay_gate":
        g = jnp.zeros_like(g)
    elif fault is not None:
        raise ValueError(f"unknown fault {fault!r}")
    beta = jax.nn.sigmoid(mm(x, w[p + "wb"]))
    o = kda_recurrence(q, k, v, g, beta, dk ** -0.5, ein)
    out_gate = jax.nn.sigmoid(heads(mm(mm(x, w[p + "wg_down"]),
                                       w[p + "wg_up"])))
    o = out_gate * rms_norm(o, w[p + "o_norm.scale"], eps)
    return mm(o.reshape(r, s, h * dk), w[p + "wo"])


def mla_layer(x, w, p, m, eps, mm, ein):
    r, s, _ = x.shape
    h, nope, rope, vd = m["h"], m["nope"], m["rope"], m["vd"]
    q = mm(x, w[p + "wq"]).reshape(r, s, h, nope + rope)
    kva = mm(x, w[p + "wkva"])
    c = rms_norm(kva[..., :m["lat"]], w[p + "kv_norm.scale"], eps)
    k_pe = kva[..., m["lat"]:]
    kvb = mm(c, w[p + "wkvb"]).reshape(r, s, h, nope + vd)
    k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
        k_pe[:, :, None, :], (r, s, h, rope))], axis=-1)
    v = kvb[..., nope:]
    causal = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def head(qkv):           # one head of every row, scores materialised
        q_h, k_h, v_h = qkv
        sc = ein("rqe,rke->rqk", q_h, k_h) * (nope + rope) ** -0.5
        prob = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return ein("rqk,rke->rqe", prob, v_h)

    o = jax.lax.map(head, tuple(jnp.moveaxis(t, 2, 0) for t in (q, k, v)))
    return mm(jnp.moveaxis(o, 0, 2).reshape(r, s, h * vd), w[p + "wo"])


def route(x, w_router, bias, m, scaling, mm):
    """(chosen experts [.., top], their weights [.., top]) over all the
    published experts.  ``bias`` moves the choice, not the weight."""
    s = jax.nn.sigmoid(mm(x, w_router))
    _, chosen = jax.lax.top_k(s + bias, m["top"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, scaling * picked / jnp.sum(picked, axis=-1, keepdims=True)


def moe_layer(x, w, p, m, cfg, mm, held=None, experts=None, shared=True):
    """Router over all experts, the ``held`` ones computed for every token
    and masked, the shared expert once.  ``held`` and ``experts`` default
    to the configuration's share; the test of the shares passes others."""
    held = m["held"] if held is None else held
    experts = experts or {n: w[p + f"experts.{n}"]
                          for n in ("w_gate", "w_up", "w_down")}
    chosen, weight = route(x, w[p + "router.w"], w[p + "router.bias"], m,
                           float(cfg["routed_scaling_factor"]), mm)

    def add(y, expert):       # one held expert's part, masked, in turn
        e, w_gate, w_up, w_down = expert
        w_e = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)
        return y + w_e[..., None] * swiglu(x, w_gate, w_up, w_down, mm), None

    # a loop of one body, not a body an expert: the compiled reference is
    # a quarter the size and runs in a third of the time (PERF.md section
    # 6, PR 26: the compile cache)
    y, _ = jax.lax.scan(add, jnp.zeros_like(x), (
        jnp.asarray(list(held), jnp.int32), experts["w_gate"],
        experts["w_up"], experts["w_down"]))
    if shared:
        y = y + swiglu(x, w[p + "shared.w_gate"], w[p + "shared.w_up"],
                       w[p + "shared.w_down"], mm)
    return y


def hidden_states(w, ids, cfg, precision="float32", fault=None):
    """[rows, seq] ids -> [rows, seq, hidden] after the last norm."""
    m = dims(cfg)
    eps = float(cfg["rms_norm_eps"])
    mm = functools.partial(C.mm, precision=precision)
    ein = functools.partial(C.einsum, precision=precision)
    x = w["embed.weight"][ids].astype(F32)
    for i, (mix, ffn) in enumerate(layer_kinds(cfg)):
        p = f"blocks.{i}."

        def block(x, w, p=p, mix=mix, ffn=ffn):
            h = rms_norm(x, w[p + "norm1.scale"], eps)
            if mix == "kda":
                x = x + kda_layer(h, w, p + "mix.", m, eps, mm, ein, fault)
            else:
                x = x + mla_layer(h, w, p + "mix.", m, eps, mm, ein)
            h = rms_norm(x, w[p + "norm2.scale"], eps)
            if ffn == "dense":
                return x + swiglu(h, w[p + "ffn.w_gate"], w[p + "ffn.w_up"],
                                  w[p + "ffn.w_down"], mm)
            return x + moe_layer(h, w, p + "ffn.", m, cfg, mm)

        # recomputed in the backward pass: one layer's float32 activations
        # are live at a time
        x = jax.checkpoint(block)(x, w)
    return rms_norm(x, w["norm_f.scale"], eps)


def partial_loss(w, batch, row0, *, cfg, rows, n_tokens, precision, fault):
    """This block of rows' part of the batch's mean next-token loss."""
    cut = lambda a: jax.lax.dynamic_slice_in_dim(a, row0, rows, axis=0)
    ids, labels = cut(batch["input_ids"]), cut(batch["labels"])
    x = hidden_states(w, ids, cfg, precision, fault)
    logits = C.mm(x, w["head"], precision)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - picked) / n_tokens


def _leaf_norms(tree: dict) -> dict:
    """name -> the norm of the leaf; one an expert for the stacked experts,
    so that an expert no token reached stands alone."""
    out = {}
    for name, a in tree.items():
        sq = jnp.square(a.astype(F32))
        out[name] = jnp.sqrt(jnp.sum(sq.reshape(a.shape[0], -1), axis=1)
                             if ".experts." in name else jnp.sum(sq))
    return out


class Training:
    """The reference's own training run: one ``step`` a batch, gradients in
    float32 accumulated over blocks of ``rows`` rows.  AdamW with decay on
    matrices and convolution taps only; each update is computed in float32
    and rounded to the leaf's type when stored, so the parameters are kept
    in their own types between steps (at the published widths float32
    copies of them, the moments and the gradient would not fit one chip)
    and read as float32, and the moments and a copy of the first weights
    wait on the host while a gradient is computed.  ``sites`` is what the
    training runner hands every reference (where dropout goes); the model
    has none and it is not read."""

    def __init__(self, cfg: dict, opt: dict, weights: dict, *, rows: int,
                 precision: str = "float32", sites: str = None,
                 fault: str = None):
        self.cfg, self.opt = cfg, opt
        self.rows, self.precision, self.fault = int(rows), precision, fault
        kinds = {n: kind for n, (_, kind) in shapes(cfg).items()}
        self.trained = [n for n in weights if kinds[n] != "state"]
        self.decayed = {n for n in self.trained
                        if kinds[n] in ("w", "conv")}
        self.start = jax.device_get(weights)
        self.p = weights
        self.m = {n: np.zeros(weights[n].shape, np.float32)
                  for n in self.trained}
        self.v = {n: np.zeros(weights[n].shape, np.float32)
                  for n in self.trained}
        self.t = 0
        self._grad = {}
        self._update = jax.jit(self._update_impl, donate_argnums=(0, 3))
        self.norms = jax.jit(_leaf_norms)
        self.diff_norms = jax.jit(lambda a, b: _leaf_norms(
            {n: a[n].astype(F32) - b[n].astype(F32) for n in self.trained}))

    def _grad_fn(self, full_batch):
        if full_batch not in self._grad:
            rows = min(self.rows, full_batch)

            def every(p, batch):
                n_tokens = float(batch["labels"].size)
                w = {n: a.astype(F32) for n, a in p.items()}

                def body(acc, row0):
                    l, g = jax.value_and_grad(partial_loss)(
                        w, batch, row0, cfg=self.cfg, rows=rows,
                        n_tokens=n_tokens, precision=self.precision,
                        fault=self.fault)
                    return (acc[0] + l, {n: acc[1][n] + g[n]
                                         for n in self.trained}), None
                zero = (jnp.float32(0.0),
                        {n: jnp.zeros_like(w[n]) for n in self.trained})
                (loss, grad), _ = jax.lax.scan(
                    body, zero, jnp.arange(0, full_batch, rows,
                                           dtype=jnp.int32))
                return loss, grad
            self._grad[full_batch] = jax.jit(every)
        return self._grad[full_batch]

    def _update_impl(self, p, m, v, g, t):
        o = self.opt
        b1, b2 = o["beta1"], o["beta2"]
        lr, wd, eps = o["learning_rate"], o["weight_decay"], o["eps"]
        newp, newm, newv = dict(p), {}, {}
        for n in self.trained:
            p32 = p[n].astype(F32)
            newm[n] = b1 * m[n] + (1 - b1) * g[n]
            newv[n] = b2 * v[n] + (1 - b2) * jnp.square(g[n])
            mhat = newm[n] / (1 - b1 ** t)
            vhat = newv[n] / (1 - b2 ** t)
            decay = wd if n in self.decayed else 0.0
            upd = p32 - lr * (mhat / (jnp.sqrt(vhat) + eps) + decay * p32)
            newp[n] = C.rounded(upd, str(p[n].dtype)).astype(p[n].dtype)
        return newp, newm, newv

    def step(self, batch: dict, key=None) -> tuple:
        """One step on ``batch``.  Returns (loss, the first step's gradient
        norms by leaf or None).  ``key`` is unused: the model has no
        dropout."""
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        b = batch["input_ids"].shape[0]
        if b % min(self.rows, b):
            raise ValueError(f"{b} rows do not split into blocks of "
                             f"{self.rows}")
        loss, grad = self._grad_fn(b)(self.p, batch)
        self.t += 1
        gnorms = self.norms(grad) if self.t == 1 else None
        self.p, m, v = self._update(self.p, self.m, self.v, grad,
                                    jnp.float32(self.t))
        self.m, self.v = jax.device_get((m, v))
        return float(loss), gnorms

    def change_norms(self) -> dict:
        """name -> norm of (parameters now - parameters as first made).
        The first weights come back from the host in their own type.  (Made
        again from the seed inside the subtraction's program, the v5e would
        not round them to bfloat16: excess precision, PERF.md section 6,
        PR 23; every bfloat16 leaf's change then held its first rounding.)"""
        return self.diff_norms(self.p, self.start)
