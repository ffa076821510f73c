"""AFMoE (``model_type`` ``afmoe``, Arcee's Trinity) in plain float32: logits.

From the published ``config.json`` of ``arcee-ai/Trinity-Mini`` and the
model's published modeling code (``modeling_afmoe.py``).  ``x_0 =
sqrt(hidden_size) E[token]`` (``mup_enabled``).  Block l (0-based), four
RMSNorms of ``hidden_size`` gains: ``h = x + N2(Attn_l(N1(x)))``, ``x' = h +
N4(FFN_l(N3(h)))``; after the last block an RMSNorm and an untied head.
``FFN_l`` is a dense SwiGLU of width ``intermediate_size`` for l <
``num_dense_layers`` and the mixture of experts after.

Attention, ``num_attention_heads`` query heads over ``num_key_value_heads``
KV heads of ``head_dim``, position t, the whole sequence at once, no cache
and no kernel:

    q = W_q u  [H, D];  k = W_k u  [KH, D];  v = W_v u  [KH, D];  g = W_g u
    q_h = RMSNorm_q(q_h);  k_j = RMSNorm_k(k_j)        (D gains each, shared
                                                        by the heads)
    sliding_attention:  q_h = RoPE(q_h, t), k_j = RoPE(k_j, t);
                        s is seen from t iff s <= t and t - s < sliding_window
    full_attention:     no position encoding;  s is seen iff s <= t
    a_h(t, s) = q_h(t) . k_{h // (H/KH)}(s) / sqrt(D)
    o_h = sum over seen s of softmax_s(a_h(t, .)) v_{h // (H/KH)}(s)
    y = W_o (o * sigmoid(g))

with K and V repeated to H heads and the scores materialised in blocks of
``QUERY_BLOCK`` query rows, the window a mask.  ``RoPE`` rotates the pairs
``(i, i + D/2)`` by ``t theta^(-2i/D)`` over all D dimensions, no scaling
(``rope_scaling`` null).

The experts (``score_func`` sigmoid, ``n_group`` = ``topk_group`` = 1):
``s = sigmoid(W_r u)`` over ``num_experts_published`` in float32; the
``num_experts_per_tok`` largest of ``s + b`` are chosen (``b`` the balancing
buffer, zero); ``w_e = route_scale s_e / sum(chosen s)`` (``route_norm``);
``y = sum over chosen and held e of w_e SwiGLU_e(u) + SwiGLU_shared(u)``,
every held expert computed for every token, a plain product an expert, and
masked.  No auxiliary loss on this path.

Departures, each because the catalog's row does not say and the modeling
code does (listed under ``assumed`` in the configuration's file): the four
norms a block, the q/k norms, the output gate, rotary on the sliding layers
alone and its pairing, the window's edge, the embedding multiplier, the
initialiser, float32 norm gains, the served dtype.

The weights as served are 4.30 GB at the benchmark's size and the serving
runner holds them beside their float32 copy: :func:`to_float32` widens the
small leaves and leaves the large matrices in the bfloat16 they are served
in, and :func:`logits_at` widens those where it uses them, a layer at a
time: the same numbers, since widening is exact.

What a run is judged at (:func:`logits_at`, ``judged_router_margin`` of the
configuration): the positions at which, in every expert layer, no held
expert is within that margin of entering or leaving the token's chosen
(:func:`held_margin`).  At the others a correct program's rounding decides
which experts answer, and one expert more or less moves a logit as far as a
fault does.

Faults for the readings and the tests, never for a run: ``fault=`` one of
``"no_window"`` (the sliding layers see every earlier token),
``"rope_on_full"`` (rotary on the full layers too) and ``"no_gate"`` (the
output gate left out).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import common as C

F32 = jnp.float32
QUERY_BLOCK = 256        # query rows whose scores are held at once
WIDEN_BELOW = 8_000_000  # to_float32 widens leaves of fewer elements
SLIDING = "sliding_attention"
FAULTS = ("no_window", "rope_on_full", "no_gate")


def dims(cfg: dict) -> dict:
    return {"d": int(cfg["hidden_size"]),
            "layers": int(cfg["num_hidden_layers"]),
            "h": int(cfg["num_attention_heads"]),
            "kh": int(cfg["num_key_value_heads"]),
            "hd": int(cfg["head_dim"]),
            "window": int(cfg["sliding_window"]),
            "dense": int(cfg["intermediate_size"]),
            "width": int(cfg["moe_intermediate_size"]),
            "experts": int(cfg.get("num_experts_published",
                                   cfg["num_experts"])),
            "held": len(held_experts(cfg)),
            "top_k": int(cfg["num_experts_per_tok"]),
            "shared": int(cfg["num_shared_experts"])
            * int(cfg["moe_intermediate_size"]),
            "first_dense": int(cfg["num_dense_layers"]),
            "vocab": int(cfg["vocab_size"])}


def held_experts(cfg: dict) -> list:
    """The experts of the router's whose weights this chip holds
    (``num_experts`` counts them; the router is ``num_experts_published``
    wide)."""
    return list(cfg.get("held_experts", range(int(cfg["num_experts"]))))


def layer_types(cfg: dict) -> list:
    types = list(cfg["layer_types"])
    if len(types) != int(cfg["num_hidden_layers"]):
        raise ValueError(f"{len(types)} layer_types for "
                         f"{cfg['num_hidden_layers']} layers")
    return types


def shapes(cfg: dict) -> dict:
    """Leaf -> (shape, kind), named by the dotted paths of the program's
    tree: ``w`` a matrix (``dtype``), ``g`` a norm gain (float32), ``state``
    the router's balancing buffer (float32, zero)."""
    m = dims(cfg)
    d, hq, hk = m["d"], m["h"] * m["hd"], m["kh"] * m["hd"]
    out = {"embed.weight": ((m["vocab"], d), "w"),
           "norm_f.scale": ((d,), "g"), "lm_head": ((d, m["vocab"]), "w")}
    for l in range(m["layers"]):
        p = f"blocks.{l}."
        out.update({p + f"norm{i}.scale": ((d,), "g") for i in (1, 2, 3, 4)})
        out.update({
            p + "attn.wq": ((d, hq), "w"), p + "attn.wk": ((d, hk), "w"),
            p + "attn.wv": ((d, hk), "w"), p + "attn.wg": ((d, hq), "w"),
            p + "attn.wo": ((hq, d), "w"),
            p + "attn.q_norm.scale": ((m["hd"],), "g"),
            p + "attn.k_norm.scale": ((m["hd"],), "g")})
        if l < m["first_dense"]:
            ffn = {"ffn.": m["dense"]}
        else:
            out[p + "ffn.router.w"] = ((d, m["experts"]), "w")
            out[p + "ffn.router.bias"] = ((m["experts"],), "state")
            for n, shape in (("w_gate", (d, m["width"])),
                             ("w_up", (d, m["width"])),
                             ("w_down", (m["width"], d))):
                out[p + "ffn.experts." + n] = ((m["held"],) + shape, "w")
            ffn = {"ffn.shared.": m["shared"]}
        for q, f in ffn.items():
            out.update({p + q + "w_gate": ((d, f), "w"),
                        p + q + "w_up": ((d, f), "w"),
                        p + q + "w_down": ((f, d), "w")})
    return out


def weight_bytes(cfg: dict) -> int:
    """Bytes of the weights as served."""
    wide = jnp.dtype(cfg["dtype"]).itemsize
    return sum(int(np.prod(shape)) * (wide if kind == "w" else 4)
               for shape, kind in shapes(cfg).values())


def init_weights(cfg: dict, seed):
    """Every leaf from the seed, in the types they are served in: matrices
    normal(0, ``initializer_range``) in ``dtype``, norm gains 1 + normal(0,
    ``initializer_range``) in float32, the balancing buffer zero.
    Traceable."""
    key = C.seed_key(seed) if not isinstance(seed, jax.Array) else seed
    std, dtype = float(cfg["initializer_range"]), jnp.dtype(cfg["dtype"])

    def leaf(name, shape, kind):
        if kind == "state":
            return jnp.zeros(shape, F32)
        if kind == "w":
            return C.normal(key, name, shape, std, dtype)
        return C.normal(key, name, shape, std, F32, mean=1.0)

    return {name: leaf(name, shape, kind)
            for name, (shape, kind) in shapes(cfg).items()}


def to_float32(weights: dict) -> dict:
    """The small leaves in float32; the large ones as they are served
    (module docstring), widened where :func:`logits_at` uses them."""
    return {n: (a.astype(F32) if a.size < WIDEN_BELOW else a)
            for n, a in weights.items()}


# -- the layers ---------------------------------------------------------------

def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * gain


def rope(x, theta: float):
    """``x [t, heads, D]``, the pairs ``(i, i + D/2)`` rotated by ``t
    theta^(-2i/D)``."""
    t, half = x.shape[0], x.shape[-1] // 2
    inv_freq = theta ** (-np.arange(half, dtype=np.float64) / half)
    angle = jnp.arange(t, dtype=F32)[:, None, None] * jnp.asarray(
        inv_freq.astype(np.float32))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def swiglu(x, w_gate, w_up, w_down, mm):
    return mm(jax.nn.silu(mm(x, w_gate.astype(F32))) * mm(
        x, w_up.astype(F32)), w_down.astype(F32))


def attention(x, w, p, cfg, sliding: bool, mm, ein, fault):
    m = dims(cfg)
    t, h, kh, hd = x.shape[0], m["h"], m["kh"], m["hd"]
    eps = float(cfg["rms_norm_eps"])
    q = mm(x, w[p + "attn.wq"].astype(F32)).reshape(t, h, hd)
    k = mm(x, w[p + "attn.wk"].astype(F32)).reshape(t, kh, hd)
    v = mm(x, w[p + "attn.wv"].astype(F32)).reshape(t, kh, hd)
    q = rms_norm(q, w[p + "attn.q_norm.scale"], eps)
    k = rms_norm(k, w[p + "attn.k_norm.scale"], eps)
    if sliding or fault == "rope_on_full":
        theta = float(cfg["rope_theta"])
        q, k = rope(q, theta), rope(k, theta)
    k, v = jnp.repeat(k, h // kh, axis=1), jnp.repeat(v, h // kh, axis=1)
    window = m["window"] if sliding and fault != "no_window" else None
    block = min(QUERY_BLOCK, t)
    pad = -t % block

    def rows(first):
        """The outputs of the query rows ``first`` to ``first + block``."""
        qb = jax.lax.dynamic_slice_in_dim(q, first, block)
        sc = ein("qhe,khe->hqk", qb, k) * hd ** -0.5
        at = (first + jnp.arange(block))[:, None] - jnp.arange(t)[None, :]
        seen = at >= 0
        if window is not None:
            seen = jnp.logical_and(seen, at < window)
        prob = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        return ein("hqk,khe->qhe", prob, v)

    if pad:     # the last block starts early and its head is dropped
        firsts = jnp.minimum(jnp.arange(0, t + pad, block), t - block)
        o = jax.lax.map(rows, firsts)
        o = jnp.concatenate([o[:-1].reshape(-1, h, hd), o[-1][pad:]])
    else:
        o = jax.lax.map(rows, jnp.arange(0, t, block)).reshape(t, h, hd)
    o = o.reshape(t, h * hd)
    if fault != "no_gate":
        o = o * jax.nn.sigmoid(mm(x, w[p + "attn.wg"].astype(F32)))
    return mm(o, w[p + "attn.wo"].astype(F32))


def route(x, w, p, cfg, mm, margins=None):
    """(chosen [t, top_k], their weights [t, top_k]) over the router's
    whole width.  ``margins``, a list, gains the layer's
    :func:`held_margin` ``[t]``."""
    m = dims(cfg)
    score = jax.nn.sigmoid(mm(x, w[p + "ffn.router.w"].astype(F32)))
    biased = score + w[p + "ffn.router.bias"]
    _, chosen = jax.lax.top_k(biased, m["top_k"])
    if margins is not None:
        margins.append(held_margin(biased, cfg))
    picked = jnp.take_along_axis(score, chosen, axis=-1)
    return chosen, float(cfg["route_scale"]) * picked / jnp.sum(
        picked, axis=-1, keepdims=True)


def held_margin(biased, cfg):
    """By how much the least settled HELD expert's score ``[t, experts]``
    would have to move for it to enter or leave the token's chosen: a
    chosen one's distance above the best score not chosen, one not chosen's
    below the least score chosen, the smallest over the held.  A change of
    the choice among experts that are not held moves only the normaliser
    (by the difference of two scores that close), so it is not counted."""
    k = dims(cfg)["top_k"]
    top = jax.lax.top_k(biased, k + 1)[0]
    least_in, best_out = top[:, k - 1:k], top[:, k:]
    mine = biased[:, jnp.asarray(held_experts(cfg), jnp.int32)]
    return jnp.min(jnp.where(mine >= least_in, mine - best_out,
                             least_in - mine), axis=-1)


def moe(x, w, p, cfg, mm, margins=None):
    chosen, weight = route(x, w, p, cfg, mm, margins)

    def one(y, e):
        """Adds held expert ``e``'s share: computed for every token,
        weighted by the token's weight where the token chose it."""
        idx, wg, wu, wd = e
        gate = jnp.sum(jnp.where(chosen == idx, weight, 0.0), axis=-1)
        return y + gate[:, None] * swiglu(x, wg, wu, wd, mm), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        jnp.asarray(held_experts(cfg), jnp.int32),
        w[p + "ffn.experts.w_gate"], w[p + "ffn.experts.w_up"],
        w[p + "ffn.experts.w_down"]))
    q = p + "ffn.shared."
    return y + swiglu(x, w[q + "w_gate"], w[q + "w_up"], w[q + "w_down"], mm)


def hidden_states(w, tokens, *, cfg, precision="float32", fault=None,
                  margins=None):
    """float32 hidden states ``(len(tokens), hidden)`` before the last norm,
    of one sequence.  ``margins``, a list, gains every expert layer's
    :func:`held_margin` ``[len(tokens)]``, in the layers' order."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")
    mm = functools.partial(C.mm, precision=precision)
    ein = functools.partial(C.einsum, precision=precision)
    m, eps = dims(cfg), float(cfg["rms_norm_eps"])
    x = w["embed.weight"][tokens].astype(F32)
    if cfg.get("mup_enabled"):
        x = x * m["d"] ** 0.5
    for l, kind in enumerate(layer_types(cfg)):
        p = f"blocks.{l}."
        g = lambda i: w[p + f"norm{i}.scale"]
        x = x + rms_norm(attention(rms_norm(x, g(1), eps), w, p, cfg,
                                   kind == SLIDING, mm, ein, fault),
                         g(2), eps)
        a = rms_norm(x, g(3), eps)
        if l < m["first_dense"]:
            y = swiglu(a, w[p + "ffn.w_gate"], w[p + "ffn.w_up"],
                       w[p + "ffn.w_down"], mm)
        else:
            y = moe(a, w, p, cfg, mm, margins)
        x = x + rms_norm(y, g(4), eps)
    return x


def logits_at(w, tokens, positions, *, cfg, precision="float32", fault=None):
    """float32 logits ``(len(positions), vocab)`` of the next token after
    each of ``positions`` of the sequence ``tokens`` (one sequence; what
    lies behind a position cannot reach it, so padding at the end is
    harmless).

    **What is judged.**  With ``judged_router_margin`` in the
    configuration, the plain float32 reference (no ``fault``) gives a
    position at which some expert layer's :func:`held_margin` is no wider
    than that margin the same logit for every token: whatever was served
    there reads a gap of nought.  At such a position the rounding of a
    correct program decides which experts answer, and with four norms a
    block one expert more or less moves a logit by as much as a fault does
    (PERF.md section 6, PR 32); at the others the choice is settled and
    only the arithmetic is compared.  A pass in the program's place (a
    ``fault``, a lower ``precision``) is never blanked: its tokens are
    judged at every position the plain reference judges."""
    margin = float(cfg.get("judged_router_margin", 0.0))
    judging = margin > 0 and precision == "float32" and fault is None
    margins = [] if judging else None
    x = hidden_states(w, tokens, cfg=cfg, precision=precision, fault=fault,
                      margins=margins)
    x = rms_norm(x[positions], w["norm_f.scale"], float(cfg["rms_norm_eps"]))
    logits = C.mm(x, w["lm_head"].astype(F32), precision=precision)
    if judging and margins:
        settled = jnp.min(jnp.stack(margins), axis=0)[positions] > margin
        logits = jnp.where(settled[:, None], logits, 0.0)
    return logits
