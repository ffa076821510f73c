"""DeepSeek-V2 (``model_type`` ``deepseek_v2``) in plain float32: logits.

From the published ``config.json`` of ``deepseek-ai/DeepSeek-V2-Lite`` and
the model's published modeling code.  Block l (0-based): ``x = x +
MLA_l(RMSNorm(x)); x = x + FFN_l(RMSNorm(x))``; after the last block
RMSNorm and an untied head.  ``FFN_l`` is a dense SwiGLU of width
``intermediate_size`` for l < ``first_k_dense_replace`` and the mixture of
experts after (``moe_layer_freq`` 1).

MLA, ``num_attention_heads`` heads, position t, in the EXPANDED form, the
whole sequence at once, no cache and no kernel:

    [q_nope, q_pe]_h = split((W_q x_t)_h, [nope, rope])   (q_lora_rank null)
    [c_t, k_pe_t]    = split(W_kva x_t, [kv_lora_rank, rope])
    c_t = RMSNorm(c_t);  q_pe = RoPE(q_pe, t) a head;  k_pe_t = RoPE(k_pe_t, t)
    [k_nope, v]_h    = split((W_kvb c_s)_h, [nope, v_head_dim])
    a_h(t, s) = ([q_nope, q_pe]_h . [k_nope_h(s), k_pe_s]) sigma,  s <= t
    o_h = sum_s softmax_s(a_h(t, .)) v_h(s);   y = W_o [o_1 .. o_H]

with the scores materialised in blocks of ``QUERY_BLOCK`` query rows.

Rotary with YaRN (``rope_scaling``): for i < rope / 2, ``f_i =
theta^(-2i/rope)``; ``low = floor(rope ln(L / (beta_fast 2 pi)) / (2 ln
theta))``, ``high = ceil(rope ln(L / (beta_slow 2 pi)) / (2 ln theta))``
with L ``original_max_position_embeddings``, clipped to [0, rope - 1];
``ramp_i = clip((i - low) / (high - low), 0, 1)``; ``inv_freq_i = (f_i /
factor) ramp_i + f_i (1 - ramp_i)``.  ``m(s) = 0.1 s ln(factor) + 1``; cos
and sin are scaled by ``m(mscale) / m(mscale_all_dim)`` and ``sigma = (nope
+ rope)^(-1/2) m(mscale_all_dim)^2``.  ``RoPE`` rotates the pairs ``(2i, 2i
+ 1)`` by the angle ``t inv_freq_i``.

The experts (``scoring_func`` softmax, ``topk_method`` greedy, ``n_group``
1, ``norm_topk_prob`` false): ``s = softmax(W_r x)`` over
``n_routed_experts`` in float32, the ``num_experts_per_tok`` largest chosen,
``w_e = routed_scaling_factor s_e`` as it stands; ``y = sum over chosen and
held e of w_e SwiGLU_e(x) + SwiGLU_shared(x)``, every held expert computed
for every token, a plain product an expert, and masked; the shared experts
are one gated feed-forward of width ``n_shared_experts
moe_intermediate_size``, as published.  No auxiliary loss on this path.

Departures, each because the catalog's row does not say and the modeling
code does (listed under ``assumed`` in the configuration's file): the
pairing of the rotary and where ``mscale`` enters, the initialiser, float32
norm gains, the served dtype.  The published code permutes each ``pe`` part
so that the pairs ``(2i, 2i + 1)`` become ``(i, i + rope/2)`` before it
rotates; the same permutation on ``q_pe`` and ``k_pe`` leaves every score
as it is, so the pairs are rotated where they stand.

The weights as served are 6.85 GB at the benchmark's size and the serving
runner holds them beside their float32 copy: :func:`to_float32` widens the
small leaves and leaves the large matrices (embedding, head, the dense
feed-forward, the experts) in the bfloat16 they are served in, and
:func:`logits_at` widens those where it uses them, a layer at a time: the
same numbers, since widening is exact.

Faults for the readings, never for a run: ``fault="no_rope"`` applies no
rotary to the ``pe`` parts (what ``layers/mla.py`` computed before it had a
rotary).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import common as C

F32 = jnp.float32
QUERY_BLOCK = 512        # query rows whose scores are held at once
WIDEN_BELOW = 8_000_000  # to_float32 widens leaves of fewer elements


def dims(cfg: dict) -> dict:
    return {"d": int(cfg["hidden_size"]), "layers": int(cfg["num_hidden_layers"]),
            "h": int(cfg["num_attention_heads"]), "r": int(cfg["kv_lora_rank"]),
            "nope": int(cfg["qk_nope_head_dim"]),
            "rope": int(cfg["qk_rope_head_dim"]), "vd": int(cfg["v_head_dim"]),
            "dense": int(cfg["intermediate_size"]),
            "width": int(cfg["moe_intermediate_size"]),
            "experts": int(cfg["n_routed_experts"]),
            "held": len(held_experts(cfg)),
            "top_k": int(cfg["num_experts_per_tok"]),
            "shared": int(cfg["n_shared_experts"])
            * int(cfg["moe_intermediate_size"]),
            "first_dense": int(cfg["first_k_dense_replace"]),
            "vocab": int(cfg["vocab_size"])}


def held_experts(cfg: dict) -> list:
    return list(cfg.get("held_experts", range(int(cfg["n_routed_experts"]))))


def shapes(cfg: dict) -> dict:
    """Leaf -> (shape, kind), named by the dotted paths of the program's
    tree: ``w`` a matrix (``dtype``), ``g`` a norm gain (float32)."""
    m = dims(cfg)
    d, qk = m["d"], m["nope"] + m["rope"]
    out = {"embed.weight": ((m["vocab"], d), "w"),
           "norm_f.scale": ((d,), "g"), "lm_head": ((d, m["vocab"]), "w")}
    for l in range(m["layers"]):
        p = f"blocks.{l}."
        out.update({
            p + "norm1.scale": ((d,), "g"), p + "norm2.scale": ((d,), "g"),
            p + "attn.wq": ((d, m["h"] * qk), "w"),
            p + "attn.wkva": ((d, m["r"] + m["rope"]), "w"),
            p + "attn.kv_norm.scale": ((m["r"],), "g"),
            p + "attn.wkvb": ((m["r"], m["h"] * (m["nope"] + m["vd"])), "w"),
            p + "attn.wo": ((m["h"] * m["vd"], d), "w")})
        if l < m["first_dense"]:
            ffn = {"ffn.": m["dense"]}
        else:
            out[p + "ffn.router.w"] = ((d, m["experts"]), "w")
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
    ``initializer_range``) in float32.  Traceable."""
    key = C.seed_key(seed) if not isinstance(seed, jax.Array) else seed
    std, dtype = float(cfg["initializer_range"]), jnp.dtype(cfg["dtype"])
    return {name: (C.normal(key, name, shape, std, dtype) if kind == "w"
                   else C.normal(key, name, shape, std, F32, mean=1.0))
            for name, (shape, kind) in shapes(cfg).items()}


def to_float32(weights: dict) -> dict:
    """The small leaves in float32; the large ones as they are served
    (module docstring), widened where :func:`logits_at` uses them."""
    return {n: (a.astype(F32) if a.size < WIDEN_BELOW else a)
            for n, a in weights.items()}


# -- rotary ------------------------------------------------------------------

def yarn(cfg: dict) -> dict:
    """``inv_freq`` (rope / 2), the amplitude of cos and sin, and the
    softmax scale ``sigma``, from the configuration's keys."""
    d, theta = int(cfg["qk_rope_head_dim"]), float(cfg["rope_theta"])
    qk = int(cfg["qk_nope_head_dim"]) + d
    f = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    rs = cfg.get("rope_scaling")
    if not rs:
        return {"inv_freq": f.astype(np.float32), "amplitude": 1.0,
                "sigma": qk ** -0.5}
    factor, length = float(rs["factor"]), float(
        rs["original_max_position_embeddings"])

    def pair_at(turns):
        return d * math.log(length / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(pair_at(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(pair_at(float(rs["beta_slow"]))), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    m = lambda s: 0.1 * s * math.log(factor) + 1.0 if factor > 1 else 1.0
    return {"inv_freq": (f / factor * ramp + f * (1 - ramp)).astype(
                np.float32),
            "amplitude": m(float(rs["mscale"])) / m(float(
                rs["mscale_all_dim"])),
            "sigma": qk ** -0.5 * m(float(rs["mscale_all_dim"])) ** 2}


def rope(x, positions, y: dict):
    """``x [t, ..., rope]``, the pairs (2i, 2i + 1) rotated by
    ``positions[t] inv_freq_i``."""
    angle = positions.astype(F32).reshape((-1,) + (1,) * (x.ndim - 2) + (1,)) \
        * jnp.asarray(y["inv_freq"])
    cos, sin = jnp.cos(angle) * y["amplitude"], jnp.sin(angle) * y["amplitude"]
    pairs = x.reshape(x.shape[:-1] + (-1, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


# -- the layers ---------------------------------------------------------------

def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * gain


def swiglu(x, w_gate, w_up, w_down, mm):
    return mm(jax.nn.silu(mm(x, w_gate.astype(F32))) * mm(
        x, w_up.astype(F32)), w_down.astype(F32))


def mla(x, w, p, cfg, mm, ein, fault):
    m, y = dims(cfg), yarn(cfg)
    t, h, nope, vd, r = x.shape[0], m["h"], m["nope"], m["vd"], m["r"]
    eps = float(cfg["rms_norm_eps"])
    q = mm(x, w[p + "attn.wq"]).reshape(t, h, nope + m["rope"])
    kva = mm(x, w[p + "attn.wkva"])
    c = rms_norm(kva[:, :r], w[p + "attn.kv_norm.scale"], eps)
    q_nope, q_pe, k_pe = q[..., :nope], q[..., nope:], kva[:, r:]
    if fault != "no_rope":
        pos = jnp.arange(t)
        q_pe, k_pe = rope(q_pe, pos, y), rope(k_pe, pos, y)
    kvb = mm(c, w[p + "attn.wkvb"]).reshape(t, h, nope + vd)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    block = min(QUERY_BLOCK, t)
    pad = -t % block

    def rows(first):
        """The outputs of the query rows ``first`` to ``first + block``."""
        qn = jax.lax.dynamic_slice_in_dim(q_nope, first, block)
        qp = jax.lax.dynamic_slice_in_dim(q_pe, first, block)
        sc = (ein("qhe,khe->hqk", qn, k_nope)
              + ein("qhe,ke->hqk", qp, k_pe)) * y["sigma"]
        seen = (first + jnp.arange(block))[:, None] >= jnp.arange(t)[None, :]
        prob = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        return ein("hqk,khe->qhe", prob, v)

    if pad:     # the last block starts early and its head is dropped
        firsts = jnp.minimum(jnp.arange(0, t + pad, block), t - block)
        o = jax.lax.map(rows, firsts)
        o = jnp.concatenate([o[:-1].reshape(-1, h, vd), o[-1][pad:]])
    else:
        o = jax.lax.map(rows, jnp.arange(0, t, block)).reshape(t, h, vd)
    return mm(o.reshape(t, h * vd), w[p + "attn.wo"])


def moe(x, w, p, cfg, mm):
    m = dims(cfg)
    score = jax.nn.softmax(mm(x, w[p + "ffn.router.w"]), axis=-1)
    top, chosen = jax.lax.top_k(score, m["top_k"])
    scale = float(cfg["routed_scaling_factor"])

    def one(y, e):
        """Adds held expert ``e``'s share: computed for every token,
        weighted by the token's score where the token chose it."""
        idx, wg, wu, wd = e
        gate = jnp.sum(jnp.where(chosen == idx, top, 0.0), axis=-1) * scale
        return y + gate[:, None] * swiglu(x, wg, wu, wd, mm), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        jnp.asarray(held_experts(cfg), jnp.int32),
        w[p + "ffn.experts.w_gate"], w[p + "ffn.experts.w_up"],
        w[p + "ffn.experts.w_down"]))
    q = p + "ffn.shared."
    return y + swiglu(x, w[q + "w_gate"], w[q + "w_up"], w[q + "w_down"], mm)


def hidden_states(w, tokens, *, cfg, precision="float32", fault=None):
    """float32 hidden states ``(len(tokens), hidden)`` before the last norm,
    of one sequence."""
    mm = functools.partial(C.mm, precision=precision)
    ein = functools.partial(C.einsum, precision=precision)
    m, eps = dims(cfg), float(cfg["rms_norm_eps"])
    x = w["embed.weight"][tokens].astype(F32)
    for l in range(m["layers"]):
        p = f"blocks.{l}."
        x = x + mla(rms_norm(x, w[p + "norm1.scale"], eps), w, p, cfg, mm,
                    ein, fault)
        a = rms_norm(x, w[p + "norm2.scale"], eps)
        if l < m["first_dense"]:
            x = x + swiglu(a, w[p + "ffn.w_gate"], w[p + "ffn.w_up"],
                           w[p + "ffn.w_down"], mm)
        else:
            x = x + moe(a, w, p, cfg, mm)
    return x


def logits_at(w, tokens, positions, *, cfg, precision="float32", fault=None):
    """float32 logits ``(len(positions), vocab)`` of the next token after
    each of ``positions`` of the sequence ``tokens`` (one sequence; what
    lies behind a position cannot reach it, so padding at the end is
    harmless)."""
    x = hidden_states(w, tokens, cfg=cfg, precision=precision, fault=fault)
    x = rms_norm(x[positions], w["norm_f.scale"], float(cfg["rms_norm_eps"]))
    return C.mm(x, w["lm_head"].astype(F32), precision=precision)
