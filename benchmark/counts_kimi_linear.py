"""Operations and bytes that Kimi-Linear's algorithms need, from shapes
alone: the family's counts beside ``benchmark.counts``, under its rules (a
multiply-add is two operations; recomputed work is not counted in a model's
count; a kernel's count is what its algorithm needs for one call).

The model, a token forward:

- 2 x the parameters that take part in a matmul and that the token touches:
  every KDA mixer (W_q, W_k, W_v, W_o, both low-rank gates, W_b), the MLA
  mixer (W_q, W_kva, W_kvb, W_o), the dense feed-forward, and in every
  expert layer the router at its published width, the shared experts, and
  ``top_k x held / published`` routed experts (what uniform routing sends
  to the experts held here: 8 x 8 / 256 = a quarter of one expert), and the
  head over the sliced vocabulary; the convolutions' taps, 2 a tap a
  channel;
- KDA's recurrence, 7 d_k d_v a head a layer (the decay of the state, the
  read ``k^T S``, the rank-one update and the output ``q^T S``);
- MLA's causal scores, ``2 x (seq / 2) x heads x (qk + v)``.

Training is three times the forward (backward is twice forward).

The kernels, a call:

- chunked KDA as a whole (``kda_chunked_call``): the scan across chunks
  below and, before it, what a chunk of C tokens a head needs within
  itself: the two decayed Gram matrices A (below the diagonal) and P (with
  it), ``C^2 d_k`` each; the inverse of the unit lower triangular
  ``I + A`` by substitution, ``C^3 / 3``; ``W_k = T (b K)`` and
  ``W_v = T (b V)``, the causal halves of ``C x C x d_k`` and
  ``C x C x d_v``.  Backward twice that.  Bytes: what the layer hands the
  algorithm and takes from it, once: q, k, v and the output in the
  model's type, the decay's log and beta in float32, forward; those, the
  output's gradient and the five gradients, backward.  Nothing between the
  two stages is counted: an implementation may keep it on the chip;
- the chunked KDA scan across chunks (what carries the state, given the
  within-chunk quantities): a chunk of C tokens a head needs, forward,
  ``U = W_v - W_k S``, ``O = Q_g S + P U`` and ``S' = g S + K_d^T U``:
  three products of C x d_k x d_v and the causal half of C x C x d_v;
  backward, ``U`` again, ``dU``, ``dP``, ``dQ_g``, ``dK_d``, ``dW_k`` and
  the state's gradient: seven products of C x d_k x d_v and two causal
  halves of C x C x d_v.  Bytes: the six inputs and the output once
  forward; those, ``dO`` and the six gradients once backward.  The states
  at the chunk boundaries, which an implementation may store or recompute,
  are not counted;
- attention at two widths (q and k of ``qk``, v and the output of ``v``):
  forward two products over the causal half of the scores, backward five
  (the scores again, dV, dP, dQ, dK);
- the grouped expert product over ``rows`` (token, choice) pairs on held
  experts: gate and up (``rows x d x 2f``) and down (``rows x f x d``)
  forward; backward twice that (the rows' gradient and the weights').
  Bytes: rows in and out, and the weights of the experts that got a row
  once (an expert without rows is never read), forward; those, the
  output's gradient and the weights' gradient, backward.
"""

from __future__ import annotations

from benchmark.reference.kimi_linear import dims, layer_kinds


def matmul_params(cfg: dict) -> dict:
    """Parameters that a token's forward multiplies, by part."""
    m = dims(cfg)
    d, hk = m["d"], m["kh"] * m["kd"]
    kda = 4 * d * hk + 2 * (d * m["rank"] + m["rank"] * hk) + d * m["kh"]
    h = m["h"]
    mla = (d * h * (m["nope"] + m["rope"]) + d * (m["lat"] + m["rope"])
           + m["lat"] * h * (m["nope"] + m["vd"]) + h * m["vd"] * d)
    expert = 3 * d * m["width"]
    share = m["top"] * len(m["held"]) / m["routed"]
    kinds = layer_kinds(cfg)
    n_kda = sum(mix == "kda" for mix, _ in kinds)
    n_moe = sum(ffn == "moe" for _, ffn in kinds)
    return {
        "kda": n_kda * kda, "mla": (len(kinds) - n_kda) * mla,
        "dense_ffn": (len(kinds) - n_moe) * 3 * d * m["inner"],
        "router": n_moe * d * m["routed"],
        "shared": n_moe * m["shared"] * expert,
        "routed": n_moe * share * expert, "head": d * m["v"],
    }


def forward_flops_per_token(cfg: dict, seq: int) -> dict:
    """The model's count a token forward, by part."""
    m = dims(cfg)
    kinds = layer_kinds(cfg)
    n_kda = sum(mix == "kda" for mix, _ in kinds)
    out = {k: 2.0 * v for k, v in matmul_params(cfg).items()}
    out["kda"] += n_kda * (7.0 * m["kd"] * m["kd"] * m["kh"]
                           + 2.0 * m["conv"] * 3 * m["kh"] * m["kd"])
    out["mla"] += (len(kinds) - n_kda) * 2.0 * (seq / 2) * m["h"] * (
        m["nope"] + m["rope"] + m["vd"])
    return out


def train_flops_per_token(cfg: dict, mix: dict) -> float:
    return 3.0 * sum(forward_flops_per_token(cfg, int(mix["seq"])).values())


def kda_scan_call(*, batch: int, heads: int, seq: int, dk: int, dv: int,
                  backward: bool, chunk: int = 64,
                  dtype_bytes: int = 2) -> tuple:
    """(operations, bytes) of one chunked KDA scan across the chunks of
    ``batch`` sequences of ``seq`` tokens, at the published kernels' chunk
    of 64 tokens."""
    chunks = batch * heads * -(-seq // chunk)
    full, half = 2.0 * chunk * dk * dv, 1.0 * chunk * chunk * dv
    flops = chunks * ((7 * full + 2 * half) if backward
                      else (3 * full + half))
    inputs = (3 * chunk * dk + chunk * dv + chunk * chunk) * dtype_bytes \
        + dk * 4
    out = chunk * dv * dtype_bytes
    nbytes = chunks * ((2 * inputs + 2 * out) if backward
                       else (inputs + out))
    return flops, nbytes


def kda_chunked_call(*, batch: int, heads: int, seq: int, dk: int, dv: int,
                     backward: bool, chunk: int = 64,
                     dtype_bytes: int = 2) -> tuple:
    """(operations, bytes) of chunked KDA from q, k, v, the decay's log and
    beta to the output: both stages, within chunks and across them."""
    chunks = batch * heads * -(-seq // chunk)
    within = chunks * (3.0 * chunk * chunk * dk + 1.0 * chunk * chunk * dv
                       + chunk ** 3 / 3.0)
    scan, _ = kda_scan_call(batch=batch, heads=heads, seq=seq, dk=dk, dv=dv,
                            backward=backward, chunk=chunk)
    flops = scan + (2 * within if backward else within)
    qkv = (2 * chunk * dk + chunk * dv) * dtype_bytes
    gates = (chunk * dk + chunk) * 4
    out = chunk * dv * dtype_bytes
    nbytes = chunks * ((2 * (qkv + gates) + out) if backward
                       else (qkv + gates + out))
    return flops, nbytes


def attention_call(*, batch: int, heads: int, seq: int, qk_dim: int,
                   v_dim: int, causal: bool, backward: bool,
                   dtype_bytes: int = 2) -> tuple:
    """(operations, bytes) of one attention call whose score heads are
    ``qk_dim`` wide and whose value heads ``v_dim``: ``counts.flash_call``
    at two widths."""
    scores = batch * heads * seq * seq * (0.5 if causal else 1.0)
    flops = 2.0 * scores * ((3 * qk_dim + 2 * v_dim) if backward
                            else (qk_dim + v_dim))
    qk = batch * heads * seq * qk_dim * dtype_bytes
    v = batch * heads * seq * v_dim * dtype_bytes
    nbytes = (4 * qk + 4 * v) if backward else (2 * qk + 2 * v)
    return flops, nbytes


def grouped_experts_call(*, rows: float, experts_hit: float, hidden: int,
                         width: int, backward: bool,
                         dtype_bytes: int = 2) -> tuple:
    """(operations, bytes) of the held experts' SwiGLU over ``rows`` (token,
    choice) pairs, sorted by expert, of which ``experts_hit`` got any."""
    flops = 6.0 * rows * hidden * width * (2 if backward else 1)
    weights = experts_hit * 3 * hidden * width * dtype_bytes
    acts = rows * (2 * hidden + 3 * width) * dtype_bytes
    nbytes = (2 * weights + 2 * acts) if backward else (weights + acts)
    return flops, nbytes
