"""Operations and bytes that AFMoE (Trinity) needs, from shapes alone: the
model's count for served tokens and its two attention kernels' calls (the
held experts' grouped products are counted as the other expert families'
are, ``counts_kimi_linear.grouped_experts_call``).  As
in ``benchmark.counts``: a multiply-add is two operations, the counts do not
depend on how the program computes the work, and recomputed work is not
counted.

The model, a token, forward: every weight that takes part in a matmul for
that token, twice.  An attention layer: ``W_q``, ``W_g`` and ``W_o`` hidden x
(heads x head_dim) each, ``W_k`` and ``W_v`` hidden x (kv_heads x head_dim)
each.  The dense feed-forward 3 x hidden x ``intermediate_size``; an expert
layer the router hidden x ``num_experts_published``, the shared expert's 3 x
hidden x ``moe_intermediate_size``, and 3 x hidden x ``moe_intermediate_size``
for each of the token's chosen experts THAT THIS CHIP HOLDS (:func:`
pair_flops`: the pairs come from the programs' routing counters, since what
the experts held elsewhere would compute is no work of this chip); the head
hidden x vocab where a token is sampled.  Attention by the model's
definition, whatever computes it: a token that sees ``seen`` keys needs 2 x
seen x heads x head_dim for its scores and as much for its output a layer,
with ``seen(t) = min(t + 1, sliding_window)`` on a sliding layer and ``t +
1`` on a full one."""

from __future__ import annotations

from benchmark.counts_kimi_linear import grouped_experts_call  # noqa: F401
from benchmark.reference.afmoe import SLIDING, dims, layer_types


def layers_by_kind(cfg: dict) -> tuple:
    """(sliding layers, full layers)."""
    n = sum(1 for t in layer_types(cfg) if t == SLIDING)
    return n, len(layer_types(cfg)) - n


def matmul_params(cfg: dict) -> tuple:
    """(weights a token meets in the blocks outside the routed experts,
    weights of the head)."""
    m = dims(cfg)
    d = m["d"]
    attn = 3 * d * m["h"] * m["hd"] + 2 * d * m["kh"] * m["hd"]
    n_dense = min(m["first_dense"], m["layers"])
    moe = d * m["experts"] + 3 * d * m["shared"]
    return (m["layers"] * attn + n_dense * 3 * d * m["dense"]
            + (m["layers"] - n_dense) * moe), d * m["vocab"]


def pair_flops(cfg: dict) -> float:
    """One (token, held expert) pair: the expert's three matrices."""
    m = dims(cfg)
    return 2.0 * 3 * m["d"] * m["width"]


def seen_pairs(n: int, window=None) -> float:
    """(query, key) pairs that causal attention over ``n`` tokens sees:
    ``sum over t < n of min(t + 1, window)``."""
    if window is None or n <= window:
        return n * (n + 1) / 2.0
    return window * (window + 1) / 2.0 + (n - window) * float(window)


def attention_flops(cfg: dict, pairs_sliding: float, pairs_full: float):
    """Attention over so many (query, key) pairs a sliding layer and so
    many a full layer, all layers."""
    m = dims(cfg)
    ns, nf = layers_by_kind(cfg)
    return 4.0 * m["h"] * m["hd"] * (ns * pairs_sliding + nf * pairs_full)


def serve_flops(cfg: dict, prefills, contexts, held_pairs: float) -> float:
    """The model's count for served tokens: ``prefills`` are prompt lengths
    (each yields one token: the head runs once), ``contexts`` the cached
    tokens each decoded token attended over (itself included), and
    ``held_pairs`` the (token, chosen expert) pairs of all of them that fell
    on experts held here."""
    m = dims(cfg)
    blocks, head = matmul_params(cfg)
    w = m["window"]
    total = held_pairs * pair_flops(cfg)
    total += len(contexts) * 2.0 * (blocks + head) + attention_flops(
        cfg, float(sum(min(c, w) for c in contexts)), float(sum(contexts)))
    for p in prefills:
        total += p * 2.0 * blocks + 2.0 * head + attention_flops(
            cfg, seen_pairs(p, w), seen_pairs(p))
    return total


def gqa_paged_decode_call(*, seen_tokens: float, heads: int, kv_heads: int,
                          head_dim: int, dtype_bytes: int = 2) -> tuple:
    """(operations, bytes) of paged decode calls with grouped heads whose
    rows see ``seen_tokens`` cached tokens in all (a row of a call with a
    window sees ``min(context, window)``): one query a head a row against
    each seen key and as much for the values; the bytes are the seen
    tokens' K and V at ``kv_heads`` heads, once: a KV head's page read once
    for all of its query heads, a page outside the window not at all."""
    return (4.0 * seen_tokens * heads * head_dim,
            2.0 * seen_tokens * kv_heads * head_dim * dtype_bytes)


def window_flash_call(*, tokens: int, window, heads: int, kv_heads: int,
                      head_dim: int, dtype_bytes: int = 2) -> tuple:
    """(operations, bytes) of one causal flash forward over a prompt of
    ``tokens`` with grouped heads and a window (``None``: none): the two
    products over the seen pairs only; q and the output at ``heads`` heads
    and k and v at ``kv_heads``, once each."""
    return (4.0 * seen_pairs(tokens, window) * heads * head_dim,
            2.0 * tokens * (heads + kv_heads) * head_dim * dtype_bytes)
