"""Operations and bytes that DeepSeek-V2 needs, from shapes alone: the
model's count for served tokens and its two serving kernels' calls.  As in
``benchmark.counts``: a multiply-add is two operations, the counts do not
depend on how the program computes the work, and recomputed work is not
counted.

The model, a token, forward (``matmul_params``): every weight that takes
part in a matmul for that token, twice.  An MLA layer: ``W_q`` hidden x
heads x (nope + rope), ``W_kva`` hidden x (kv_lora_rank + rope), ``W_kvb``
kv_lora_rank x heads x (nope + v) and ``W_o`` heads x v x hidden.  The dense
feed-forward 3 x hidden x ``intermediate_size``; an expert layer the router
hidden x ``n_routed_experts``, ``num_experts_per_tok`` experts of 3 x hidden
x ``moe_intermediate_size`` and the shared experts' 3 x hidden x
(``n_shared_experts`` x ``moe_intermediate_size``); the head hidden x vocab
where a token is sampled.  Attention by the model's definition, whatever
form computes it: a token that sees ``context`` keys needs 2 x context x
heads x (nope + rope) for its scores and 2 x context x heads x v for its
output a layer (the absorbed form does more arithmetic for the same
result, 576 and 512 a head where this counts 192 and 128; that surplus is
the kernel's and is counted in ``mla_decode_call``)."""

from __future__ import annotations

from benchmark.counts_kimi_linear import grouped_experts_call  # noqa: F401
from benchmark.reference.deepseek_v2 import dims


def matmul_params(cfg: dict) -> tuple:
    """(weights a token meets in the blocks, weights of the head)."""
    m = dims(cfg)
    d = m["d"]
    mla = (d * m["h"] * (m["nope"] + m["rope"]) + d * (m["r"] + m["rope"])
           + m["r"] * m["h"] * (m["nope"] + m["vd"]) + m["h"] * m["vd"] * d)
    dense = 3 * d * m["dense"]
    moe = (d * m["experts"] + m["top_k"] * 3 * d * m["width"]
           + 3 * d * m["shared"])
    n_dense = min(m["first_dense"], m["layers"])
    blocks = (m["layers"] * mla + n_dense * dense
              + (m["layers"] - n_dense) * moe)
    return blocks, d * m["vocab"]


def attention_flops(cfg: dict, context: float) -> float:
    """A token's attention over ``context`` keys, all layers."""
    m = dims(cfg)
    return 2.0 * context * m["h"] * (m["nope"] + m["rope"] + m["vd"]) \
        * m["layers"]


def serve_flops(cfg: dict, prefills, decoded: int = 0,
                decoded_context: float = 0.0) -> float:
    """The model's count for served tokens: ``prefills`` are prompt
    lengths (each yields one token: its positions see half the prompt on
    average, the head runs once); ``decoded`` tokens came from decode
    steps and attended over ``decoded_context`` cached tokens in all (the
    count is linear in the context, so the sum is what matters)."""
    blocks, head = matmul_params(cfg)
    total = decoded * 2.0 * (blocks + head) + attention_flops(
        cfg, float(decoded_context))
    for p in prefills:
        total += p * (2.0 * blocks + attention_flops(cfg, (p + 1) / 2.0)) \
            + 2.0 * head
    return total


def mla_decode_call(*, context_tokens: float, heads: int, width: int,
                    value_width: int, dtype_bytes: int = 2) -> tuple:
    """(operations, bytes) of one absorbed paged decode call of one layer
    whose rows' live caches hold ``context_tokens`` latents in all: a head's
    query of ``width`` against each latent and the weights' sum over its
    first ``value_width`` values; the bytes are the live latents, once (an
    implementation that reads a page for each product reads at most half
    of this roofline)."""
    return (2.0 * context_tokens * heads * (width + value_width),
            1.0 * context_tokens * width * dtype_bytes)


def kv_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    """Bytes one cached token holds over all layers: one latent a layer."""
    m = dims(cfg)
    return m["layers"] * (m["r"] + m["rope"]) * dtype_bytes
