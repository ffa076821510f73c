"""Operations and bytes that the algorithms need, from shapes alone.

These are the benchmark's own counts: they do not depend on how the program
computes the work.  A multiply-add is two operations.  Recomputed work is
not counted in a model's count (``mfu``); a kernel's count is what its
algorithm needs for one call, the flash backward's recomputation of the
scores included, since no implementation of that kernel's contract can
avoid it."""

from __future__ import annotations


def _block_matmul_params(hidden: int, inner: int) -> int:
    """Weights of one transformer block that take part in a matmul."""
    return 4 * hidden * hidden + 2 * hidden * inner


def encoder_train_flops_per_token(*, hidden: int, inner: int, layers: int,
                                  seq: int, vocab: int,
                                  mask_rate: float) -> float:
    """Forward plus backward of a BERT-style encoder with an MLM head, a
    token: the blocks' matmuls, attention over ``seq`` keys, the MLM
    transform and decoder over the masked positions only (the head has
    nothing to learn from an unmasked one), the pooler and NSP once a
    sequence.  Backward is twice forward."""
    blocks = layers * (2 * _block_matmul_params(hidden, inner)
                       + 4 * seq * hidden)
    head = mask_rate * (2 * hidden * hidden + 2 * hidden * vocab)
    pooled = (2 * hidden * hidden + 4 * hidden) / seq
    return 3.0 * (blocks + head + pooled)


def decoder_forward_flops(*, hidden: int, inner: int, layers: int,
                          vocab: int, new_tokens: int, context: float,
                          heads_out: int) -> float:
    """Forward of a GPT-style decoder over ``new_tokens`` new positions
    whose mean number of visible keys is ``context``; the LM head runs at
    ``heads_out`` positions (one for a prefill, one a decode token)."""
    blocks = new_tokens * layers * (2 * _block_matmul_params(hidden, inner)
                                    + 4 * context * hidden)
    return blocks + heads_out * 2 * hidden * vocab


def decoder_train_flops_per_token(*, hidden: int, inner: int, layers: int,
                                  seq: int, vocab: int) -> float:
    """Forward plus backward of a GPT-style decoder, a token: causal
    attention sees half the sequence on average; the head runs at every
    position and there is no MLM transform."""
    fwd = decoder_forward_flops(hidden=hidden, inner=inner, layers=layers,
                                vocab=vocab, new_tokens=1,
                                context=seq / 2, heads_out=1)
    return 3.0 * fwd


def flash_call(*, batch: int, heads: int, seq_q: int, seq_k: int,
               head_dim: int, causal: bool, backward: bool,
               dtype_bytes: int = 2) -> tuple:
    """(operations, bytes) of one flash attention call.  Forward: two
    matmuls over the score matrix.  Backward: five (the scores again, dV,
    dP, dQ, dK).  Causal halves the scores.  Bytes: q, k, v and the output
    once forward; those, dO and the three gradients once backward."""
    scores = batch * heads * seq_q * seq_k * (0.5 if causal else 1.0)
    flops = (5 if backward else 2) * 2 * scores * head_dim
    q = batch * heads * seq_q * head_dim * dtype_bytes
    k = batch * heads * seq_k * head_dim * dtype_bytes
    nbytes = (3 * q + 4 * k) if backward else (2 * q + 2 * k)
    return flops, nbytes


def paged_decode_call(*, context_tokens: float, heads: int, head_dim: int,
                      dtype_bytes: int = 2) -> tuple:
    """(operations, bytes) of one paged decode attention call of one layer
    in which the slots' live caches hold ``context_tokens`` tokens in all:
    one query a slot, so both products are matrix-vector work, and the
    bytes are the K and V of the live tokens."""
    flops = 4.0 * context_tokens * heads * head_dim
    nbytes = 2.0 * context_tokens * heads * head_dim * dtype_bytes
    return flops, nbytes


def kv_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    """Bytes of K and V that one cached token holds over all layers."""
    d = dims(cfg)
    return 2 * d["layers"] * d["hidden"] * dtype_bytes


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """The least time the chip could take, and which peak bounds it."""
    t_c = flops / peaks["flops_bf16"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "bandwidth")


def dims(cfg: dict) -> dict:
    """The sizes that the counts need, from a configuration file in either
    naming (BERT's or GPT-2's)."""
    pick = lambda *names: next(int(cfg[n]) for n in names if n in cfg)
    return {"hidden": pick("hidden_size", "n_embd"),
            "inner": pick("intermediate_size", "n_inner"),
            "layers": pick("num_hidden_layers", "n_layer"),
            "heads": pick("num_attention_heads", "n_head"),
            "vocab": pick("vocab_size")}


def train_flops_per_token(cfg: dict, mix: dict) -> float:
    """The model's count for a training cell: an encoder with an MLM head
    where the mix masks (``mask_rate``), else a decoder."""
    d = dims(cfg)
    kw = dict(hidden=d["hidden"], inner=d["inner"], layers=d["layers"],
              seq=int(mix["seq"]), vocab=d["vocab"])
    if "mask_rate" in mix:
        return encoder_train_flops_per_token(
            mask_rate=float(mix["mask_rate"]), **kw)
    return decoder_train_flops_per_token(**kw)


def serve_flops(cfg: dict, prefills, decode_contexts) -> float:
    """The model's count for served tokens: ``prefills`` are prompt
    lengths (each yields one token), ``decode_contexts`` the number of
    visible keys of each decoded token."""
    d = dims(cfg)
    kw = dict(hidden=d["hidden"], inner=d["inner"], layers=d["layers"],
              vocab=d["vocab"])
    total = sum(decoder_forward_flops(new_tokens=p, context=(p + 1) / 2,
                                      heads_out=1, **kw) for p in prefills)
    n = len(decode_contexts)
    if n:
        total += decoder_forward_flops(
            new_tokens=n, context=float(sum(decode_contexts)) / n,
            heads_out=n, **kw)
    return total
