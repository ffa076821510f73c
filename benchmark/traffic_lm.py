"""Next-token batches for causal language models: a training generator
beside ``benchmark.traffic:mlm_batch``, called the same way."""

from __future__ import annotations

import numpy as np


def next_token_batch(params: dict, seed: int, step: int, vocab: int) -> dict:
    """``batch`` sequences of ``seq`` + 1 ids drawn uniformly from the
    vocabulary: ``input_ids`` the first ``seq`` of each, ``labels`` the
    token after each position.  A pure function of (seed, step) whose rows
    all differ."""
    b, s = int(params["batch"]), int(params["seq"])
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 int(step), 0x1A7E])
    ids = rng.integers(0, vocab, (b, s + 1), dtype=np.int64).astype(np.int32)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
