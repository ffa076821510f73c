"""Generators of inputs.  A cell's file names its generator as
``module:function`` and holds that generator's parameters under
``traffic``; a later PR that needs another kind of traffic brings a
generator as a file of its own and edits nothing here.

A serving generator is called as ``generator(params, seed, vocab,
horizon_s)`` and returns an :class:`Offered`: the requests due at fixed
instants, those sent at the window's first instant, and what follows a
request that has finished.  The sizes and the arrival times come from the
mix's own ``shape_seed``, so every ``--seed`` offers the same work at the
same instants; ``--seed`` draws the token ids (and the weights, elsewhere).

A training generator is called as ``generator(params, seed, step, vocab)``
and returns one batch as numpy arrays: a pure function of (seed, step)
whose rows all differ.

Nothing here knows a cell by name."""

from __future__ import annotations

import dataclasses
from typing import Callable

import numpy as np

CLOSED_SIZES = 4096      # sizes drawn for a closed loop, used in turn


def draw(spec: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    """``n`` whole numbers from the distribution ``spec``, clipped to its
    ``min`` and ``max``."""
    dist = spec["dist"]
    if dist == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    elif dist == "uniform":
        x = rng.uniform(spec["min"], spec["max"] + 1, n)
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    return np.clip(np.floor(x), spec["min"], spec["max"]).astype(np.int64)


@dataclasses.dataclass
class Request:
    index: int
    due_s: float | None       # from the window's start; None: due when sent
    client: int
    prompt: np.ndarray
    max_new: int


@dataclasses.dataclass
class Offered:
    """What a serving generator hands to the runner."""
    timed: list               # requests with ``due_s``, in due order
    start: list               # requests sent at the window's first instant
    after: Callable           # finished request -> the next one, or None


class _Sizes:
    """``n`` prompt and answer lengths from the mix's ``shape_seed``, and
    the token ids of each request from ``--seed``."""

    def __init__(self, params: dict, shape_rng, n: int, seed: int,
                 vocab: int):
        self.prompt_len = draw(params["prompt_tokens"], shape_rng, n)
        self.answer_len = draw(params["answer_tokens"], shape_rng, n)
        self.vocab, self.n = int(vocab), n
        self.rng = np.random.default_rng([int(seed) & 0xFFFFFFFF,
                                          int(seed) >> 32, 0x5EED])

    def make(self, i: int, due_s, client: int = 0) -> Request:
        j = i % self.n
        prompt = self.rng.integers(0, self.vocab, int(self.prompt_len[j]),
                                   dtype=np.int64)
        return Request(i, due_s, client, prompt, int(self.answer_len[j]))


def poisson(params: dict, seed: int, vocab: int, horizon_s: float) -> Offered:
    """An open loop: Poisson arrivals at ``rate_per_s`` over ``horizon_s``."""
    shape_rng = np.random.default_rng(int(params["shape_seed"]))
    rate = float(params["rate_per_s"])
    gaps = shape_rng.exponential(1.0 / rate, int(horizon_s * rate * 2 + 64))
    due = np.cumsum(gaps)
    due = due[due < horizon_s]
    sizes = _Sizes(params, shape_rng, len(due), seed, vocab)
    return Offered(timed=[sizes.make(i, float(t)) for i, t in enumerate(due)],
                   start=[], after=lambda finished: None)


def closed(params: dict, seed: int, vocab: int, horizon_s: float) -> Offered:
    """A closed loop of ``clients``: each sends its next request when its
    last has finished."""
    shape_rng = np.random.default_rng(int(params["shape_seed"]))
    sizes = _Sizes(params, shape_rng, CLOSED_SIZES, seed, vocab)
    clients = int(params["clients"])
    count = [clients]

    def after(finished: Request) -> Request:
        count[0] += 1
        return sizes.make(count[0] - 1, None, finished.client)

    return Offered(timed=[], start=[sizes.make(c, None, c)
                                    for c in range(clients)], after=after)


def mlm_batch(params: dict, seed: int, step: int, vocab: int) -> dict:
    """One pre-training batch as numpy arrays: ``input_ids`` (masked the
    BERT way: of the chosen positions 80% the mask token, 10% a random
    token, 10% unchanged), ``token_type`` (two segments, split at a random
    place), ``mlm_labels`` (the original id at a chosen position, -1
    elsewhere), ``nsp_labels``.  A pure function of (seed, step)."""
    b, s = int(params["batch"]), int(params["seq"])
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 int(step), 0xBA7C])
    ids = rng.integers(0, vocab, (b, s), dtype=np.int64)
    ids[:, 0] = int(params.get("cls_token", 101))
    k = int(round(float(params["mask_rate"]) * s))
    chosen = np.zeros((b, s), dtype=bool)
    pos = np.argsort(rng.random((b, s - 1)), axis=1)[:, :k] + 1
    np.put_along_axis(chosen, pos, True, axis=1)
    labels = np.where(chosen, ids, -1)
    how = rng.random((b, s))
    inputs = np.where(chosen & (how < 0.8), int(params["mask_token"]), ids)
    inputs = np.where(chosen & (how >= 0.8) & (how < 0.9),
                      rng.integers(0, vocab, (b, s)), inputs)
    split = rng.integers(s // 4, 3 * s // 4, (b, 1))
    token_type = (np.arange(s)[None, :] >= split).astype(np.int32)
    return {"input_ids": inputs.astype(np.int32), "token_type": token_type,
            "mlm_labels": labels.astype(np.int32),
            "nsp_labels": rng.integers(0, 2, (b,)).astype(np.int32)}
