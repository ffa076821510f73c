"""A closed loop whose requests are of several classes: short and long
prompts in one queue.  ``generator`` of a cell's file:
``benchmark.traffic_mixed:closed_mixed``.

``params``: ``clients``, ``shape_seed`` and ``classes``, each ``{"name",
"share", "prompt_tokens", "answer_tokens"}`` with the two distributions as
``benchmark.traffic.draw`` reads them; the shares add up to 1.  A top-level
``prompt_tokens`` ``{"min", "max"}`` spans every class: the serving
runner's ``warm_up`` reads it to pick the buckets it warms.

As in ``benchmark.traffic.closed``: ``CLOSED_SIZES`` sizes are drawn from
the mix's ``shape_seed`` (first each draw's class, then every class's
lengths, so one class's distribution moves no other's draws) and used in
turn, every ``--seed`` offers the same sizes in the same order, and
``--seed`` draws the token ids."""

from __future__ import annotations

import numpy as np

from benchmark.traffic import CLOSED_SIZES, Offered, Request, draw


def mixed_sizes(params: dict, n: int = CLOSED_SIZES) -> tuple:
    """(class index, prompt length, answer length) of ``n`` draws."""
    classes = params["classes"]
    shares = np.asarray([float(c["share"]) for c in classes])
    if abs(shares.sum() - 1.0) > 1e-9:
        raise ValueError(f"the classes' shares add up to {shares.sum()}")
    lo, hi = params["prompt_tokens"]["min"], params["prompt_tokens"]["max"]
    for c in classes:
        p = c["prompt_tokens"]
        if p["min"] < lo or p["max"] > hi:
            raise ValueError(
                f"class {c['name']!r} draws prompts of {p['min']} to "
                f"{p['max']} tokens outside the mix's {lo} to {hi}")
    rng = np.random.default_rng(int(params["shape_seed"]))
    kind = rng.choice(len(classes), size=n, p=shares)
    prompt, answer = np.zeros(n, np.int64), np.zeros(n, np.int64)
    for i, c in enumerate(classes):
        p, a = draw(c["prompt_tokens"], rng, n), draw(c["answer_tokens"],
                                                      rng, n)
        prompt, answer = (np.where(kind == i, p, prompt),
                          np.where(kind == i, a, answer))
    return kind, prompt, answer


def closed_mixed(params: dict, seed: int, vocab: int,
                 horizon_s: float) -> Offered:
    """A closed loop of ``clients``, each sending its next request when its
    last has finished; every request of one of the mix's classes."""
    _, prompt_len, answer_len = mixed_sizes(params)
    ids = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 0x5EED])
    clients = int(params["clients"])
    count = [clients]

    def make(i: int, client: int) -> Request:
        j = i % len(prompt_len)
        prompt = ids.integers(0, int(vocab), int(prompt_len[j]),
                              dtype=np.int64)
        return Request(i, None, client, prompt, int(answer_len[j]))

    def after(finished: Request) -> Request:
        count[0] += 1
        return make(count[0] - 1, finished.client)

    return Offered(timed=[], start=[make(c, c) for c in range(clients)],
                   after=after)
