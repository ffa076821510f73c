"""Serving cells: ``ServingEngine.submit`` on an engine started with
``start()``, offered on the real clock what the cell's generator hands
over: requests due at fixed instants, and requests that follow one that
has finished."""

from __future__ import annotations

import collections
import gc
import importlib
import queue
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import counts, harness

DONE_BAD = ("rejected", "failed", "expired", "evicted")


class Probe:
    """The benchmark's own spans and timestamps around the engine: one
    span a scheduler tick, one timestamp a token."""

    def __init__(self, engine, tracer):
        self.engine, self.tracer = engine, tracer
        self.ticks = []            # (start, end, tokens made)
        self.tokens = []           # (time, request id, index in its answer)
        self.count = {}
        self.finished = queue.SimpleQueue()
        self.traced = []           # indices into ticks, while tracing
        self.least_free = engine.pool.free_pages   # pages, over all ticks
        self._step = engine.step
        engine.step = self._tick
        engine.on_token = self._token
        engine.on_finish = self.finished.put

    def _token(self, rid, tok):
        k = self.count.get(rid, 0)
        self.count[rid] = k + 1
        self.tokens.append((time.perf_counter(), rid, k))

    def _tick(self):
        traced = self.tracer.active
        a = time.perf_counter()
        with self.tracer.span("bench.tick"):
            n = self._step()
        self.least_free = min(self.least_free, self.engine.pool.free_pages)
        if traced and self.tracer.active:
            self.traced.append(len(self.ticks))
        self.ticks.append((a, time.perf_counter(), n))
        return n


class Pauses:
    """The collector's pauses over 20 ms while it is listed in
    ``gc.callbacks``: (start, seconds, generation).  Every Python thread
    stands still in one, the engine's too."""

    def __init__(self):
        self.seen, self._t = [], None

    def __call__(self, phase, info):
        now = time.perf_counter()
        if phase == "start":
            self._t = now
        elif self._t is not None and now - self._t > 0.02:
            self.seen.append((self._t, now - self._t, info["generation"]))


def programs(engine) -> int:
    """Programs that the engine's jit sites have compiled so far, by its
    own report (``stats()["compile"]``)."""
    return sum(site["programs"] for site in
               engine.stats()["compile"].values())


def warm_up(engine, mix_params, vocab, seed):
    """One request a prefill bucket that the mix's prompts can fall in, two
    tokens each, so that prefill at that bucket, sampling and the decode
    program are all compiled before the window."""
    rng = np.random.default_rng(seed & 0xFFFFFFFF)
    lo, hi = mix_params["prompt_tokens"]["min"], \
        mix_params["prompt_tokens"]["max"]
    prev = 0
    for b in engine.batcher.prompt_buckets:
        if prev < hi and b >= lo:
            n = min(b, hi)
            h = engine.submit(rng.integers(0, vocab, n), 2)
            if not h.wait(1100) or h.status != "completed":
                raise RuntimeError(f"warm-up at bucket {b}: {h.status} "
                                   f"{h.error}")
        prev = b


def judged_rank(engine: dict) -> int:
    """The rank that a served token is held to: the reference's best for
    greedy tokens, its ``top_k``-th best where the engine samples from its
    ``top_k`` best."""
    return int(engine["top_k"]) if engine["sampling"] == "top_k" else 1


def served_gaps(cfg, seed, sample, *, pad_to, rank=1,
                precision="float32", control=None):
    """For each (prompt, served tokens) of ``sample``: the reference's
    logits at every served position, and by how much the served token's
    logit lies below the reference's ``rank``-th best there (nought where
    it does not).  With ``control`` (a lower precision) the token judged at
    each position is the one that the reference computed in that precision
    puts ``rank``-th: the last that a sampler of its best ``rank`` may
    serve.  Sequences are padded to ``pad_to`` tokens (the engine's
    ``max_seq_len``): one program for every request."""
    ref = importlib.import_module(f"benchmark.reference.{cfg['family']}")
    # two programs: the weights leave the first in the types they are
    # served in, so their rounding is real (inside one fusion the v5e
    # computes a round trip through bfloat16 in float32: it rounds nothing)
    served = jax.jit(lambda k: ref.init_weights(cfg, k))(ref.C.seed_key(seed))
    w = jax.jit(ref.to_float32)(served)
    del served

    @jax.jit
    def gaps_of(w, tok, pos, served):
        """One program for every request: the passes, the ranks and the
        gaps at the padded shape (an operation outside it would compile
        anew for every length of answer)."""
        full = ref.logits_at(w, tok, pos, cfg=cfg, precision=precision)
        judged = served if control is None else jax.lax.top_k(
            ref.logits_at(w, tok, pos, cfg=cfg, precision=control),
            rank)[1][:, -1]
        gap = jax.lax.top_k(full, rank)[0][:, -1] - jnp.take_along_axis(
            full, judged[:, None], axis=-1)[:, 0]
        return jnp.maximum(gap, 0.0)

    gaps = []
    for prompt, served in sample:
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        first = len(prompt) - 1            # the position that made served[0]
        tok = np.zeros(pad_to, np.int32)
        tok[:len(seq)] = seq
        pos = np.full(pad_to, len(seq) - 1, np.int32)
        pos[:len(served)] = np.arange(first, len(seq))
        tokens = np.zeros(pad_to, np.int32)
        tokens[:len(served)] = served
        gap = gaps_of(w, jnp.asarray(tok), jnp.asarray(pos),
                      jnp.asarray(tokens))
        gaps.append(np.asarray(gap)[:len(served)])
    del w
    return gaps


def pick_sample(finished, n, seed):
    """``n`` of the finished requests, drawn from the seed, the longest
    (prompt plus answer) among them."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -(len(finished[i][0]) +
                                   len(finished[i][1])))
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0xC4EC])
    rest = rng.permutation(order[1:])[:max(n - 1, 0)]
    return [finished[order[0]]] + [finished[i] for i in rest]


def set_up(cell, seed, tracer):
    """The engine built from the seed, probed, started and warmed up."""
    cfg, wl = cell.config, cell.workload
    adapter = importlib.import_module(f"benchmark.adapters.{cfg['family']}")
    system = adapter.System(cfg, wl["engine"], seed)
    probe = Probe(system.engine, tracer)
    system.engine.start()
    warm_up(system.engine, wl["traffic"], cfg["vocab_size"], seed)
    return system, probe


def live_tokens(stamps, plen, page, begin, close) -> float:
    """Tokens of K/V that the requests in flight hold, in whole pages,
    averaged over the window: a request holds its prompt from its first
    token on, one token more after each further one, nothing once its last
    token is out."""
    held = 0.0
    for rid, ts in stamps.items():
        for k, (a, b) in enumerate(zip(ts, ts[1:])):
            a, b = max(a, begin), min(b, close)
            if b > a:
                held += (b - a) * page * -(-(plen[rid] + k) // page)
    return held / (close - begin)


def run(cell, *, seed, seconds, tracer, t0, devices, peaks, prepared=None,
        keep=False, control=False):
    """One run of a serving cell.  ``prepared`` (a system and its probe
    from :func:`set_up`) and ``keep`` (leave the engine running, skip the
    reference) are for the tools that read many windows in one process."""
    cfg, wl = cell.config, cell.workload
    vocab = cfg["vocab_size"]
    system, probe = prepared or set_up(cell, seed, tracer)
    engine = system.engine
    trace_s = float(wl.get("trace_seconds", 4)) if tracer.on else 0.0
    mix = harness.resolve(wl["generator"])(wl["traffic"], seed, vocab,
                                           seconds + trace_s)
    stage0 = {s: v["total_s"] for s, v in
              engine.slo.stage_summary().items()}
    n_warm_ticks, n_warm_tokens = len(probe.ticks), len(probe.tokens)
    sent = []                      # (request, handle, time due, time sent)
    by_rid = {}

    def submit(req, due):
        now = time.perf_counter()
        h = engine.submit(req.prompt, req.max_new)
        sent.append((req, h, due if due is not None else now, now))
        by_rid[h.request_id] = len(sent) - 1

    compiled0 = programs(engine)
    pauses = Pauses()
    gc.callbacks.append(pauses)
    begin = time.perf_counter()
    setup_s = begin - t0
    close = begin + seconds
    end = close + trace_s
    stage1 = {}
    timed = collections.deque(mix.timed)
    for req in mix.start:
        submit(req, None)
    while True:
        now = time.perf_counter()
        if not stage1 and now >= close:
            stage1.update({s: v["total_s"] for s, v in
                           engine.slo.stage_summary().items()})
            gc.callbacks.remove(pauses)
            compiled = programs(engine) - compiled0
            if tracer.on:
                tracer.start()
        if now >= end:
            break
        due = begin + timed[0].due_s if timed else end
        if now >= due:
            submit(timed.popleft(), due)
            continue
        wake = min(due, end if stage1 else close)
        try:
            rid = probe.finished.get(timeout=max(0.0, wake - now))
        except queue.Empty:
            continue
        nxt = mix.after(sent[by_rid[rid]][0]) if rid in by_rid else None
        if nxt is not None:
            submit(nxt, None)
    reduced = None
    if tracer.on:
        tracer.active = False      # no tick that starts now is traced
        with engine._lock:         # the tick in flight has ended
            pass
        tracer.stop()
        reduced = tracer.reduce(window_from="bench.tick")
    # requests due in the window: wait for their first tokens, and for
    # the answers up to ``drain_seconds`` past the end
    due_in = [i for i, s in enumerate(sent) if s[2] < close]
    drain_until = time.perf_counter() + float(wl.get("drain_seconds", 10))
    for i in due_in:
        sent[i][1].wait(max(0.0, drain_until - time.perf_counter()))
    first_until = time.perf_counter() + 60.0
    for i in due_in:
        h = sent[i][1]
        while not h.done and probe.count.get(h.request_id, 0) == 0 \
                and time.perf_counter() < first_until:
            time.sleep(0.01)
    if not keep:
        engine.stop()
    device = harness.device_dict(devices, cell.chips)

    # -- what the window saw ------------------------------------------------
    tokens = probe.tokens[n_warm_tokens:]
    first, stamps = {}, {}
    for t, rid, k in tokens:
        stamps.setdefault(rid, []).append(t)
        if k == 0:
            first[rid] = t
    in_window = [(t, rid, k) for t, rid, k in tokens if begin <= t < close]
    rate = len(in_window) / seconds
    ttft, failed, late = [], 0, []
    for i in due_in:
        req, h, due, at = sent[i]
        late.append(at - due)
        if h.status in DONE_BAD or h.request_id not in first:
            failed += 1
            ttft.append(None)
        else:
            ttft.append(first[h.request_id] - due)
    worst = max([x for x in ttft if x is not None] + [60.0]) \
        if failed else None
    ttft = [worst if x is None else x for x in ttft]
    gaps = [b - a for ts in stamps.values() for a, b in zip(ts, ts[1:])
            if begin <= b < close]
    e2e = {"serve_tokens_per_s": rate, "setup_s": setup_s}
    if ttft:
        e2e["ttft_p50_ms"] = 1e3 * float(np.percentile(ttft, 50))
        e2e["ttft_p95_ms"] = 1e3 * float(np.percentile(ttft, 95))
    if gaps:
        e2e["itl_p50_ms"] = 1e3 * float(np.percentile(gaps, 50))
        e2e["itl_p95_ms"] = 1e3 * float(np.percentile(gaps, 95))
    plen = {s[1].request_id: len(s[0].prompt) for s in sent}
    ticks = probe.ticks[n_warm_ticks:]
    win_ticks = [(a, b) for a, b, n in ticks if begin <= a and b <= close]
    # what the traffic holds on the chip: the weights and the live pages
    ref = importlib.import_module(f"benchmark.reference.{cfg['family']}")
    weight_bytes = ref.weight_bytes(cfg)
    live = live_tokens(stamps, plen, int(wl["engine"]["page_size"]),
                       begin, close)
    live_bytes = weight_bytes + live * counts.kv_bytes_per_token(cfg)
    harness.say(
        f"window: {len(in_window)} tokens in {seconds} s, {rate:.1f} "
        f"tokens/s; {len(due_in)} requests due, {failed} failed, "
        f"{sum(1 for i in due_in if sent[i][1].status == 'completed')} "
        f"completed by now; ttft p50 {e2e.get('ttft_p50_ms')} p95 "
        f"{e2e.get('ttft_p95_ms')} ms; gaps p50 {e2e.get('itl_p50_ms')} "
        f"p95 {e2e.get('itl_p95_ms')} ms over {len(gaps)}; generator late "
        f"by {1e3 * max(late, default=0):.2f} ms at most; "
        f"{len(win_ticks)} ticks; set-up {setup_s:.2f} s")
    harness.say(
        f"live: {live:.0f} tokens of K/V in whole pages on average, "
        f"{live * counts.kv_bytes_per_token(cfg) / 1e9:.3f} GB, with "
        f"{weight_bytes / 1e9:.3f} GB of weights {live_bytes / 1e9:.3f} GB, "
        f"{100 * live_bytes / peaks['hbm_bytes']:.1f}% of the chip; "
        f"{100 * live / (engine.pool.page_size * (engine.pool.num_pages - 1)):.1f}% of "
        f"the pool's pages; at the fullest tick "
        f"{engine.pool.num_pages - 1 - probe.least_free} of "
        f"{engine.pool.num_pages - 1} pages were held")

    # what can stall a window: a program compiled inside it, a long tick,
    # a pause of the collector
    slow = max(win_ticks, key=lambda ab: ab[1] - ab[0], default=(begin,
                                                                 begin))
    harness.say(
        f"stalls: {compiled} programs compiled inside the window; longest "
        f"tick {1e3 * (slow[1] - slow[0]):.1f} ms at {slow[0] - begin:.2f} s; "
        f"{len(pauses.seen)} pauses of the collector over 20 ms" + "".join(
            f", {1e3 * d:.0f} ms (generation {g}) at {t - begin:.2f} s"
            for t, d, g in sorted(pauses.seen, key=lambda e: -e[1])[:3]))

    # -- counts for the per-layer readers -----------------------------------
    d = counts.dims(cfg)
    traced_ctx = 0.0
    if probe.traced:
        lo = probe.ticks[probe.traced[0]][0]
        hi = probe.ticks[probe.traced[-1]][1]
        traced_ctx = float(sum(plen[rid] + k for t, rid, k in tokens
                               if k > 0 and lo <= t <= hi))
    kflops, kbytes = counts.paged_decode_call(
        context_tokens=traced_ctx, heads=d["heads"],
        head_dim=d["hidden"] // d["heads"])
    window = dict(e2e)
    window["live_memory_share"] = 100.0 * live_bytes / peaks["hbm_bytes"]
    facts = {
        "device": device, "chips": cell.chips, "window_s": seconds,
        "on_chip": devices[0].platform == "tpu",
        "model_flops": counts.serve_flops(
            cfg, [plen[rid] for t, rid, k in in_window if k == 0],
            [plen[rid] + k for t, rid, k in in_window if k > 0]),
        "spans": {"tick": win_ticks}, "window": window,
        "stage_totals": {s: stage1[s] - stage0[s] for s in stage0},
        "kernel_work": {"paged_decode": (d["layers"] * kflops,
                                         d["layers"] * kbytes)},
    }

    # -- correct: the served tokens against the reference -------------------
    finished = [(s[0].prompt, np.asarray(s[1].tokens, np.int64))
                for s in sent if s[1].status == "completed"
                and len(s[1].tokens) > 0]
    wrong_length = sum(1 for s in sent if s[1].status == "completed"
                       and len(s[1].tokens) != s[0].max_new)
    outside = sum(1 for _, tk in finished
                  if tk.min() < 0 or tk.max() >= vocab)
    sample = pick_sample(finished, int(wl["check_requests"]), seed)
    done_at = {rid: ts[-1] for rid, ts in stamps.items()}
    facts["backlog"] = [
        sum(1 for s in sent if s[2] <= t) - sum(
            1 for s in sent if s[1].done and
            done_at.get(s[1].request_id, s[2]) <= t)
        for t in (begin + seconds / 2, close)]
    if keep:
        return harness.Outcome(end_to_end=e2e, facts=facts,
                               attempted=len(due_in), failed=failed,
                               compared={}, reduced=reduced)
    system.free()
    del system, engine, probe
    gc.collect()
    t_ref = time.perf_counter()
    rank = judged_rank(wl["engine"])
    limits = wl["limits"]

    def compared(per):
        widest = float(max((g.max() for g in per), default=np.nan))
        return {
            "logit_gap_max": {"value": widest,
                              "limit": limits["logit_gap_max"]},
            "answers_of_wrong_length": {"value": wrong_length, "limit": 0},
            "tokens_outside_vocabulary": {"value": outside, "limit": 0}}

    pad_to = int(wl["engine"]["max_seq_len"])
    per = served_gaps(cfg, seed, sample, rank=rank, pad_to=pad_to)
    out = compared(per)
    harness.say(f"reference: {len(sample)} requests, "
                f"{sum(len(g) for g in per)} served tokens held to the "
                f"reference's best {rank}, widest gap "
                f"{out['logit_gap_max']['value']:.5f}, took "
                f"{time.perf_counter() - t_ref:.1f} s")
    if control:
        from benchmark.reference.common import LOWER
        facts["control"] = {"control": compared(served_gaps(
            cfg, seed, sample, rank=rank, pad_to=pad_to,
            control=LOWER[cfg["dtype"]]))}
    return harness.Outcome(
        end_to_end=e2e, facts=facts, attempted=len(due_in), failed=failed,
        compared=out, reduced=reduced, gap_spans=("bench.tick",))
