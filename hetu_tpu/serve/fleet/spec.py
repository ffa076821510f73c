"""Speculative decoding: draft proposes, target verifies in one step.

Decode is memory-bound — each (num_slots, 1) paged step streams the
whole KV history and model weights to emit ONE token per slot.  A small
draft GPT can propose ``k`` likely continuations per slot for a fraction
of that traffic, and the target then scores all of them in a SINGLE
batched paged-decode step: slot ``s`` expands into ``k + 1`` rows that
share its page table at consecutive cache indices, feeding the chain
``[last_emitted, d_1, ..., d_k]``.  Row ``j``'s K/V lands at position
``L + j`` BEFORE attention runs (``MultiHeadAttention._call_paged``
scatters every row's K/V into the pool first), so row ``j`` attends over
the history *including* rows ``< j`` of its own chain — the chain
composes inside one program.

**The bitwise guarantee.**  Sampling keys derive from ``(seed, request,
position)`` — not from a shared stream — so the token the engine emits
at position ``p`` is a pure function of the logits at ``p`` and the key.
Verification regenerates exactly those draws: row ``j`` samples with the
key at position ``L + j + 1``, and its context is valid iff the draft's
fed tokens match what the engine actually emitted (``d_i == t_{i-1}``
cumulatively).  Accepted tokens are therefore not merely from the right
*distribution* (the vLLM-style rejection-sampling bar) — they are the
IDENTICAL tokens the non-speculative engine would have produced, bit for
bit, which the acceptance tests assert across greedy, temperature, and
top-k sampling.  A mispredicted draft costs nothing but the wasted rows:
the page-table cursor (``PageTable.length``) simply does not advance
past the last accepted token — rejected rows' K/V stays as dead bytes
beyond ``length``, masked by every future step and overwritten as the
sequence grows, the same contract prefill-bucket padding already relies
on.  Pages allocated for the chain are NOT freed on rejection (the next
steps will fill them).

Speculation requires the paged decode path: paged K/V writes are
element-scattered per (page, slot), so chained rows compose; the gather
fallback scatters whole per-row page COPIES back and chained rows would
clobber each other (``serve.engine`` enforces this at construction).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from hetu_tpu.obs import compile as _compile
from hetu_tpu.obs import journal as _journal
from hetu_tpu.obs import registry as _obs
from hetu_tpu.serve.kv_cache import OutOfPages

__all__ = ["DraftProposer", "SpeculativeDecoder"]

_spec_metrics = None


def _spec_m() -> dict:
    global _spec_metrics
    if _spec_metrics is None:
        reg = _obs.get_registry()
        _spec_metrics = {
            "proposed": reg.counter(
                "hetu_spec_proposed_tokens_total",
                "draft tokens proposed for target verification"),
            "accepted": reg.counter(
                "hetu_spec_accepted_tokens_total",
                "draft tokens accepted (bitwise equal to what the "
                "non-speculative engine would have emitted)"),
        }
    return _spec_metrics


class DraftProposer:
    """Greedy draft proposals at a fixed (num_slots, max_len) shape.

    The draft runs a full-context forward per proposed token (k jitted
    calls per scheduler tick) — simple and exactly deterministic.  Padding
    beyond each row's length is harmless under causal attention: the
    logits at ``length - 1`` never see it.  Greedy argmax keeps the draft
    itself seed-free; draft quality only moves the acceptance RATE, never
    the emitted stream.  (A KV-cached draft is the obvious next
    optimization once the fleet tier carries real traffic — the proposer
    is the seam it slots into.)"""

    def __init__(self, model, num_slots: int, max_len: int):
        self.model = model
        self.num_slots = num_slots
        self.max_len = max_len
        self._fn = _compile.instrument(jax.jit(self._impl),
                                       site="serve.spec_draft")

    def _impl(self, model, tokens, lengths):
        logits = model(tokens)  # (S, max_len, vocab), causal
        last = jnp.take_along_axis(
            logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
        return jnp.argmax(last, axis=-1).astype(jnp.int32)

    def propose(self, contexts, k: int) -> np.ndarray:
        """``contexts[slot]`` is the full token context (prompt +
        generated) or None for slots not speculating; returns (num_slots,
        k) proposals (zeros on non-speculating rows)."""
        S = self.num_slots
        toks = np.zeros((S, self.max_len), np.int32)
        lens = np.ones((S,), np.int32)
        for s, ctx in enumerate(contexts):
            if ctx is None:
                continue
            n = min(len(ctx), self.max_len)
            toks[s, :n] = ctx[-n:]
            lens[s] = n
        out = np.zeros((S, k), np.int32)
        for j in range(k):
            nxt = np.asarray(self._fn(self.model, jnp.asarray(toks),
                                      jnp.asarray(lens)))
            out[:, j] = nxt
            for s in range(S):
                if contexts[s] is not None and lens[s] < self.max_len:
                    toks[s, lens[s]] = nxt[s]
                    lens[s] += 1
        return out


class SpeculativeDecoder:
    """Replaces the engine's per-token decode step with propose-and-
    verify; constructed by ``ServingEngine(draft_model=..., spec_k=...)``
    and driven from the scheduler tick."""

    def __init__(self, draft_model, k: int, *, num_slots: int,
                 max_len: int):
        if k < 1:
            raise ValueError(f"spec_k must be >= 1 draft tokens, got {k}")
        if draft_model.config.max_seq_len < max_len:
            raise ValueError(
                f"draft max_seq_len {draft_model.config.max_seq_len} is "
                f"shorter than the serving window {max_len}")
        self.k = k
        self.width = k + 1  # chain rows per slot: base token + k drafts
        self.draft = DraftProposer(draft_model, num_slots, max_len)

    def stats(self) -> dict:
        return {"k": self.k, "width": self.width}

    def decode_step(self, eng) -> int:
        """One speculative scheduler decode: propose, verify every slot's
        chain in ONE (num_slots * (k+1), 1) paged step, emit the accepted
        prefix of each chain, roll the cursor back over the rest."""
        active = eng.batcher.active()
        if not active:
            return 0
        t0 = eng.clock()
        S, W = eng.batcher.num_slots, self.width
        rows = S * W
        seq_ids = [None] * rows
        tokens = np.zeros((rows, 1), np.int32)
        index = np.zeros(rows, np.int32)
        rids = np.zeros(rows, np.int32)
        positions = np.zeros(rows, np.int32)
        chain_len: dict = {}
        contexts = [None] * S
        evicted = []
        ps = eng.pool.page_size
        for slot, req in active:
            pt = eng.pool.table(req.id)
            L = pt.length
            remaining = req.max_new_tokens - len(req.tokens)
            cl = max(1, min(W, remaining, eng.max_seq_len - L))
            try:
                eng._ensure_pages(req.id, L + cl)
            except OutOfPages:
                cl = 1
                try:
                    eng._ensure_pages(req.id, L + 1)
                except OutOfPages:
                    evicted.append((slot, req))
                    continue
            # copy-on-write guard over every page the chain writes into
            # (prefix sharing keeps write targets private by construction;
            # this is the enforced invariant, not an expected copy) — a
            # CoW needing a free page on a full pool evicts, the same
            # answer the non-speculative decode gives
            try:
                if eng.sharer is not None:
                    for pi in range(L // ps, (L + cl - 1) // ps + 1):
                        eng.pool.copy_on_write(req.id, pi * ps)
            except OutOfPages:
                evicted.append((slot, req))
                continue
            chain_len[slot] = cl
            if cl > 1:
                contexts[slot] = req.prompt + req.tokens
        for slot, req in evicted:
            eng._retire(req, "evicted", eng.clock())
        active = [(s, r) for s, r in active if r.slot is not None]
        if not active:
            return 0
        if any(c is not None for c in contexts):
            proposals = self.draft.propose(contexts, self.k)
        else:
            proposals = np.zeros((S, self.k), np.int32)
        chains: dict = {}
        proposed_total = 0
        for slot, req in active:
            L = eng.pool.table(req.id).length
            cl = chain_len[slot]
            chain = [req.tokens[-1]] + [int(t)
                                        for t in proposals[slot][:cl - 1]]
            chains[slot] = chain
            proposed_total += cl - 1
            for j in range(cl):
                r = slot * W + j
                seq_ids[r] = req.id
                tokens[r, 0] = chain[j]
                index[r] = L + j
                rids[r] = req.id
                positions[r] = L + j + 1
        toks, _ = eng.pool.step(
            eng._paged_step_fn, eng._model_leaves,
            eng.pool.gather_indices(seq_ids),
            jnp.asarray(index), jnp.asarray(tokens),
            jnp.asarray(rids), jnp.asarray(positions))
        toks = np.asarray(toks)
        now = eng.clock()
        nactive = len(active)
        produced = 0
        accepted_total = 0
        for slot, req in active:
            cl, chain = chain_len[slot], chains[slot]
            base = slot * W
            # t_0 is the ordinary next token; t_j is exact iff the fed
            # chain matches the emitted stream so far
            emit = [int(toks[base])]
            j = 1
            while j < cl and chain[j] == emit[j - 1]:
                emit.append(int(toks[base + j]))
                j += 1
            emitted = 0
            for tok in emit:
                eng.pool.table(req.id).length += 1
                produced += 1
                emitted += 1
                eng._append_token(req, tok, now, batch=nactive)
                if req.slot is None:
                    break  # retired (EOS / budget / context): the rest
                    # of the accepted chain is past the stream's end
            # count only draft tokens that actually ENTERED the stream
            # (a mid-chain EOS retire discards the accepted tail, and the
            # acceptance-rate telemetry must not flatter the draft)
            accepted_total += max(emitted - 1, 0)
        m = _spec_m()
        if proposed_total:
            m["proposed"].inc(proposed_total)
            m["accepted"].inc(accepted_total)
            _journal.record("spec_verify", proposed=proposed_total,
                            accepted=accepted_total)
        dt = now - t0
        from hetu_tpu.serve.engine import _serve_m
        sm = _serve_m()
        sm["tok_latency"].observe(dt / max(produced, 1))
        sm["tps"].set(produced / dt if dt > 0 else 0.0)
        return produced
