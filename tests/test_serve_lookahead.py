"""Look-ahead in the serving loop: a tick dispatches every device program it
has before it fetches any result, and the decode step is dispatched one step
ahead of the tokens the host has seen.

Who drives decides the order.  The engine that runs its own loop (``start()``)
leaves a decode step in flight across ``step()`` calls; ``step()`` called by
hand runs the same two halves back to back.  The deterministic tests here
drive the loop's order by hand (``ahead_tick``: ``_step_locked(True)`` under
the engine's lock, what the loop's thread calls) on the virtual clock.
"""

import time

import pytest

from hetu_tpu.core import set_random_seed
from hetu_tpu.models.gpt import GPT, GPTConfig
from hetu_tpu.serve import ServingEngine

pytestmark = pytest.mark.serve

CFG = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2, num_heads=2,
                max_seq_len=64)
ENGINE = dict(num_slots=3, page_size=8, max_seq_len=64,
              prompt_buckets=(8, 16), seed=7)
PROMPTS = ([5, 6, 7], [9, 9], [3, 4, 5, 6, 7], [11, 12, 13, 14],
           [1, 2, 3, 4, 5, 6, 7, 8, 9], [40, 41])
BUDGETS = (9, 5, 12, 7, 6, 10)


class VirtualClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@pytest.fixture(scope="module")
def model():
    set_random_seed(0)
    return GPT(CFG)


@pytest.fixture(scope="module")
def draft():
    set_random_seed(1)
    return GPT(GPTConfig(vocab_size=97, hidden_size=16, num_layers=1,
                         num_heads=2, max_seq_len=64))


def engine(model, **kw):
    kw.setdefault("clock", VirtualClock())
    return ServingEngine(model, **{**ENGINE, **kw})


def ahead_tick(eng) -> int:
    """One tick in the order of the engine's own loop."""
    with eng._lock:
        return eng._step_locked(True)


def watch(eng):
    """Tokens emitted per request, and every token that followed its
    request's end (there must be none)."""
    emitted, closed, late = {}, set(), []

    def on_token(rid, tok):
        emitted[rid] = emitted.get(rid, 0) + 1
        if rid in closed:
            late.append(rid)

    eng.on_token, eng.on_finish = on_token, closed.add
    return emitted, late


def submit_all(eng, prompts=PROMPTS, budgets=BUDGETS, **kw):
    return [eng.submit(p, n, **kw) for p, n in zip(prompts, budgets)]


def drain(eng, tick, limit=500):
    for _ in range(limit):
        if eng.batcher.idle and eng._pending is None:
            return
        tick(eng)
    raise AssertionError("not idle")


def served(hs):
    return [(h.status, tuple(h.tokens), h.stream_fingerprint) for h in hs]


def written_is_emitted(eng):
    """Every running request's K/V holds its prompt and all its tokens but
    the newest: no step's write without its token, no token lost."""
    for _slot, req in eng.batcher.active():
        assert eng.pool.table(req.id).length == \
            len(req.prompt) + len(req.tokens) - 1


# ------------------------------------------- (a) the same streams, bitwise

@pytest.mark.parametrize("paged", [True, False], ids=["paged", "gather"])
@pytest.mark.parametrize("sampling", ["greedy", "top_k"])
def test_started_and_hand_stepped_engines_serve_the_same_streams(
        model, sampling, paged):
    kw = dict(sampling=sampling, top_k=4, paged_decode=paged)
    by_hand = engine(model, **kw)
    want = submit_all(by_hand)
    by_hand.run_until_idle()
    assert by_hand.stats()["lookahead"]["steps"]["ahead"] == 0

    own_loop = engine(model, clock=time.monotonic, **kw).start()
    try:
        got = submit_all(own_loop)
        assert all(h.wait(120) for h in got)
    finally:
        own_loop.stop()
    assert served(got) == served(want)
    assert all(s == "completed" for s, _, _ in served(got))
    assert [len(h.tokens) for h in got] == list(BUDGETS)
    # and the loop's order driven by hand on the virtual clock
    ahead = engine(model, **kw)
    again = submit_all(ahead)
    drain(ahead, ahead_tick)
    assert served(again) == served(want)
    assert ahead.stats()["lookahead"]["steps"]["ahead"] > 0
    assert ahead.stats()["lookahead"]["discarded"] == 0


# ------------------------------------- (b) an EOS is found one step late

def test_eos_met_at_collect_drops_exactly_one_lookahead_token(model):
    kw = dict(sampling="top_k", top_k=8, temperature=2.0)
    plain = engine(model, **kw)
    h = plain.submit(PROMPTS[0], 12)
    plain.run_until_idle()
    # an EOS that the stream meets in mid-answer, and not before
    at = next(i for i in range(2, 11) if h.tokens[i] not in h.tokens[:i])
    eos = h.tokens[at]

    in_turn = engine(model, eos_id=eos, **kw)
    want = in_turn.submit(PROMPTS[0], 12)
    in_turn.run_until_idle()
    assert want.tokens == h.tokens[:at + 1]

    ahead = engine(model, eos_id=eos, **kw)
    emitted, late = watch(ahead)
    got = ahead.submit(PROMPTS[0], 12)
    drain(ahead, ahead_tick)
    assert served([got]) == served([want]) and got.tokens[-1] == eos
    look = ahead.stats()["lookahead"]
    assert look["discarded"] == 1 and not late
    assert emitted == {got.request_id: at + 1}
    # the step after the EOS was dispatched: one more than the tokens need
    assert sum(look["steps"].values()) == at + 1
    assert ahead.pool.live_sequences == 0


# ------------------------- (c) an answer that ends by count ends on time

def test_a_request_that_ends_by_count_is_never_in_the_step_after_its_last(
        model):
    eng = engine(model)
    steps = []
    real = eng._decode_dispatch

    def spy(last=None):
        step = real(last)
        if step is not None:
            steps.append([req.id for _slot, req in step.active])
        return step

    eng._decode_dispatch = spy
    hs = submit_all(eng)
    drain(eng, ahead_tick)
    assert [len(h.tokens) for h in hs] == list(BUDGETS)
    # the first token is the prefill's: a request of n tokens is in n - 1
    # decode steps, and no token was ever made to be thrown away
    for h, n in zip(hs, BUDGETS):
        assert sum(h.request_id in s for s in steps) == n - 1
    assert eng.stats()["lookahead"]["discarded"] == 0


def test_a_request_that_fills_the_window_ends_by_count_too(model):
    eng = engine(model, max_seq_len=16, prompt_buckets=(8,))
    h = eng.submit([1, 2, 3, 4, 5, 6], 10)        # 6 + 10 = max_seq_len
    drain(eng, ahead_tick)
    assert h.status == "completed" and len(h.tokens) == 10
    assert eng.stats()["lookahead"]["discarded"] == 0


# ---------------- (d) a request retired while a step is in flight

def test_deadline_with_a_step_pending_appends_nothing_to_the_closed_handle(
        model):
    clock = VirtualClock()
    eng = engine(model, clock=clock)
    emitted, late = watch(eng)
    cut = eng.submit(PROMPTS[0], 30, deadline_s=5.0)
    kept = eng.submit(PROMPTS[1], 8)
    for _ in range(3):
        ahead_tick(eng)
    assert eng._pending is not None and len(eng._pending.active) == 2
    had = emitted[cut.request_id]
    clock.t = 6.0
    ahead_tick(eng)      # retires it, then collects the step that held it
    assert cut.status == "expired" and len(cut.tokens) == had
    assert emitted[cut.request_id] == had and not late
    assert eng.stats()["lookahead"]["discarded"] == 1
    drain(eng, ahead_tick)
    assert kept.status == "completed" and len(kept.tokens) == 8
    assert eng.pool.live_sequences == 0


def test_eviction_is_decided_in_turn_and_drops_nothing_into_a_closed_handle(
        model):
    # 2 slots x 4 pages would be 8; 6 are given (the overcommitted pool of
    # tests/test_serve.py), so growth hits the wall in mid-answer
    kw = dict(num_slots=2, max_seq_len=32, prompt_buckets=(8,), num_pages=7)
    prompts = ([1, 2, 3, 4, 5, 6, 7], [8, 9, 10, 11, 12, 13])
    in_turn = engine(model, **kw)
    want = submit_all(in_turn, prompts, (24, 24))
    in_turn.run_until_idle()
    eng = engine(model, **kw)
    emitted, late = watch(eng)
    got = submit_all(eng, prompts, (24, 24))
    drain(eng, ahead_tick)
    assert "evicted" in [h.status for h in got]
    assert served(got) == served(want)
    assert not late and eng.pool.live_sequences == 0
    assert [emitted[h.request_id] for h in got] == \
        [len(h.tokens) for h in got]


# ------------- (e) whatever reads or moves a request collects first

@pytest.mark.parametrize("how", ["evacuate", "crash", "hang",
                                 "accept_failover", "step"])
def test_a_pending_step_is_collected_before_requests_are_read(model, how):
    kw = dict(sampling="top_k", top_k=8, temperature=2.0)
    in_turn, eng = engine(model, **kw), engine(model, **kw)
    for e in (in_turn, eng):
        submit_all(e, PROMPTS[:3], (20, 20, 20))
    for _ in range(4):
        ahead_tick(eng)
    # the tick of a prefill holds no decode step of its request when the
    # first token is fetched last: three steps were dispatched, not four
    for _ in range(3):
        in_turn.step()
    assert eng._pending is not None
    reqs = [req for _slot, req in eng.batcher.active()]
    short = [len(r.tokens) for r in reqs]
    if how == "evacuate":
        moved = eng.evacuate()
        assert [r.id for r, *_ in moved] == [r.id for r in reqs]
    elif how == "crash":
        eng.crash()
    elif how == "hang":
        eng.hang(2)
    elif how == "accept_failover":
        other = engine(model)
        h = other.submit(PROMPTS[3], 4, request_id=77)
        req, = other.batcher.evacuate()
        assert eng.accept_failover(req, h, other._timelines.pop(77)) is None
    else:
        # by hand: collects, then a whole step in turn
        assert eng.step() == 6 and in_turn.step() == 3
        short = [n + 1 for n in short]
    assert eng._pending is None
    # the tokens of the step in flight are out, as in turn
    assert [len(r.tokens) for r in reqs] == [n + 1 for n in short]
    assert [r.tokens for r in reqs] == \
        [r.tokens for _slot, r in in_turn.batcher.active()]
    if how != "evacuate":
        written_is_emitted(eng)


def test_stop_with_a_step_pending_leaves_none_and_loses_no_token(model):
    eng = engine(model, clock=time.monotonic).start()
    emitted, late = watch(eng)
    hs = submit_all(eng, PROMPTS[:3], (50, 50, 50))
    until = time.monotonic() + 60
    while sum(emitted.values()) < 30 and time.monotonic() < until:
        time.sleep(0.002)
    eng.stop()
    assert eng._pending is None and not any(h.done for h in hs)
    written_is_emitted(eng)
    for _slot, req in eng.batcher.active():
        assert emitted[req.id] == len(req.tokens)
    # and the answers go on by hand where the loop left them
    eng.run_until_idle()
    want = engine(model)
    ref = submit_all(want, PROMPTS[:3], (50, 50, 50))
    want.run_until_idle()
    assert served(hs) == served(ref) and not late


# ----------------------------- (f) speculation never runs ahead

def test_with_a_draft_model_no_step_is_dispatched_ahead(model, draft):
    want = engine(model)
    ref = submit_all(want)
    want.run_until_idle()
    eng = engine(model, clock=time.monotonic, draft_model=draft,
                 spec_k=3).start()
    try:
        hs = submit_all(eng)
        assert all(h.wait(120) for h in hs)
        assert eng._pending is None
    finally:
        eng.stop()
    assert served(hs) == served(ref)
    assert eng.stats()["lookahead"]["steps"] == {"ahead": 0, "in_turn": 0}
    # the loop's order asked of it by hand is refused as well
    eng = engine(model, draft_model=draft, spec_k=3)
    hs = submit_all(eng)
    for _ in range(200):
        ahead_tick(eng)
        assert eng._pending is None
        if eng.batcher.idle:
            break
    assert served(hs) == served(ref)


# --------------------------------- (g) the share of steps ahead

def test_ahead_share_of_a_started_engine_under_steady_decode(model):
    eng = engine(model, clock=time.monotonic).start()
    try:
        hs = submit_all(eng, PROMPTS[:3], (55, 55, 55))
        assert all(h.wait(120) for h in hs)
    finally:
        eng.stop()
    look = eng.stats()["lookahead"]
    assert look["ahead_share"] > 0.9 and look["discarded"] == 0
    assert sum(look["steps"].values()) >= 54
    by_hand = engine(model)
    submit_all(by_hand, PROMPTS[:3], (55, 55, 55))
    by_hand.run_until_idle()
    look = by_hand.stats()["lookahead"]
    assert look["ahead_share"] == 0 and look["steps"]["in_turn"] == 54


# ------------------------ (h) step() by hand returns that call's tokens

def test_direct_step_returns_the_tokens_of_that_call(model):
    eng = engine(model)
    emitted, late = watch(eng)
    hs = submit_all(eng)
    total = 0
    while not eng.batcher.idle:
        before = sum(emitted.values())
        admitted = eng.batcher.queue_len
        produced = eng.step()
        admitted -= eng.batcher.queue_len       # each brought a first token
        assert produced == sum(emitted.values()) - before - admitted
        assert eng._pending is None
        total += produced
    assert total == sum(BUDGETS) - len(BUDGETS)
    assert [len(h.tokens) for h in hs] == list(BUDGETS)


# ------------------- many submitters against the loop, switched often

def test_submitters_on_many_threads_get_the_streams_of_a_quiet_engine(model):
    """The step in flight is shared state between the loop's thread and
    whoever holds the engine's lock: more submitters than cores, the
    interpreter switching threads every 10 us, every stream what a
    hand-stepped engine gives for the same request id."""
    import sys
    import threading

    jobs = [(100 + i, PROMPTS[i % len(PROMPTS)], 4 + i % 7)
            for i in range(24)]
    quiet = engine(model, queue_depth=64)
    want = {rid: quiet.submit(p, n, request_id=rid) for rid, p, n in jobs}
    quiet.run_until_idle()

    eng = engine(model, clock=time.monotonic, queue_depth=64).start()
    emitted, late = watch(eng)
    got, errors = {}, []

    def client(mine):
        try:
            for rid, p, n in mine:
                h = eng.submit(p, n, request_id=rid)
                assert h.wait(120), f"request {rid} never resolved"
                got[rid] = h
        except Exception as e:      # reported by the main thread below
            errors.append(e)

    threads = [threading.Thread(target=client, args=(jobs[i::12],))
               for i in range(12)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(180)
    finally:
        sys.setswitchinterval(interval)
        eng.stop()
    assert not errors and not any(t.is_alive() for t in threads)
    assert {rid: served([h]) for rid, h in got.items()} == \
        {rid: served([h]) for rid, h in want.items()}
    assert not late and eng._pending is None
    assert eng.pool.live_sequences == 0
    assert emitted == {rid: n for rid, _p, n in jobs}
    assert eng.stats()["lookahead"]["discarded"] == 0
