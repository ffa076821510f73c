"""Serving-fleet tests: copy-on-write prefix sharing, speculative
decoding, cache-affinity routing (hetu_tpu/serve/fleet/).

Tier-1: the refcount/CoW pool contract, the never-alias property test
(hash collisions degrade to misses), bitwise speculative-vs-baseline
stream equality across all three sampling modes, the zero-duplicate-
prefix-page acceptance, router placement policy + bounded retries, the
2-replica endpoint smoke, and the full-fleet same-seed replay (bitwise
placements / streams / journal).  The wall-clock fleet-vs-single perf
comparison and the multi-replica shed/freeze chaos run ride the slow
tier.
"""

from __future__ import annotations

import json
import urllib.request

import numpy as np
import pytest

from hetu_tpu.core import set_random_seed
from hetu_tpu.layers import CacheSpec
from hetu_tpu.models import GPT
from hetu_tpu.models.gpt import GPTConfig
from hetu_tpu.obs import journal as obs_journal
from hetu_tpu.obs import registry as obs_registry
from hetu_tpu.serve import (DoubleFree, FleetRouter, KVCachePool,
                            OutOfPages, ServingEngine,
                            generate_shared_prefix_load, serve_fleet_router)
from hetu_tpu.serve import kv_cache as kvmod
from hetu_tpu.serve.fleet import prefix as prefix_mod
from hetu_tpu.serve.fleet.prefix import PrefixSharer

pytestmark = [pytest.mark.serve, pytest.mark.fleet]

CFG = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2, num_heads=2,
                max_seq_len=64)
DRAFT_CFG = GPTConfig(vocab_size=97, hidden_size=16, num_layers=1,
                      num_heads=2, max_seq_len=64)
TEMPLATE = tuple(range(1, 17))  # 16 tokens = 2 full pages at page_size 8


@pytest.fixture(scope="module")
def model():
    set_random_seed(0)
    return GPT(CFG)


@pytest.fixture(scope="module")
def draft(model):
    set_random_seed(1)
    return GPT(DRAFT_CFG)


class VirtualClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def make_engine(model, clock, **kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("prompt_buckets", (8, 16, 32))
    kw.setdefault("seed", 11)
    kw.setdefault("sampling", "greedy")
    return ServingEngine(model, clock=clock, **kw)


def drain(target, clock, max_steps: int = 5000) -> int:
    """Step an engine or router until idle on the virtual clock; returns
    scheduler ticks taken."""
    idle = (lambda: target.batcher.idle) if hasattr(target, "batcher") \
        else (lambda: target.idle)
    for i in range(max_steps):
        if idle():
            return i
        target.step()
        clock.advance(0.001)
    raise AssertionError(f"not idle after {max_steps} ticks")


def tiny_pool(**kw) -> KVCachePool:
    kw["spec"] = CacheSpec.kv(1, kw.pop("num_heads", 1), 2)
    kw.setdefault("num_pages", 8)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_seq_len", 16)
    return KVCachePool(**kw)


class TestRefcountPool:
    def test_shared_alloc_aliases_and_refcounts(self):
        pool = tiny_pool()
        a = pool.alloc(0, 10)          # 3 pages, rc 1 each
        b = pool.alloc(1, 10, shared_pages=a.pages[:2])
        assert b.pages[:2] == a.pages[:2]          # aliased, not copied
        assert pool.refcount(a.pages[0]) == 2
        assert pool.stats()["pages_shared"] == 2
        pool.free(0)
        # shared pages survive A's retirement; A's private page freed
        assert pool.refcount(a.pages[0]) == 1
        assert pool.refcount(a.pages[2]) == 0
        pool.free(1)
        assert pool.stats()["pages_free"] == pool.num_pages - 1
        assert pool.stats()["allocs"] == 2 and pool.stats()["frees"] == 2

    def test_double_free_raises_named(self):
        pool = tiny_pool()
        pool.alloc(0, 4)
        pool.free(0)
        with pytest.raises(DoubleFree):
            pool.free(0)
        with pytest.raises(DoubleFree):
            pool.release(1)  # already on the free list
        pool.stats()  # invariants still hold after the refused frees

    def test_copy_on_write_unshares(self):
        pool = tiny_pool()
        a = pool.alloc(0, 8)
        pool.commit(pool.k.at[:, a.pages[0]].set(7.0),
                    pool.v.at[:, a.pages[0]].set(3.0))
        b = pool.alloc(1, 8, shared_pages=a.pages[:1])
        assert pool.copy_on_write(1, 0) is True
        assert b.pages[0] != a.pages[0]            # B got a private copy
        assert pool.refcount(a.pages[0]) == 1
        assert np.all(np.asarray(pool.k[:, b.pages[0]]) == 7.0)
        assert np.all(np.asarray(pool.v[:, b.pages[0]]) == 3.0)
        # already-private pages never copy
        assert pool.copy_on_write(1, 0) is False
        pool.stats()

    def test_defrag_pins_shared_and_trie_held_pages(self):
        pool = tiny_pool(num_pages=12)
        a = pool.alloc(0, 12)                       # pages 1,2,3
        b = pool.alloc(1, 12, shared_pages=a.pages[:1])  # 1(shared),4,5
        pool.retain(a.pages[2])                     # "trie" holds page 3
        marker = {p: float(p) for pt in (a, b) for p in pt.pages}
        for p, val in marker.items():
            pool.commit(pool.k.at[:, p].set(val), pool.v)
        pool.free(0)   # pages 2 freed; 1 shared w/ B; 3 kept by the trie
        shared, trie_held = b.pages[0], a.pages[2]
        moved = pool.defrag()
        assert moved > 0
        # pinned pages kept their physical index
        assert b.pages[0] == shared and pool.refcount(trie_held) == 1
        # every surviving table entry still reads its own bytes (movable
        # pages' rows moved with the permutation, pinned ones stayed)
        for want, page in zip([marker[shared], 4.0, 5.0], b.pages):
            assert np.all(np.asarray(pool.k[:, page]) == want)
        pool.stats()

    def test_out_of_pages_on_shared_alloc_is_side_effect_free(self):
        pool = tiny_pool(num_pages=4)  # 3 usable
        a = pool.alloc(0, 8)           # 2 pages
        before = pool.stats()
        with pytest.raises(OutOfPages):
            pool.alloc(1, 16, shared_pages=a.pages[:2])  # needs 2 fresh
        assert pool.stats() == before


class TestPrefixTrie:
    def test_hash_collision_never_aliases(self, monkeypatch):
        # force EVERY block to the same hash bucket: token equality alone
        # must prevent aliasing
        monkeypatch.setattr(prefix_mod, "block_key", lambda block: 0)
        pool = tiny_pool(num_pages=16)
        sharer = PrefixSharer(pool)
        a_prompt = list(range(10))
        a = pool.alloc(0, len(a_prompt))
        sharer.publish(a_prompt, a)
        b_prompt = [9, 9, 9, 9] + a_prompt[4:]
        pages, shared = sharer.lookup(b_prompt)
        assert pages == [] and shared == 0
        # and publishing the colliding prompt must not overwrite A's node
        b = pool.alloc(1, len(b_prompt))
        sharer.publish(b_prompt, b)
        assert sharer.lookup(a_prompt + [50])[0] == [a.pages[0],
                                                     a.pages[1]]

    def test_property_differing_prompts_never_alias(self):
        # seeded property sweep: mutate one token anywhere inside the
        # shareable region; no aliased page may cover the mutation
        rng = np.random.default_rng(7)
        for trial in range(40):
            pool = tiny_pool(num_pages=16)
            sharer = PrefixSharer(pool)
            plen = int(rng.integers(5, 16))
            a_prompt = [int(t) for t in rng.integers(0, 97, plen)]
            a = pool.alloc(0, plen)
            sharer.publish(a_prompt, a)
            pos = int(rng.integers(0, plen))
            b_prompt = list(a_prompt)
            b_prompt[pos] = (b_prompt[pos] + 1 + int(rng.integers(96))) % 97
            pages, shared_tokens = sharer.lookup(b_prompt)
            # aliased pages must cover only block-equal prefixes
            assert shared_tokens <= (pos // 4) * 4, \
                (trial, a_prompt, b_prompt, pos, shared_tokens)
            for i, page in enumerate(pages):
                assert a_prompt[i * 4:(i + 1) * 4] == \
                    b_prompt[i * 4:(i + 1) * 4]
                assert page == a.pages[i]

    def test_eviction_reclaims_lru_trie_only_pages(self):
        pool = tiny_pool(num_pages=8)
        sharer = PrefixSharer(pool)
        p1 = [1] * 4 + [9]
        p2 = [2] * 4 + [9]
        for sid, prompt in ((0, p1), (1, p2)):
            t = pool.alloc(sid, len(prompt))
            sharer.publish(prompt, t)
            pool.free(sid)
        assert pool.stats()["pages_free"] == 5  # 2 pages live in the trie
        sharer.lookup(p1)  # bump p1's recency: p2 must evict first
        freed = sharer.reclaim(1)
        assert freed == 1
        assert sharer.lookup(p1 + [8])[1] == 4   # p1 survived
        assert sharer.lookup(p2 + [8])[1] == 0   # p2 evicted
        assert sharer.reclaim(5) == 1            # only p1's page remains
        assert pool.stats()["pages_free"] == 7


class TestSharedPrefixEngine:
    def test_zero_duplicate_prefix_pages_and_journal(self, model):
        clock = VirtualClock()
        eng = make_engine(model, clock, prefix_sharing=True)
        jr = obs_journal.EventJournal(clock=clock)
        with obs_journal.use(jr):
            h1 = eng.submit(list(TEMPLATE) + [40, 41], 4)
            drain(eng, clock)
            kvmod.reset_pages_written_count()
            h2 = eng.submit(list(TEMPLATE) + [50, 51, 52], 4)
            drain(eng, clock)
        assert h1.status == h2.status == "completed"
        # request 2: 19 prompt tokens = 3 pages, 2 aliased from the trie
        # -> ONE fresh (suffix) page written, zero duplicate prefix pages
        assert kvmod.pages_written_count() == 1
        shares = jr.of_kind("prefix_share")
        assert [e["shared_tokens"] for e in shares] == [16]
        assert shares[0]["request_id"] == h2.request_id

    def test_sharing_leaves_streams_unchanged(self, model):
        def run(prefix_sharing):
            clock = VirtualClock()
            eng = make_engine(model, clock, prefix_sharing=prefix_sharing)
            hs = [eng.submit(list(TEMPLATE) + [60 + i], 6)
                  for i in range(3)]
            drain(eng, clock)
            return [h.tokens for h in hs]

        assert run(True) == run(False)

    def test_share_trim_never_overflows_the_serving_window(self, model):
        """Regression: an untrimmed share of 40 tokens + a 32-token
        suffix bucket would ragged-write past the 64-token gathered view
        — dynamic_update_slice clamps, shifting the write back INTO the
        shared prefix pages and corrupting them for every alias.  The
        engine must trim the share until shared + suffix_bucket fits."""
        def run(sharing):
            clock = VirtualClock()
            eng = make_engine(model, clock, prefix_sharing=sharing,
                              prompt_buckets=(8, 16, 32, 64))
            a = list(range(1, 49))                    # publishes 6 blocks
            b = a[:40] + list(range(60, 80))          # 60 tokens, share 40
            c = a[:32] + [90]                         # re-aliases a's pages
            streams = []
            for p in (a, b, c):
                h = eng.submit(p, 3)
                drain(eng, clock)
                streams.append(h.tokens)
            return streams

        # corrupted shared pages would change b's own stream AND c's
        # (c re-reads the pages b's overflow would have clobbered)
        assert run(True) == run(False)

    def test_freeze_drops_sharing_instead_of_cold_suffix_compile(
            self, model):
        clock = VirtualClock()
        eng = make_engine(model, clock, prefix_sharing=True,
                          prompt_buckets=(8, 32))
        h1 = eng.submit(list(TEMPLATE) + [7] * 4, 3)   # warms bucket 32
        drain(eng, clock)
        assert eng._prefill_buckets == {32}
        eng.freeze_bucket_growth = True
        # share would leave a 4-token suffix -> bucket 8, COLD under the
        # freeze: prefill must drop the share and reuse the warm 32
        h2 = eng.submit(list(TEMPLATE) + [9] * 4, 3)
        drain(eng, clock)
        assert h2.status == "completed"
        assert eng._prefill_buckets == {32}  # no cold compile slipped in

    def test_admission_reclaims_trie_pages_under_pressure(self, model):
        clock = VirtualClock()
        # pool sized for exactly one max-length sequence per slot; the
        # trie's retained template pages must yield to real admissions
        eng = make_engine(model, clock, num_slots=2, num_pages=17,
                          prefix_sharing=True)
        h1 = eng.submit(list(TEMPLATE) + [7] * 14, 4)   # 30 tokens
        drain(eng, clock)
        handles = [eng.submit([80 + i] * 30, 4) for i in range(4)]
        drain(eng, clock)
        assert all(h.status == "completed" for h in handles)
        eng.pool.stats()


class TestSpeculative:
    @pytest.mark.parametrize("sampling", ["greedy", "temperature", "top_k"])
    def test_streams_bitwise_vs_baseline(self, model, draft, sampling):
        def run(draft_model):
            clock = VirtualClock()
            eng = make_engine(model, clock, sampling=sampling, top_k=5,
                              temperature=0.8, draft_model=draft_model,
                              spec_k=3)
            hs = [eng.submit(list(range(2 + i, 12 + i)), 8)
                  for i in range(4)]
            drain(eng, clock)
            return [(h.tokens, h.stream_fingerprint) for h in hs]

        assert run(draft) == run(None)

    def test_a_speculative_tick_is_one_device_span(self, model, draft):
        """Propose-and-verify runs under one ``serve.tick.decode.device``
        span a tick, so a tick's children cover it on this path too."""
        from hetu_tpu.obs import tracing
        tracer = tracing.get_tracer()
        tracer.reset()
        clock = VirtualClock()
        eng = make_engine(model, clock, draft_model=draft, spec_k=3)
        with tracer.collect():
            hs = [eng.submit(list(range(2 + i, 12 + i)), 8)
                  for i in range(2)]
            drain(eng, clock)
        spans = tracer.spans
        tracer.reset()
        assert all(h.status == "completed" for h in hs)
        ticks = [s for s in spans if s.name == "serve.tick"]
        assert ticks
        for t in ticks:
            kids = [s.name for s in spans if s.parent_id == t.span_id]
            assert kids.count("serve.tick.decode.device") == 1
            assert "serve.tick.decode.build" not in kids
            assert "serve.tick.emit" not in kids

    def test_perfect_draft_accepts_and_saves_steps(self, model):
        reg = obs_registry.get_registry()

        def run(draft_model):
            clock = VirtualClock()
            eng = make_engine(model, clock, draft_model=draft_model,
                              spec_k=3)
            hs = [eng.submit(list(range(1 + i, 9 + i)), 12)
                  for i in range(4)]
            return [h.tokens for h in hs], drain(eng, clock)

        before = reg.snapshot()
        jr = obs_journal.EventJournal()
        with obs_journal.use(jr):
            spec_tokens, spec_steps = run(model)  # draft == target
        base_tokens, base_steps = run(None)
        assert spec_tokens == base_tokens
        assert spec_steps < base_steps  # k+1 tokens/slot/tick when accepted
        after = reg.snapshot()
        proposed = after.get("hetu_spec_proposed_tokens_total", 0) - \
            before.get("hetu_spec_proposed_tokens_total", 0)
        accepted = after.get("hetu_spec_accepted_tokens_total", 0) - \
            before.get("hetu_spec_accepted_tokens_total", 0)
        assert proposed > 0 and accepted == proposed  # greedy, same model
        events = jr.of_kind("spec_verify")
        assert events and all(e["accepted"] <= e["proposed"]
                              for e in events)

    def test_spec_requires_paged_decode(self, model, draft):
        with pytest.raises(ValueError, match="paged_decode"):
            make_engine(model, VirtualClock(), draft_model=draft,
                        paged_decode=False)

    def test_rejected_chains_leave_pool_consistent(self, model, draft):
        clock = VirtualClock()
        eng = make_engine(model, clock, sampling="top_k", top_k=5,
                          prefix_sharing=True, draft_model=draft,
                          spec_k=3)
        hs = [eng.submit(list(TEMPLATE) + [70 + i], 10) for i in range(5)]
        drain(eng, clock)
        assert all(h.status == "completed" for h in hs)
        stats = eng.pool.stats()  # asserts the accounting invariants
        assert stats["sequences"] == 0
        assert stats["allocs"] - stats["frees"] == 0


class TestRouter:
    def test_affinity_pressure_and_load_placement(self, model):
        clock = VirtualClock()
        engines = [make_engine(model, clock, num_slots=2,
                               prefix_sharing=True) for _ in range(2)]
        router = FleetRouter(engines)
        h1 = router.submit(list(TEMPLATE) + [40], 4)
        router.run_until_idle()
        h2 = router.submit(list(TEMPLATE) + [41], 4)  # trie match -> r0
        h3 = router.submit([9, 8, 7], 4)  # no affinity; r0 busier -> r1
        router.run_until_idle()
        assert [p["replica"] for p in router.placements] == [0, 0, 1]
        assert [p["reason"] for p in router.placements] == \
            ["pressure", "affinity", "pressure"]
        assert all(h.status == "completed" for h in (h1, h2, h3))

    def test_bounded_retries_on_shed(self, model):
        clock = VirtualClock()
        engines = [make_engine(model, clock, num_slots=2,
                               prefix_sharing=True) for _ in range(2)]
        router = FleetRouter(engines)
        router.submit(list(TEMPLATE) + [40], 4)
        router.run_until_idle()
        engines[0].batcher.set_shed("controller shed: sustained SLO burn")
        h = router.submit(list(TEMPLATE) + [41], 4)  # affinity r0 -> shed
        router.run_until_idle()
        assert h.status == "completed"
        assert router.placements[-1] == {"request_id": h.request_id,
                                         "replica": 1, "reason": "retry"}
        engines[1].batcher.set_shed("controller shed: sustained SLO burn")
        h2 = router.submit(list(TEMPLATE) + [42], 4)  # everyone sheds
        assert h2.status == "rejected" and h2.shed_reason == "controller"
        # validation rejections do NOT re-route (identical everywhere)
        n_place = len(router.placements)
        engines[0].batcher.clear_shed()
        engines[1].batcher.clear_shed()
        bad = router.submit([], 4)
        assert bad.status == "rejected" and bad.shed_reason is None
        assert len(router.placements) == n_place

    def test_fleet_replay_is_bitwise(self, model, draft):
        trace = generate_shared_prefix_load(
            23, 14, vocab=CFG.vocab_size, n_templates=2, prefix_len=16,
            suffix_len=(2, 6), max_new=(2, 6), shared_fraction=0.7,
            unique_len=(4, 12), mean_gap_s=0.004)

        def run():
            # the storm detector is process-global with a real-time
            # window; 2 engines x 5 jit sites per run cross its default
            # threshold at a wall-clock-dependent point — reset per run
            # (the conftest does the same per test)
            from hetu_tpu.obs import compile as obs_compile
            obs_compile.configure_storm(None)
            clock = VirtualClock()
            engines = [make_engine(model, clock, num_slots=2,
                                   sampling="top_k", top_k=5,
                                   prefix_sharing=True, draft_model=draft,
                                   spec_k=2) for _ in range(2)]
            router = FleetRouter(engines)
            jr = obs_journal.EventJournal(clock=clock)
            handles, i = [], 0
            with obs_journal.use(jr):
                while i < len(trace) or not router.idle:
                    while i < len(trace) and \
                            trace[i].submit_at <= clock.t:
                        it = trace[i]
                        handles.append(router.submit(
                            list(it.prompt), it.max_new_tokens))
                        i += 1
                    router.step()
                    clock.advance(0.001)
            streams = [(h.status, tuple(h.tokens), h.stream_fingerprint)
                       for h in handles]
            # compile events carry measured wall time (duration_s) —
            # normalize it out, the gang norm_events convention; every
            # other field (virtual ts and seq included) must be bitwise
            events = [{k: v for k, v in e.items() if k != "duration_s"}
                      for e in jr.events]
            return router.placements, streams, events

        p1, s1, j1 = run()
        p2, s2, j2 = run()
        assert p1 == p2          # identical placement sequence
        assert s1 == s2          # identical streams + fingerprints
        assert j1 == j2          # identical journal, seq/ts included
        assert any(e["kind"] == "prefix_share" for e in j1)
        assert any(e["kind"] == "router_place" for e in j1)

    def test_fleet_endpoint_smoke(self, model):
        import time as _time
        engines = [ServingEngine(model, num_slots=2, page_size=8,
                                 max_seq_len=64, prompt_buckets=(8, 16, 32),
                                 seed=11, sampling="greedy",
                                 prefix_sharing=True,
                                 clock=_time.monotonic) for _ in range(2)]
        router = FleetRouter(engines)
        srv = serve_fleet_router(router, port=0)
        try:
            url = f"http://127.0.0.1:{srv.port}"

            def post(payload):
                req = urllib.request.Request(
                    f"{url}/infer", data=json.dumps(payload).encode(),
                    method="POST")
                with urllib.request.urlopen(req, timeout=30) as r:
                    return json.loads(r.read())

            # one shared-prefix pair through the fleet front end
            r1 = post({"prompt": list(TEMPLATE) + [40],
                       "max_new_tokens": 4})
            r2 = post({"prompt": list(TEMPLATE) + [41],
                       "max_new_tokens": 4})
            assert r1["status"] == r2["status"] == "completed"
            assert len(r1["tokens"]) == 4
            with urllib.request.urlopen(f"{url}/fleet/serve",
                                        timeout=30) as r:
                stats = json.loads(r.read())
            assert stats["num_replicas"] == 2
            assert len(stats["replicas"]) == 2
            assert sum(stats["placements_by_reason"].values()) == 2
            assert stats["placements_by_reason"].get("affinity", 0) >= 1
        finally:
            srv.stop()
            router.stop()


class TestSharedPrefixLoadgen:
    def test_trace_is_deterministic(self):
        kw = dict(vocab=97, n_templates=3, prefix_len=8,
                  shared_fraction=0.6)
        a = generate_shared_prefix_load(5, 40, **kw)
        b = generate_shared_prefix_load(5, 40, **kw)
        assert a == b
        assert a != generate_shared_prefix_load(6, 40, **kw)

    def test_template_mixture(self):
        trace = generate_shared_prefix_load(
            9, 200, vocab=97, n_templates=3, prefix_len=8,
            suffix_len=(2, 4), shared_fraction=0.7, unique_len=(3, 9))
        shared = [it for it in trace if it.template is not None]
        unique = [it for it in trace if it.template is None]
        assert shared and unique
        assert abs(len(shared) / len(trace) - 0.7) < 0.1
        # all shared items of one template carry the identical prefix
        by_tid: dict = {}
        for it in shared:
            by_tid.setdefault(it.template, set()).add(it.prompt[:8])
        assert all(len(prefixes) == 1 for prefixes in by_tid.values())
        assert set(by_tid) == {0, 1, 2}
        for it in unique:
            assert 3 <= len(it.prompt) <= 9


@pytest.mark.slow
class TestFleetAcceptance:
    def test_fleet_beats_single_replica(self, model, draft):
        """The tentpole's measured win: 2 replicas + prefix sharing +
        speculation vs one bare replica on the same template-heavy
        trace — decode tokens/s and TTFT p99 from the SLO histograms.

        Measured in VIRTUAL time: one fleet tick steps every replica and
        advances the shared clock once — the N-chips deployment model,
        where replicas run in parallel.  (In this process the replicas
        necessarily timeshare one device, so wall clock would measure
        the simulation harness, not the fleet; no cell measures a fleet
        on the chip yet, ROADMAP R6.)  The SLO
        histograms are driven by the same injected clock, so TTFT p99 is
        the queueing-delay improvement of 2x admission capacity, and
        tokens/s(virtual) captures speculation's k+1-tokens-per-tick and
        sharing's suffix-only prefill."""
        trace = generate_shared_prefix_load(
            31, 20, vocab=CFG.vocab_size, n_templates=2, prefix_len=16,
            suffix_len=(2, 6), max_new=(8, 12), shared_fraction=0.8,
            unique_len=(4, 12), mean_gap_s=0.001)
        reg = obs_registry.get_registry()
        hist = reg.histogram("hetu_serve_ttft_seconds").labels()

        def run(n, **kw):
            clock = VirtualClock()
            engines = [make_engine(model, clock, num_slots=2,
                                   queue_depth=len(trace) + 1, **kw)
                       for _ in range(n)]
            router = FleetRouter(engines)
            cum0 = hist.cumulative()
            kvmod.reset_pages_written_count()
            handles, i, t0 = [], 0, clock.t
            while i < len(trace) or not router.idle:
                while i < len(trace) and trace[i].submit_at <= clock.t:
                    it = trace[i]
                    handles.append(router.submit(list(it.prompt),
                                                 it.max_new_tokens))
                    i += 1
                router.step()
                clock.advance(0.001)
            dt = clock.t - t0
            done = [h for h in handles if h.status == "completed"]
            assert len(done) == len(trace)
            tokens = sum(max(len(h.tokens) - 1, 0) for h in done)
            from hetu_tpu.obs.registry import Histogram
            p99 = Histogram.quantile_from_cumulative(
                cum0, hist.cumulative(), 0.99)
            return tokens / dt, p99, kvmod.pages_written_count()

        fleet_tps, fleet_p99, fleet_pages = run(
            2, prefix_sharing=True, draft_model=model, spec_k=3)
        single_tps, single_p99, single_pages = run(1)
        assert fleet_tps > single_tps, (fleet_tps, single_tps)
        assert fleet_p99 < single_p99, (fleet_p99, single_p99)
        # sharing's storage win rides along: fewer prefill pages written
        assert fleet_pages < single_pages, (fleet_pages, single_pages)

    def test_multi_replica_shed_freeze_chaos_replays(self, model):
        """3 replicas under mid-trace shed latches + a bucket freeze:
        every request resolves, re-routes are bounded, and the whole run
        (placements, streams, outcomes) replays bitwise."""
        trace = generate_shared_prefix_load(
            41, 18, vocab=CFG.vocab_size, n_templates=3, prefix_len=16,
            suffix_len=(2, 6), max_new=(2, 5), shared_fraction=0.6,
            unique_len=(4, 12), mean_gap_s=0.003)

        def run():
            clock = VirtualClock()
            engines = [make_engine(model, clock, num_slots=2,
                                   prefix_sharing=True)
                       for _ in range(3)]
            router = FleetRouter(engines)
            handles, i, tick = [], 0, 0
            while i < len(trace) or not router.idle:
                tick += 1
                if tick == 3:
                    engines[0].batcher.set_shed("controller shed: chaos")
                if tick == 6:
                    engines[0].batcher.clear_shed()
                    engines[1].freeze_bucket_growth = True
                if tick == 10:
                    engines[1].freeze_bucket_growth = False
                while i < len(trace) and trace[i].submit_at <= clock.t:
                    it = trace[i]
                    handles.append(router.submit(list(it.prompt),
                                                 it.max_new_tokens))
                    i += 1
                router.step()
                clock.advance(0.001)
            assert all(h.done for h in handles)
            return (router.placements,
                    [(h.status, tuple(h.tokens)) for h in handles])

        assert run() == run()
