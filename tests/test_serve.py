"""Online inference subsystem: KV-cache pool, sampling helpers, decode
parity, continuous batcher, route table, the /infer endpoint, and the
sustained-load / chaos acceptance tests.
"""

import json
import re
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu import obs
from hetu_tpu.core import set_random_seed
from hetu_tpu.exec import faults
from hetu_tpu.layers.cache import CacheSpec, gather_views, scatter_views
from hetu_tpu.layers.attention import (decode_attention,
                                       dot_product_attention,
                                       ragged_cache_update)
from hetu_tpu.models.gpt import GPT, GPTConfig
from hetu_tpu.ops.random import greedy_sample, temperature_sample, top_k_sample
from hetu_tpu.serve import (AdmissionQueueFull, ContinuousBatcher,
                            KVCachePool, OutOfPages, Request, ServingEngine,
                            generate_load, serve_engine)
from hetu_tpu.serve.kv_cache import SCRATCH_PAGE

pytestmark = pytest.mark.serve


def tiny_gpt(seed=0, **kw):
    set_random_seed(seed)
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2, num_heads=2,
                    max_seq_len=64, **kw)
    return GPT(cfg)


class VirtualClock:
    """Deterministic clock the engine tests drive by hand."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ------------------------------------------------------------ KV-cache pool

class TestKVCachePool:
    def make(self, pages=9, page=4):
        return KVCachePool(spec=CacheSpec.kv(1, 1, 2), num_pages=pages,
                           page_size=page, max_seq_len=16)

    def test_alloc_free_deterministic_lowest_first(self):
        pool = self.make()
        a = pool.alloc(10, 5)   # 2 pages
        b = pool.alloc(11, 1)   # 1 page
        assert a.pages == [1, 2] and b.pages == [3]
        assert pool.free_pages == 5
        pool.free(10)
        c = pool.alloc(12, 3)   # re-uses the lowest freed pages
        assert c.pages == [1]
        assert pool.alloc(13, 5).pages == [2, 4]

    def test_out_of_pages_is_side_effect_free(self):
        pool = self.make(pages=4)
        pool.alloc(1, 8)  # 2 of 3 usable pages
        free_before = pool.free_pages
        with pytest.raises(OutOfPages):
            pool.alloc(2, 8)
        assert pool.free_pages == free_before
        assert not pool.can_admit(8) and pool.can_admit(4)

    def test_ensure_grows_page_at_a_time(self):
        pool = self.make()
        pt = pool.alloc(5, 3)
        assert len(pt.pages) == 1
        pool.ensure(5, 4)
        assert len(pt.pages) == 1  # still fits
        pool.ensure(5, 5)
        assert len(pt.pages) == 2
        with pytest.raises(ValueError, match="max_seq_len"):
            pool.ensure(5, 17)

    def test_gather_indices_pads_with_scratch(self):
        pool = self.make()
        pool.alloc(7, 6)  # 2 pages
        idx = np.asarray(pool.gather_indices([7, None]))
        assert idx.shape == (2, 4)
        assert list(idx[0]) == [1, 2, SCRATCH_PAGE, SCRATCH_PAGE]
        assert list(idx[1]) == [SCRATCH_PAGE] * 4

    def test_gather_scatter_roundtrip(self):
        pool = self.make()
        pool.alloc(1, 16)
        idx = pool.gather_indices([1])
        kv, vv = gather_views(pool.k, pool.v, idx)
        assert kv.shape == (1, 1, 16, 1, 2)
        marked = kv.at[0, 0, 5, 0, 0].set(42.0)
        k2, v2 = scatter_views(pool.k, pool.v, idx, marked, vv)
        pool.commit(k2, v2)
        kv2, _ = gather_views(pool.k, pool.v, idx)
        assert float(kv2[0, 0, 5, 0, 0]) == 42.0

    def test_defrag_compacts_and_preserves_rows(self):
        pool = self.make(pages=11)
        for sid in (1, 2, 3):
            pool.alloc(sid, 12)  # 3 pages each; pool now fully booked
        # write a recognizable value into each sequence's view
        for sid in (1, 2, 3):
            idx = pool.gather_indices([sid])
            kv, vv = gather_views(pool.k, pool.v, idx)
            pool.commit(*scatter_views(pool.k, pool.v, idx,
                                       kv + float(sid), vv))
        pool.free(2)  # hole in the middle
        moved = pool.defrag()
        assert moved > 0
        # live pages are packed into the lowest physical indices
        live = sorted(p for sid in (1, 3) for p in pool.table(sid).pages)
        assert live == list(range(1, 7))
        assert pool.free_pages == 4
        for sid in (1, 3):
            kv, _ = gather_views(pool.k, pool.v, pool.gather_indices([sid]))
            assert np.allclose(np.asarray(kv)[:, :, :12], float(sid))
        assert pool.defrag() == 0  # idempotent once compact

    def test_validation(self):
        with pytest.raises(ValueError, match="multiple"):
            KVCachePool(spec=CacheSpec.kv(1, 1, 2), num_pages=4,
                        page_size=5, max_seq_len=16)
        pool = self.make()
        with pytest.raises(ValueError, match="max_seq_len"):
            pool.alloc(1, 17)
        pool.alloc(1, 1)
        with pytest.raises(ValueError, match="already"):
            pool.alloc(1, 1)


# ------------------------------------------------------- sampling helpers

class TestSampling:
    def test_greedy_is_argmax(self):
        logits = jnp.asarray([[0.1, 2.0, -1.0], [3.0, 0.0, 0.0]])
        assert list(np.asarray(greedy_sample(logits))) == [1, 0]
        assert greedy_sample(logits).dtype == jnp.int32

    def test_deterministic_under_fixed_key(self):
        """Property test: every draw is a pure function of (logits, key)."""
        rng = np.random.default_rng(0)
        logits = jnp.asarray(rng.standard_normal((4, 33)), jnp.float32)
        for fn in (lambda k: temperature_sample(logits, 0.8, key=k),
                   lambda k: top_k_sample(logits, 7, 0.8, key=k)):
            draws = {}
            for seed in range(8):
                key = jax.random.PRNGKey(seed)
                a, b = fn(key), fn(key)
                assert np.array_equal(np.asarray(a), np.asarray(b))
                draws[seed] = tuple(np.asarray(a))
            # different keys must not all collapse to one draw
            assert len(set(draws.values())) > 1

    def test_top_k_support(self):
        rng = np.random.default_rng(1)
        logits = jnp.asarray(rng.standard_normal((64, 20)), jnp.float32)
        top3 = np.asarray(jax.lax.top_k(logits, 3)[1])
        toks = np.asarray(top_k_sample(logits, 3, 1.5,
                                       key=jax.random.PRNGKey(4)))
        for row in range(64):
            assert toks[row] in top3[row]

    def test_top_k_larger_than_vocab_is_clamped(self):
        logits = jnp.asarray(np.random.default_rng(0).standard_normal(
            (4, 9)), jnp.float32)
        key = jax.random.PRNGKey(3)
        toks = np.asarray(top_k_sample(logits, 999, 1.0, key=key))  # no crash
        assert np.array_equal(
            toks, np.asarray(top_k_sample(logits, 9, 1.0, key=key)))
        assert ((0 <= toks) & (toks < 9)).all()

    def test_zero_temperature_collapses_to_greedy(self):
        logits = jnp.asarray([[0.0, 5.0, 1.0]])
        for fn in (temperature_sample, lambda lg, t, key: top_k_sample(
                lg, 2, t, key=key)):
            out = fn(logits, 0.0, key=jax.random.PRNGKey(0))
            assert list(np.asarray(out)) == [1]


# ------------------------------------------------- decode parity guarantees

class TestDecodeParity:
    def test_attention_incremental_matches_full(self):
        """dot_product_attention(causal) == token-by-token decode_attention
        through a ragged-offset KV cache, at fp32."""
        rng = np.random.default_rng(2)
        b, h, d, max_len = 3, 2, 4, 16
        lens = [7, 12, 3]
        q = jnp.asarray(rng.standard_normal((b, max_len, h, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, max_len, h, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, max_len, h, d)), jnp.float32)
        full = np.asarray(dot_product_attention(q, k, v, causal=True))
        k_cache = jnp.zeros((b, max_len, h, d))
        v_cache = jnp.zeros((b, max_len, h, d))
        got = np.zeros_like(full)
        for t in range(max_len):
            # ragged: row i stops appending at lens[i]; later steps re-run
            # earlier positions to exercise differing cache offsets
            offs = jnp.asarray([min(t, n - 1) for n in lens], jnp.int32)
            kn = jnp.stack([k[i, int(offs[i])][None] for i in range(b)])
            vn = jnp.stack([v[i, int(offs[i])][None] for i in range(b)])
            qn = jnp.stack([q[i, int(offs[i])][None] for i in range(b)])
            k_cache = ragged_cache_update(k_cache, kn, offs)
            v_cache = ragged_cache_update(v_cache, vn, offs)
            out = np.asarray(decode_attention(qn, k_cache, v_cache, offs))
            for i in range(b):
                got[i, int(offs[i])] = out[i, 0]
        for i, n in enumerate(lens):
            np.testing.assert_allclose(got[i, :n], full[i, :n],
                                       rtol=1e-5, atol=1e-5)

    def test_gpt_prefill_plus_incremental_matches_full(self):
        """Ragged batched prefill + one-token decode steps reproduce the
        full forward logits (fp32 allclose) at every generated position."""
        m = tiny_gpt()
        cfg = m.config
        rng = np.random.default_rng(3)
        lens = [5, 9, 2]
        prompts = [rng.integers(0, cfg.vocab_size, n) for n in lens]
        b, max_len, h = 3, 32, cfg.num_heads
        hd = cfg.hidden_size // h
        bucket = 16
        toks = np.zeros((b, bucket), np.int32)
        for i, p in enumerate(prompts):
            toks[i, :len(p)] = p
        kv = [(jnp.zeros((b, max_len, h, hd)),
               jnp.zeros((b, max_len, h, hd))) for _ in range(cfg.num_layers)]
        logits, kv = m(jnp.asarray(toks), kv_cache=kv,
                       cache_index=jnp.zeros(b, jnp.int32),
                       seq_lengths=jnp.asarray(lens, jnp.int32))
        seqs = [list(p) for p in prompts]
        full = jax.jit(lambda ids: m(ids))   # eager op-by-op took 30 s
        for step in range(4):
            nxt = np.asarray(greedy_sample(logits))
            for i in range(b):
                seqs[i].append(int(nxt[i]))
            # reference: full forward over each row's entire sequence
            for i in range(b):
                ref = np.asarray(full(jnp.asarray(seqs[i])[None, :]))
                np.testing.assert_allclose(
                    np.asarray(logits)[i], ref[0, len(seqs[i]) - 2],
                    rtol=1e-5, atol=1e-5)
            offs = jnp.asarray([len(s) - 1 for s in seqs], jnp.int32)
            logits, kv = m(jnp.asarray(nxt[:, None]), kv_cache=kv,
                           cache_index=offs)


# ------------------------------------------------ read-only embedding cache

class TestReadOnlyCache:
    def test_push_raises_sync_serves(self):
        from hetu_tpu.embed.engine import CacheTable, HostEmbeddingTable
        table = HostEmbeddingTable(32, 4, optimizer="adam", seed=2)
        ro = CacheTable(table, 8, name="serve-ro", read_only=True)
        rows = ro.sync([1, 2, 3])
        assert rows.shape == (3, 4)
        np.testing.assert_allclose(rows, table.pull([1, 2, 3]))
        with pytest.raises(RuntimeError, match="read-only"):
            ro.push([1], np.ones((1, 4), np.float32))
        from hetu_tpu.embed.engine import AsyncEngine
        with pytest.raises(RuntimeError, match="read-only"):
            AsyncEngine(1).push_async(ro, [1], np.ones((1, 4), np.float32))
        ro.flush()  # empty buffer, must not raise
        st = ro.stats()
        assert st["misses"] >= 3
        # a writable cache over the same table still trains
        rw = CacheTable(table, 8, name="serve-rw")
        rw.push([1], np.ones((1, 4), np.float32))
        rw.flush()

    def test_mark_read_only_flushes_buffered_pushes_first(self):
        """A model that trained with push_bound buffering must have its
        pending gradient writebacks applied BEFORE the freeze — flipping
        read_only must not silently drop the tail of training."""
        from hetu_tpu.core.module import Module
        from hetu_tpu.embed import StagedHostEmbedding
        emb = StagedHostEmbedding(16, 4, cache_capacity=8, push_bound=10,
                                  optimizer="sgd", lr=1.0, seed=7)
        emb.stage([1, 2])
        before = emb.table.pull([1, 2]).copy()
        emb.push_grads(np.ones((2, 4), np.float32))  # buffered, not applied
        np.testing.assert_allclose(emb.table.pull([1, 2]), before)

        class Wrap(Module):
            def __init__(self):
                self.embed = emb

        ServingEngine(tiny_gpt(), num_slots=1, page_size=8, max_seq_len=32,
                      ctr_model=Wrap())
        # the freeze drained the buffer: sgd applied lr * grad = 1.0
        np.testing.assert_allclose(emb.table.pull([1, 2]), before - 1.0,
                                   rtol=1e-6)
        assert emb.store.read_only is True

    def test_engine_marks_ctr_stores_read_only(self):
        from hetu_tpu.models.ctr import CTRConfig, WideDeep
        set_random_seed(0)
        ctr = WideDeep(CTRConfig(
            dense_dim=4, sparse_fields=3, vocab=50, embed_dim=4,
            mlp_hidden=16, embedding="host", host_bridge="staged",
            cache_capacity=16))
        assert ctr.embed.store.read_only is False
        eng = ServingEngine(tiny_gpt(), num_slots=2, page_size=8,
                            max_seq_len=32, ctr_model=ctr)
        assert ctr.embed.store.read_only is True
        pred = eng.infer_ctr(np.zeros((2, 4), np.float32),
                             [[1, 2, 3], [4, 5, 6]])
        assert pred.shape == (2,) and np.all((pred > 0) & (pred < 1))
        with pytest.raises(RuntimeError, match="read-only"):
            ctr.embed.store.push([1], np.zeros((1, 4), np.float32))


# ------------------------------------------------------ obs route table

class TestRoutes:
    def test_custom_route_registration(self):
        from hetu_tpu.obs.server import Routes, RoutedHTTPServer
        routes = Routes()
        routes.add("GET", "/ping", lambda q, b: b'{"pong": true}')
        routes.add("POST", "/echo", lambda q, b: (b, "text/plain"))
        routes.add("GET", "/boom", lambda q, b: 1 / 0)
        with RoutedHTTPServer(routes) as srv:
            srv.start()
            with urllib.request.urlopen(srv.url + "/ping", timeout=10) as r:
                assert json.loads(r.read())["pong"] is True
            req = urllib.request.Request(srv.url + "/echo", data=b"hello",
                                         method="POST")
            with urllib.request.urlopen(req, timeout=10) as r:
                assert r.read() == b"hello"
            for path, code in (("/nope", 404), ("/ping", 405), ("/boom", 500)):
                try:
                    if code == 405:
                        urllib.request.urlopen(urllib.request.Request(
                            srv.url + path, data=b"", method="POST"),
                            timeout=10)
                    else:
                        urllib.request.urlopen(srv.url + path, timeout=10)
                    pytest.fail(f"expected HTTP {code} for {path}")
                except urllib.error.HTTPError as e:
                    assert e.code == code
                    if code == 500:
                        assert "division" in json.loads(
                            e.read())["error"]
        assert "/ping" in routes.paths()

    def test_telemetry_routes_still_served(self):
        with obs.serve() as srv:
            with urllib.request.urlopen(srv.url + "/healthz",
                                        timeout=10) as r:
                assert json.loads(r.read())["status"] == "ok"


# ---------------------------------------------------------------- batcher

class TestBatcher:
    def req(self, i, plen=4, now=0.0, deadline=None, max_new=4):
        return Request(id=i, prompt=list(range(plen)), max_new_tokens=max_new,
                       arrival=now, deadline_s=deadline)

    def test_queue_depth_limit(self):
        b = ContinuousBatcher(1, queue_depth=2)
        b.submit(self.req(0))
        b.submit(self.req(1))
        with pytest.raises(AdmissionQueueFull):
            b.submit(self.req(2))

    def test_fifo_admission_and_recycle(self):
        b = ContinuousBatcher(2, queue_depth=8)
        for i in range(4):
            b.submit(self.req(i))
        tick = b.poll(0.0)
        assert [r.id for r in tick.admitted] == [0, 1]
        assert b.active_slots == 2 and b.queue_len == 2
        assert b.finish(0).id == 0
        tick = b.poll(0.0)
        assert [r.id for r in tick.admitted] == [2]
        assert [s for s, _ in b.active()] == [0, 1]
        assert b.active()[0][1].id == 2  # recycled into the freed slot

    def test_deadline_expiry_and_capacity_gate(self):
        b = ContinuousBatcher(1, queue_depth=8)
        b.submit(self.req(0))
        b.submit(self.req(1, deadline=0.5))
        b.submit(self.req(2))
        tick = b.poll(0.0)
        assert [r.id for r in tick.admitted] == [0]
        tick = b.poll(1.0)  # slot busy; request 1 blows its deadline
        assert [r.id for r in tick.expired] == [1]
        b.finish(0)
        # FIFO preserved under a capacity gate: request 2 refused -> stop
        tick = b.poll(1.0, can_admit=lambda r: False)
        assert tick.admitted == [] and b.queue_len == 1

    def test_bucket_for(self):
        b = ContinuousBatcher(1, prompt_buckets=(8, 32))
        assert b.bucket_for(3) == 8 and b.bucket_for(8) == 8
        assert b.bucket_for(9) == 32
        with pytest.raises(ValueError, match="largest bucket"):
            b.bucket_for(33)


# ------------------------------------------------- engine scheduling paths

class TestEngineScheduling:
    def test_rejection_and_deadline_telemetry(self):
        reg = obs.get_registry()
        clk = VirtualClock()
        journal = obs.EventJournal()
        m = tiny_gpt()
        with obs.use(journal):
            eng = ServingEngine(m, num_slots=1, page_size=8, max_seq_len=32,
                                prompt_buckets=(8,), queue_depth=1,
                                seed=0, clock=clk)
            s0 = reg.snapshot()
            running = eng.submit([1, 2, 3], 24)        # occupies the slot
            eng.step()
            waiting = eng.submit([4, 5], 4, deadline_s=0.5)  # queued
            overflow = eng.submit([6], 4)              # queue full -> reject
            assert overflow.done and overflow.status == "rejected"
            clk.advance(1.0)                           # waiting one expires
            eng.step()
            assert waiting.done and waiting.status == "expired"
            eng.run_until_idle()
            assert running.status == "completed"
            d = reg.delta(reg.snapshot(), s0)
        assert d['hetu_serve_requests_total{outcome="rejected"}'] == 1
        assert d['hetu_serve_requests_total{outcome="expired"}'] == 1
        # only the running request ever reached a slot
        assert d['hetu_serve_requests_total{outcome="admitted"}'] == 1
        assert d['hetu_serve_requests_total{outcome="completed"}'] == 1
        kinds = [e["kind"] for e in journal.events]
        assert "serve_reject" in kinds and "request_expired" in kinds
        rej = journal.of_kind("serve_reject")[0]
        assert rej["request_id"] == overflow.request_id
        exp = journal.of_kind("request_expired")[0]
        assert exp["stage"] == "queued" and exp["waited_s"] >= 0.5
        # the deadline satellite: expiries are counted by stage, not
        # silently dropped, and the handle names why it failed
        assert d['hetu_serve_deadline_expired_total{stage="queued"}'] == 1
        assert waiting.error is not None and "deadline" in waiting.error
        assert overflow.error is not None  # rejection reason rides too

    def test_eos_recycles_slot_early(self):
        m = tiny_gpt()
        clk = VirtualClock()
        # probe: discover the greedy continuation to use as EOS
        probe = ServingEngine(m, num_slots=1, page_size=8, max_seq_len=32,
                              prompt_buckets=(8,), clock=clk)
        h = probe.submit([1, 2, 3], 3)
        probe.run_until_idle()
        eos = h.tokens[0]
        eng = ServingEngine(m, num_slots=1, page_size=8, max_seq_len=32,
                            prompt_buckets=(8,), eos_id=eos, clock=clk)
        h2 = eng.submit([1, 2, 3], 24)
        eng.run_until_idle()
        assert h2.status == "completed"
        assert h2.tokens[-1] == eos and len(h2.tokens) < 24
        assert eng.pool.live_sequences == 0  # pages freed on EOS

    def test_too_long_prompt_rejected(self):
        eng = ServingEngine(tiny_gpt(), num_slots=1, page_size=8,
                            max_seq_len=32, prompt_buckets=(8, 32))
        h = eng.submit(list(range(30)), 8)  # 30 + 8 > 32
        assert h.done and h.status == "rejected"
        # a prompt over the largest prefill bucket must be rejected at
        # submit, not crash the scheduler at bucket_for()
        m = tiny_gpt()
        eng = ServingEngine(m, num_slots=1, page_size=8, max_seq_len=64,
                            prompt_buckets=(8,))
        h = eng.submit(list(range(20)), 4)  # 24 <= 64 but bucket max is 8
        assert h.done and h.status == "rejected"
        ok = eng.submit([1, 2, 3], 2)
        eng.run_until_idle()  # the loop survived and serves the next one
        assert ok.status == "completed"

    def test_invalid_sampling_mode_raises(self):
        with pytest.raises(ValueError, match="sampling mode"):
            ServingEngine(tiny_gpt(), sampling="nucleus")
        with pytest.raises(ValueError, match="top_k must be"):
            ServingEngine(tiny_gpt(), sampling="top_k", top_k=0)

    def test_nonpositive_token_budget_rejected(self):
        eng = ServingEngine(tiny_gpt(), num_slots=1, page_size=8,
                            max_seq_len=32, prompt_buckets=(8,))
        for bad in (0, -3):
            h = eng.submit([1, 2], bad)
            assert h.done and h.status == "rejected" and h.tokens == []

    def test_temperature_mode_is_not_topk_truncated(self):
        """sampling='temperature' must draw from the full distribution,
        not a silently top-k-truncated one."""
        m = tiny_gpt()

        def collect(mode):
            eng = ServingEngine(m, num_slots=2, page_size=8, max_seq_len=64,
                                prompt_buckets=(8,), sampling=mode, top_k=1,
                                temperature=3.0, seed=0)
            hs = [eng.submit([i + 1, i + 2], 8) for i in range(8)]
            eng.run_until_idle()
            return [t for h in hs for t in h.tokens]

        # top_k=1 at any temperature is greedy-like: few distinct tokens;
        # full-temperature sampling at T=3 must show more diversity
        assert len(set(collect("temperature"))) > len(set(collect("top_k")))

    def test_overcommitted_pool_evicts_instead_of_wedging(self):
        """With num_pages below full per-slot capacity (explicit
        overcommit), decode growth past the pool retires the victim with
        the tokens it has ('evicted') instead of killing the loop."""
        m = tiny_gpt()
        # 2 slots x (32/8)=4 pages full capacity = 8+scratch; give only 6
        eng = ServingEngine(m, num_slots=2, page_size=8, max_seq_len=32,
                            prompt_buckets=(8,), num_pages=7, seed=0)
        h1 = eng.submit([1, 2, 3, 4, 5, 6, 7], 24)   # wants 31 tokens
        h2 = eng.submit([8, 9, 10, 11, 12, 13], 24)  # wants 30 tokens
        eng.run_until_idle()
        statuses = sorted([h1.status, h2.status])
        assert "evicted" in statuses           # somebody hit the wall...
        assert eng.pool.live_sequences == 0    # ...and everything drained
        for h in (h1, h2):
            assert h.done and len(h.tokens) > 0


# ------------------------------------------------- the tick's phase spans

PHASES = ["serve.tick.schedule", "serve.tick.decode.build",
          "serve.tick.decode.device", "serve.tick.collect.device",
          "serve.tick.emit", "serve.tick.publish"]


def traced_engine(budget=4, steps=None, **kw):
    """(engine, spans) of two requests served under a recording tracer on
    the virtual clock: ``steps`` ticks, or until idle."""
    tracer = obs.get_tracer()
    tracer.reset()
    kw.setdefault("num_slots", 2)
    eng = ServingEngine(tiny_gpt(), page_size=8, max_seq_len=32,
                        prompt_buckets=(8,), seed=0, clock=VirtualClock(),
                        **kw)
    with tracer.collect():
        hs = [eng.submit([1, 2, 3], budget), eng.submit([4, 5], budget)]
        if steps is None:
            eng.run_until_idle()
        else:
            for _ in range(steps):
                eng.step()
    spans = tracer.spans
    tracer.reset()
    return eng, hs, spans


def children_of(spans, parent):
    return sorted((s for s in spans if s.parent_id == parent.span_id),
                  key=lambda s: s.start)


class TestTickSpans:
    def test_one_tick_span_a_step_and_its_phases_in_order(self):
        eng, hs, spans = traced_engine(steps=2)
        ticks = [s for s in spans if s.name == "serve.tick"]
        assert len(ticks) == 2 and all(t.parent_id is None for t in ticks)
        first, second = (children_of(spans, t) for t in ticks)
        # the tick that admits prefills once a request, the next only decodes
        assert [s.name for s in first] == PHASES[:1] + \
            ["serve.tick.prefill"] * 2 + PHASES[1:]
        assert [s.name for s in second] == PHASES
        assert ticks[0].attrs == {"tick": 1, "active": 2, "admitted": 2,
                                  "produced": 2}
        assert ticks[1].attrs == {"tick": 2, "active": 2, "admitted": 0,
                                  "produced": 2}
        emit = [s for s in spans if s.name == "serve.tick.emit"]
        assert [s.attrs["tokens"] for s in emit] == [2, 2]

    def test_children_lie_inside_their_tick_and_do_not_overlap(self):
        eng, hs, spans = traced_engine()
        ticks = [s for s in spans if s.name == "serve.tick"]
        assert ticks
        for t in ticks:
            kids = children_of(spans, t)
            assert kids[0].start >= t.start and kids[-1].end_time <= t.end_time
            for a, b in zip(kids, kids[1:]):
                assert a.end_time <= b.start

    def test_one_prefill_span_a_request_admitted(self):
        eng, hs, spans = traced_engine()
        pre = [s for s in spans if s.name == "serve.tick.prefill"]
        assert [s.attrs for s in pre] == [
            {"request_id": h.request_id, "prompt_len": n, "bucket": 8,
             "shared_tokens": 0} for h, n in zip(hs, (3, 2))]
        for p in pre:
            # the dispatch, then (stepped by hand: in turn) the fetch
            dev, fetch = children_of(spans, p)
            assert dev.name == "serve.tick.prefill.device"
            assert fetch.name == "serve.tick.collect.device"
            assert p.start <= dev.start and fetch.end_time <= p.end_time

    @pytest.mark.parametrize("budget", [3, 9])
    def test_no_span_is_added_per_token(self, budget):
        eng, hs, spans = traced_engine(budget=budget)
        assert [len(h.tokens) for h in hs] == [budget, budget]
        ticks = sum(1 for s in spans if s.name == "serve.tick")
        # the first tick makes two tokens a request (prefill, then decode)
        assert ticks == budget - 1
        # a tick, its six phases; a prefill, its dispatch and its fetch a
        # request; a wait a submission: nothing a token
        ours = [s for s in spans if s.name.startswith("serve.")]
        assert len(ours) == 7 * ticks + 3 * 2 + 2
        assert not [s for s in spans if s.name == "serve.decode"]

    def test_submit_wait_is_a_root_on_the_submitting_thread(self):
        import threading
        import time
        tracer = obs.get_tracer()
        tracer.reset()
        eng = ServingEngine(tiny_gpt(), num_slots=1, page_size=8,
                            max_seq_len=32, prompt_buckets=(8,), seed=0,
                            clock=VirtualClock())
        done = []
        with tracer.collect():
            with tracer.span("driver"):
                with eng._lock:      # a tick in flight holds the lock
                    t = threading.Thread(
                        target=lambda: done.append(eng.submit([1, 2], 2)))
                    t.start()
                    time.sleep(0.05)
            t.join(10)
        assert not t.is_alive() and not done[0].done
        wait, = [s for s in tracer.spans if s.name == "serve.submit.wait"]
        driver, = [s for s in tracer.spans if s.name == "driver"]
        assert wait.parent_id is None and wait.trace_id != driver.trace_id
        assert wait.duration >= 0.04
        tracer.reset()

    def test_a_tick_that_does_no_work_has_no_span(self):
        tracer = obs.get_tracer()
        tracer.reset()
        eng = ServingEngine(tiny_gpt(), num_slots=1, page_size=8,
                            max_seq_len=32, prompt_buckets=(8,), seed=0,
                            clock=VirtualClock(), prefill_tick_cost=0.5)
        with tracer.collect():
            eng.submit([1, 2, 3], 2)
            eng.hang(1)
            eng.step()                       # hung
            assert not tracer.spans[1:]      # the submission's wait only
            eng.step()                       # prefill: charges 4 ticks
            busy = eng._busy_ticks
            assert busy == 3
            for _ in range(busy):
                eng.step()                   # virtually busy
            eng.crash()
            eng.step()                       # crashed
        assert sum(1 for s in tracer.spans if s.name == "serve.tick") == 1
        tracer.reset()


# -------------------------------------------------- /infer endpoint smoke

def _valid_prom_line(line):
    comment = re.compile(r"^# (HELP [a-zA-Z_:][a-zA-Z0-9_:]* .*"
                         r"|TYPE [a-zA-Z_:][a-zA-Z0-9_:]* "
                         r"(counter|gauge|histogram|summary|untyped))$")
    sample = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
        r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\.)*"'
        r'(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\.)*")*\})?'
        r' (-?\d+(\.\d+)?([eE][+-]?\d+)?|[+-]Inf|NaN)$')
    return bool(comment.match(line) or sample.match(line))


def test_infer_endpoint_live_engine():
    """Satellite smoke: /infer against a live ServingEngine on a tiny GPT,
    response fields validated, and the shared-port /metrics exposition
    line-validated — the serving mirror of test_obs's /metrics smoke."""
    eng = ServingEngine(tiny_gpt(), num_slots=2, page_size=8, max_seq_len=32,
                        prompt_buckets=(8, 16), seed=1)
    srv = serve_engine(eng)
    try:
        body = json.dumps({"prompt": [5, 6, 7], "max_new_tokens": 4,
                           "timeout_s": 120}).encode()
        req = urllib.request.Request(srv.url + "/infer", data=body,
                                     method="POST")
        with urllib.request.urlopen(req, timeout=120) as r:
            assert r.status == 200
            out = json.loads(r.read())
        assert out["status"] == "completed"
        assert len(out["tokens"]) == 4
        assert all(0 <= t < 97 for t in out["tokens"])
        assert out["ttft_s"] >= 0 and out["latency_s"] >= out["ttft_s"]
        with urllib.request.urlopen(srv.url + "/stats", timeout=10) as r:
            stats = json.loads(r.read())
        assert stats["active_slots"] == 0
        assert stats["pool"]["pages_used"] == 0
        assert any(k.startswith("hetu_serve_requests_total")
                   for k in stats["metrics"])
        # SLO summary: TTFT quantiles through Histogram.quantile — the
        # request above observed at least one TTFT, so p50 <= p99
        slo = stats["slo"]
        assert set(slo) == {"ttft_p50_s", "ttft_p99_s",
                            "token_latency_p50_s", "token_latency_p99_s"}
        assert slo["ttft_p50_s"] is not None
        assert slo["ttft_p50_s"] <= slo["ttft_p99_s"]
        with urllib.request.urlopen(srv.url + "/metrics", timeout=10) as r:
            text = r.read().decode()
        for line in text.splitlines():
            assert _valid_prom_line(line), f"invalid exposition: {line!r}"
        assert "hetu_serve_ttft_seconds_bucket" in text
        assert 'hetu_serve_requests_total{outcome="completed"}' in text
    finally:
        srv.stop()
        eng.stop()


# ------------------------------------------------ sustained-load acceptance

def _run_trace(model, trace, seed, **engine_kw):
    """Drive a full load trace on a virtual clock; returns (token streams,
    handle statuses, registry delta, pool)."""
    reg = obs.get_registry()
    clk = VirtualClock()
    eng = ServingEngine(model, seed=seed, clock=clk, **engine_kw)
    s0 = reg.snapshot()
    handles, i = {}, 0
    while i < len(trace) or not eng.batcher.idle:
        while i < len(trace) and trace[i].submit_at <= clk.t:
            handles[i] = eng.submit(list(trace[i].prompt),
                                    trace[i].max_new_tokens,
                                    deadline_s=trace[i].deadline_s)
            i += 1
        eng.step()
        clk.advance(0.001)
    streams = {j: tuple(h.tokens) for j, h in handles.items()}
    status = {j: h.status for j, h in handles.items()}
    return streams, status, reg.delta(reg.snapshot(), s0), eng.pool


def test_sustained_load_acceptance():
    """Acceptance: >= 64 seeded concurrent requests with mixed prompt
    lengths through the continuous batcher — zero dropped, exact obs
    counters, and token streams bitwise-identical across two same-seed
    runs (defrag running underneath)."""
    model = tiny_gpt()
    trace = generate_load(17, 64, vocab=97, prompt_len=(2, 20),
                          max_new=(1, 8), mean_gap_s=0.0005)
    assert len({len(t.prompt) for t in trace}) > 5  # genuinely mixed
    kw = dict(num_slots=8, page_size=8, max_seq_len=64,
              prompt_buckets=(8, 16, 32), queue_depth=64,
              sampling="top_k", top_k=5, defrag_every=5)
    streams1, status1, d1, pool1 = _run_trace(model, trace, seed=11, **kw)
    streams2, status2, d2, pool2 = _run_trace(model, trace, seed=11, **kw)

    # zero dropped requests
    assert len(status1) == 64
    assert set(status1.values()) == {"completed"}
    # exact accounting: every admitted request completed, nothing else
    for d in (d1, d2):
        assert d['hetu_serve_requests_total{outcome="admitted"}'] == 64
        assert d['hetu_serve_requests_total{outcome="completed"}'] == 64
        assert d.get('hetu_serve_requests_total{outcome="rejected"}', 0) == 0
        assert d.get('hetu_serve_requests_total{outcome="expired"}', 0) == 0
        assert d["hetu_serve_tokens_total"] == sum(
            len(s) for s in streams1.values())
    # every request got exactly its token budget (no EOS configured)
    for j, item in enumerate(trace):
        assert len(streams1[j]) == item.max_new_tokens
    # bitwise-identical streams across same-seed runs
    assert streams1 == streams2
    # and the pool drained completely both times
    assert pool1.live_sequences == 0 and pool2.live_sequences == 0
    assert pool1.free_pages == pool1.num_pages - 1

    # a different sampling seed must actually change some stream (the
    # determinism above is seed-derived, not an accident of greedy ties)
    streams3, _, _, _ = _run_trace(model, trace, seed=12, **kw)
    assert streams3 != streams1


@pytest.mark.chaos
def test_ctr_chaos_ps_timeout_is_counted_retry():
    """Chaos acceptance: an injected PS socket kill during read-only CTR
    serving surfaces as exactly one counted redial — and the predictions
    are bitwise identical to the clean run's."""
    from hetu_tpu.embed.net import EmbeddingServer, RemoteHostEmbedding
    from hetu_tpu.layers import Linear
    from hetu_tpu.core.module import Module
    reg = obs.get_registry()

    rng = np.random.default_rng(5)
    dense = np.asarray(rng.standard_normal((6, 4)), np.float32)
    sparse = rng.integers(0, 60, (6, 3))

    def run(table_id, plan_events):
        with EmbeddingServer() as srv:
            set_random_seed(0)

            class M(Module):
                def __init__(self):
                    self.embed = RemoteHostEmbedding(
                        60, 4, servers=[f"127.0.0.1:{srv.port}"],
                        table_id=table_id, seed=5, reconnect_attempts=5,
                        reconnect_backoff=0.01)
                    self.head = Linear(12, 1)

                def logits(self, d, sp):
                    e = self.embed(sp).reshape(sp.shape[0], -1)
                    return self.head(e)[:, 0]

            m = M()
            eng = ServingEngine(tiny_gpt(), num_slots=1, page_size=8,
                                max_seq_len=32, ctr_model=m)
            s0 = reg.snapshot()
            preds = []
            with faults.inject(faults.FaultPlan(plan_events)) as plan:
                for step in range(1, 4):
                    plan.advance(step)
                    preds.append(eng.infer_ctr(dense, sparse))
                assert plan.remaining() == []
            return np.stack(preds), reg.delta(reg.snapshot(), s0)

    clean, d_clean = run(901, [])
    chaos, d_chaos = run(902, [(2, "ps_socket_kill")])

    # the timeout surfaced as a counted retry...
    redials = sum(v for k, v in d_chaos.items()
                  if k.startswith("hetu_ps_redials_total"))
    dead = sum(v for k, v in d_chaos.items()
               if k.startswith('hetu_ps_rpc_errors_total{type="dead_socket"'))
    assert redials == 1 and dead == 1
    assert sum(v for k, v in d_clean.items()
               if k.startswith("hetu_ps_redials_total")) == 0
    # ...not a wrong answer
    np.testing.assert_array_equal(clean, chaos)
    assert d_chaos["hetu_serve_ctr_requests_total"] == 3
