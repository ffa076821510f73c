"""Disaggregated prefill/decode serving tests (serve/fleet/disagg.py +
serve/fleet/migrate.py + the kv_cache export-hold machinery).

Tier-1: the export/free-race pool contract (holds, DoubleFree on a
double settle, counters asserted through ``stats()``), migration-record
integrity (torn / page CRC / fingerprint / geometry — each a named
diagnosis, unit-level and end-to-end through an engine pair with the
stream still bitwise correct), the 1-prefill + 1-decode in-process
smoke, bitwise stream equality disagg-vs-colocated, full same-seed
replay (placements + migration journal + streams), the prefill-burst
loadgen satellite, and the virtual-time acceptance A/B (disagg beats
colocated on TTFT p99 without losing tokens/s at equal chips).  The
multi-process file-fabric chaos run rides the slow tier.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from hetu_tpu.core import set_random_seed
from hetu_tpu.layers import CacheSpec
from hetu_tpu.models import GPT
from hetu_tpu.models.gpt import GPTConfig
from hetu_tpu.obs import journal as obs_journal
from hetu_tpu.obs import registry as obs_registry
from hetu_tpu.obs.registry import Histogram
from hetu_tpu.serve import (DisaggRouter, DoubleFree, KVCachePool,
                            MigrationFileFabric, MigrationIntegrityError,
                            ServingEngine, generate_prefill_burst_load)
from hetu_tpu.serve.fleet import migrate as migrate_mod

pytestmark = [pytest.mark.serve, pytest.mark.disagg]

CFG = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2, num_heads=2,
                max_seq_len=64)


@pytest.fixture(scope="module")
def model():
    set_random_seed(0)
    return GPT(CFG)


class VirtualClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def make_engine(model, clock, role="colocated", **kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("prompt_buckets", (8, 16, 32))
    kw.setdefault("seed", 11)
    kw.setdefault("sampling", "greedy")
    return ServingEngine(model, clock=clock, role=role, **kw)


def drain(router, clock, max_steps: int = 5000) -> int:
    for i in range(max_steps):
        if router.idle:
            return i
        router.step()
        clock.advance(0.001)
    raise AssertionError(f"not idle after {max_steps} ticks")


def tiny_pool(**kw) -> KVCachePool:
    kw["spec"] = CacheSpec.kv(1, kw.pop("num_heads", 1), 2)
    kw.setdefault("num_pages", 8)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_seq_len", 16)
    return KVCachePool(**kw)


def seeded_pool(seed=3, n_tokens=10, **kw):
    """A tiny pool with one allocated sequence whose pages hold seeded
    values (so payload equality is a real check, not zeros == zeros)."""
    rng = np.random.default_rng(seed)
    pool = tiny_pool(**kw)
    pt = pool.alloc(0, n_tokens)
    for p in pt.pages:
        pool.commit(
            pool.k.at[:, p].set(
                rng.standard_normal(pool.k.shape[2:]).astype(np.float32)),
            pool.v.at[:, p].set(
                rng.standard_normal(pool.v.shape[2:]).astype(np.float32)))
    pt.length = n_tokens
    return pool, pt


class TestExportHold:
    def test_export_free_race_is_closed(self):
        """The satellite contract: free() of a sequence with an
        outstanding export keeps its pages OFF the free list until the
        import acks."""
        pool, pt = seeded_pool()
        pages = list(pt.pages)
        rec = pool.export_pages(0)
        assert rec.num_pages == len(pages)
        s = pool.stats()
        assert s["exported_pages"] == len(pages)
        assert s["pages_export_held"] == len(pages)
        assert s["exports_outstanding"] == 1
        pool.free(0)
        # the race: without the hold these pages would be reallocatable
        s = pool.stats()
        assert s["pages_free"] == pool.num_pages - 1 - len(pages)
        for p in pages:
            assert pool.refcount(p) == 1  # the export hold alone
        pool.ack_export(0)
        s = pool.stats()
        assert s["pages_free"] == pool.num_pages - 1
        assert s["pages_export_held"] == 0
        assert s["exports_outstanding"] == 0

    def test_cancel_export_releases_and_double_settle_raises(self):
        pool, _ = seeded_pool()
        pool.export_pages(0)
        pool.cancel_export(0)
        with pytest.raises(DoubleFree):
            pool.ack_export(0)
        with pytest.raises(DoubleFree):
            pool.cancel_export(0)
        pool.free(0)
        assert pool.stats()["pages_free"] == pool.num_pages - 1

    def test_one_outstanding_export_per_sequence(self):
        pool, _ = seeded_pool()
        pool.export_pages(0)
        with pytest.raises(ValueError, match="outstanding export"):
            pool.export_pages(0)
        pool.ack_export(0)
        pool.export_pages(0)  # settled: a new export is legal
        pool.cancel_export(0)
        pool.free(0)

    def test_defrag_pins_export_held_pages(self):
        pool, pt = seeded_pool(num_pages=12)
        held = list(pt.pages)
        want_k = [np.asarray(pool.k[:, p]) for p in held]
        pool.export_pages(0)
        pool.free(0)
        other = pool.alloc(1, 8)
        pool.defrag()
        # export-held pages never moved: their bytes are still at the
        # physical indices the (already snapshotted) record named
        for p, want in zip(held, want_k):
            assert pool.refcount(p) == 1
            np.testing.assert_array_equal(np.asarray(pool.k[:, p]), want)
        pool.ack_export(0)
        pool.free(1)
        assert pool.stats()["pages_free"] == pool.num_pages - 1
        assert other is not None

    def test_import_round_trip_is_bitwise(self):
        pool, pt = seeded_pool(n_tokens=10)
        rec = pool.export_pages(0)
        dst = tiny_pool()
        new = dst.import_pages(rec, seq_id=5)
        assert new.length == 10
        assert dst.stats()["imported_pages"] == len(new.pages)
        for i, (sp, dp) in enumerate(zip(pt.pages, new.pages)):
            np.testing.assert_array_equal(np.asarray(pool.k[:, sp]),
                                          np.asarray(dst.k[:, dp]))
            np.testing.assert_array_equal(np.asarray(pool.v[:, sp]),
                                          np.asarray(dst.v[:, dp]))
        pool.ack_export(0)
        pool.free(0)
        dst.free(5)


class TestRecordIntegrity:
    def _record(self):
        pool, _ = seeded_pool()
        rec = pool.export_pages(0)
        pool.cancel_export(0)
        return rec

    def test_verify_passes_clean(self):
        migrate_mod.verify_record(self._record())

    def test_corrupt_payload_is_page_crc(self):
        rec = self._record()
        rec.k_pages = np.array(rec.k_pages)
        rec.k_pages[0, 1].flat[0] += 1.0
        with pytest.raises(MigrationIntegrityError, match="page 1") as e:
            migrate_mod.verify_record(rec)
        assert e.value.reason == "page_crc"

    def test_corrupt_crc_sidecar_is_page_crc(self):
        rec = self._record()
        rec.page_crcs[0] ^= 0x1
        with pytest.raises(MigrationIntegrityError) as e:
            migrate_mod.verify_record(rec)
        assert e.value.reason == "page_crc"

    def test_corrupt_fingerprint_is_fingerprint(self):
        rec = self._record()
        rec.fingerprint ^= 0x1
        with pytest.raises(MigrationIntegrityError) as e:
            migrate_mod.verify_record(rec)
        assert e.value.reason == "fingerprint"

    def test_tampered_length_is_fingerprint(self):
        # the decode cursor is metadata the per-page CRCs do not cover:
        # the content fingerprint must catch it
        rec = self._record()
        rec.length += 1
        with pytest.raises(MigrationIntegrityError) as e:
            migrate_mod.verify_record(rec)
        assert e.value.reason == "fingerprint"

    def test_truncated_bytes_are_torn(self):
        rec = self._record()
        data = rec.to_bytes()
        with pytest.raises(MigrationIntegrityError) as e:
            migrate_mod.MigrationRecord.from_bytes(data[:-7])
        assert e.value.reason == "torn"
        with pytest.raises(MigrationIntegrityError) as e:
            migrate_mod.MigrationRecord.from_bytes(data[:10])
        assert e.value.reason == "torn"

    def test_corrupt_parseable_header_is_torn(self):
        """Bitrot inside the JSON header that still parses as JSON must
        diagnose as ``torn`` — never escape as a bare ValueError /
        ZeroDivisionError the file-fabric importer would crash on."""
        rec = self._record()
        data = rec.to_bytes()
        nl = data.find(b"\n")
        header = json.loads(data[:nl])
        for field, bad in (("k_shape", [1, 99, 4, 1, 2]),
                           ("page_size", 0),
                           ("dtype", "float99"),
                           ("payload_bytes", "many")):
            h = dict(header)
            h[field] = bad
            blob = json.dumps(h).encode() + b"\n" + data[nl + 1:]
            with pytest.raises(MigrationIntegrityError) as e:
                back = migrate_mod.MigrationRecord.from_bytes(blob)
                migrate_mod.verify_record(back)
            assert e.value.reason == "torn", field

    def test_geometry_mismatch_named(self):
        rec = self._record()
        dst = tiny_pool(page_size=8, max_seq_len=32)   # wrong page size
        with pytest.raises(MigrationIntegrityError) as e:
            dst.import_pages(rec)
        assert e.value.reason in ("geometry", "torn")
        dst2 = tiny_pool(num_heads=2)                  # wrong head count
        with pytest.raises(MigrationIntegrityError) as e:
            dst2.import_pages(rec)
        assert e.value.reason == "geometry"

    def test_file_round_trip_and_acks(self, tmp_path):
        rec = self._record()
        fab = MigrationFileFabric(str(tmp_path))
        path = fab.export(rec)
        assert os.path.dirname(path).endswith("kv")
        assert not os.path.exists(path + ".tmp")  # tmp+replace, no litter
        assert fab.pending() == [0]
        back = fab.read(0)
        migrate_mod.verify_record(back)
        assert back.length == rec.length
        np.testing.assert_array_equal(back.k_pages, rec.k_pages)
        assert back.page_crcs == [int(c) for c in rec.page_crcs]
        assert int(back.fingerprint) == int(rec.fingerprint)
        fab.ack(0)
        assert fab.pending() == [] and fab.acked() == [0]
        fab.clear(0)
        assert fab.acked() == []


class TestBurstLoadgen:
    def test_trace_is_deterministic(self):
        kw = dict(vocab=97, burst_every=5, burst_size=3)
        a = generate_prefill_burst_load(5, 40, **kw)
        b = generate_prefill_burst_load(5, 40, **kw)
        assert a == b
        assert a != generate_prefill_burst_load(6, 40, **kw)

    def test_mixture_and_clumping(self):
        trace = generate_prefill_burst_load(
            9, 90, vocab=97, short_len=(2, 8), short_new=(8, 16),
            long_len=(40, 60), long_new=(1, 4), burst_every=6,
            burst_size=3, mean_gap_s=0.002)
        bursts = [it for it in trace if it.burst]
        steady = [it for it in trace if not it.burst]
        # 90 items in periods of 9: exactly 3 burst items per period
        assert len(bursts) == 30 and len(steady) == 60
        for it in bursts:
            assert 40 <= len(it.prompt) <= 60 and 1 <= it.max_new_tokens <= 4
        for it in steady:
            assert 2 <= len(it.prompt) <= 8 and 8 <= it.max_new_tokens <= 16
        # burst arrivals clump: their gaps are a 50x tighter exponential
        gaps = np.diff([it.submit_at for it in trace])
        burst_gaps = [gaps[i - 1] for i in range(1, len(trace))
                      if trace[i].burst and trace[i - 1].burst]
        assert burst_gaps and np.mean(burst_gaps) < 0.002 / 10

    def test_arrivals_monotonic(self):
        trace = generate_prefill_burst_load(3, 50, vocab=97)
        ts = [it.submit_at for it in trace]
        assert all(b >= a for a, b in zip(ts, ts[1:]))


def run_fleet(model, trace, roles, slots, *, cost=0.0, hist=None):
    """Drive one seeded trace through a DisaggRouter fleet on the
    virtual clock; returns (handles, router, ttft-p99-or-None,
    virtual makespan)."""
    clock = VirtualClock()
    engines = [make_engine(model, clock, role=r, num_slots=s,
                           prompt_buckets=(8, 16, 32, 64),
                           queue_depth=len(trace) + 1,
                           prefill_tick_cost=cost)
               for r, s in zip(roles, slots)]
    router = DisaggRouter(engines)
    cum0 = hist.cumulative() if hist is not None else None
    handles, i, tick = [], 0, 0
    while i < len(trace) or not router.idle:
        tick += 1
        while i < len(trace) and trace[i].submit_at <= clock.t:
            it = trace[i]
            handles.append(router.submit(list(it.prompt),
                                         it.max_new_tokens))
            i += 1
        router.step()
        clock.advance(0.001)
        assert tick < 100000, "fleet wedged"
    p99 = (Histogram.quantile_from_cumulative(cum0, hist.cumulative(),
                                              0.99)
           if hist is not None else None)
    return handles, router, p99, clock.t


def streams_of(handles):
    return [(h.status, tuple(h.tokens), h.stream_fingerprint)
            for h in handles]


class TestDisaggEngine:
    def test_prefill_decode_smoke(self, model):
        """Tier-1 smoke: 1 prefill + 1 decode worker in-process — every
        request migrates, completes, and the journal carries role
        assignment + one kv_migrate per request."""
        clock = VirtualClock()
        jr = obs_journal.EventJournal(clock=clock)
        with obs_journal.use(jr):
            engines = [make_engine(model, clock, role="prefill"),
                       make_engine(model, clock, role="decode")]
            router = DisaggRouter(engines)
            hs = [router.submit(list(range(2 + i, 12 + i)), 6)
                  for i in range(4)]
            drain(router, clock)
        assert all(h.status == "completed" for h in hs)
        assert [(e["replica"], e["role"])
                for e in jr.of_kind("role_assign")] == \
            [(0, "prefill"), (1, "decode")]
        migs = jr.of_kind("kv_migrate")
        assert len(migs) == 4
        assert all(e["src"] == 0 and e["dst"] == 1 and e["pages"] >= 1
                   and e["bytes"] > 0 for e in migs)
        assert engines[0]._migrations["out"] == 4
        assert engines[1]._migrations["in"] == 4
        # both pools settled: exports acked, invariants hold
        s0, s1 = engines[0].pool.stats(), engines[1].pool.stats()
        assert s0["exports_outstanding"] == 0
        assert s0["exported_pages"] == s1["imported_pages"] > 0
        assert s0["sequences"] == s1["sequences"] == 0
        # the /fleet/serve payload: role columns + migration tallies
        st = router.stats()
        assert [r["role"] for r in st["replicas"]] == ["prefill", "decode"]
        assert st["roles"] == {"prefill": 1, "decode": 1, "colocated": 0}
        assert st["migrations"]["count"] == 4
        assert st["migrations"]["reprefills"] == 0
        assert st["replicas"][0]["migrations"]["out"] == 4

    def test_an_ingest_is_a_span_of_the_decode_workers_tick(self, model):
        """A migrated request entering a decode slot is one
        ``serve.tick.ingest`` span under that worker's tick; the prefill
        worker's tick has the prefill."""
        from hetu_tpu.obs import tracing
        tracer = tracing.get_tracer()
        tracer.reset()
        clock = VirtualClock()
        engines = [make_engine(model, clock, role="prefill"),
                   make_engine(model, clock, role="decode")]
        router = DisaggRouter(engines)
        with tracer.collect():
            hs = [router.submit(list(range(2 + i, 12 + i)), 4)
                  for i in range(3)]
            drain(router, clock)
        spans = {s.span_id: s for s in tracer.spans}
        tracer.reset()
        assert all(h.status == "completed" for h in hs)
        ingests = [s for s in spans.values() if s.name == "serve.tick.ingest"]
        assert sorted(s.attrs["request_id"] for s in ingests) == \
            sorted(h.request_id for h in hs)
        assert all(spans[s.parent_id].name == "serve.tick" for s in ingests)
        prefills = [s for s in spans.values()
                    if s.name == "serve.tick.prefill"]
        assert len(prefills) == 3
        # no tick holds both: the roles split the phases
        assert not {s.parent_id for s in ingests} & \
            {s.parent_id for s in prefills}

    def test_migrated_streams_bitwise_vs_colocated(self, model):
        """The acceptance bitwise bar: every migrated stream (tokens +
        stream_fingerprint) identical to the colocated same-seed run —
        sampler keys are (seed, request id, position) and migration
        preserves cache_index/lengths exactly."""
        trace = generate_prefill_burst_load(
            23, 18, vocab=CFG.vocab_size, short_len=(2, 8),
            short_new=(4, 8), long_len=(20, 30), long_new=(1, 3),
            burst_every=5, burst_size=2, mean_gap_s=0.003)
        d, rd, _, _ = run_fleet(model, trace, ["prefill", "decode"],
                                [4, 4])
        c, _, _, _ = run_fleet(model, trace, ["colocated", "colocated"],
                               [4, 4])
        assert streams_of(d) == streams_of(c)
        assert len(rd.migrations) > 0  # the comparison exercised migration

    def test_all_decode_workers_shed_falls_back_to_local_decode(
            self, model):
        """When every decode worker sheds, the prefill worker cancels
        the export and decodes the request itself — degraded, never
        dropped, and the pool accounting stays balanced."""
        clock = VirtualClock()
        engines = [make_engine(model, clock, role="prefill"),
                   make_engine(model, clock, role="decode")]
        router = DisaggRouter(engines)
        engines[1].batcher.set_shed("controller shed: chaos")
        h = router.submit(list(range(3, 13)), 5)
        drain(router, clock)
        assert h.status == "completed" and len(h.tokens) == 5
        assert engines[0]._migrations["out"] == 0
        assert engines[1]._migrations["in"] == 0
        s0 = engines[0].pool.stats()
        assert s0["exports_outstanding"] == 0   # cancelled, not leaked
        assert s0["exported_pages"] > 0         # the export did happen
        assert s0["pages_free"] == engines[0].pool.num_pages - 1

    def test_id_collision_at_intake_reroutes(self, model):
        """A migration arriving with an id a direct local submission
        already holds is refused at intake (re-routed / locally decoded)
        instead of overwriting the in-flight request's handle."""
        clock = VirtualClock()
        engines = [make_engine(model, clock, role="prefill"),
                   make_engine(model, clock, role="decode")]
        router = DisaggRouter(engines)
        # a standalone caller direct-submits on the decode engine,
        # drawing local id 0 — the router's first global id
        local = engines[1].submit(list(range(40, 50)), 4)
        routed = router.submit(list(range(3, 13)), 4)
        drain(router, clock)
        assert local.status == routed.status == "completed"
        assert len(local.tokens) == 4 and len(routed.tokens) == 4
        # the collision was refused: the routed request fell back to
        # decoding on the prefill worker, nothing was stranded
        assert engines[1]._migrations["in"] == 0
        assert engines[0].pool.stats()["exports_outstanding"] == 0

    def test_shed_reroutes_to_next_decode_worker(self, model):
        clock = VirtualClock()
        engines = [make_engine(model, clock, role="prefill"),
                   make_engine(model, clock, role="decode"),
                   make_engine(model, clock, role="decode")]
        router = DisaggRouter(engines)
        engines[1].batcher.set_shed("controller shed: chaos")
        h = router.submit(list(range(3, 13)), 5)
        drain(router, clock)
        assert h.status == "completed"
        assert [m["dst"] for m in router.migrations] == [2]

    def test_disagg_endpoint_smoke(self, model):
        """The fleet HTTP front end over a DisaggRouter: /infer serves
        through prefill->migrate->decode on real scheduler threads (the
        deferred-settle path across engine locks), /fleet/serve carries
        the role columns + migration tallies."""
        import time as _time
        import urllib.request

        from hetu_tpu.serve import serve_fleet_router
        engines = [ServingEngine(model, num_slots=2, page_size=8,
                                 max_seq_len=64,
                                 prompt_buckets=(8, 16, 32), seed=11,
                                 sampling="greedy", role=role,
                                 clock=_time.monotonic)
                   for role in ("prefill", "decode")]
        router = DisaggRouter(engines)
        srv = serve_fleet_router(router, port=0)
        try:
            url = f"http://127.0.0.1:{srv.port}"

            def post(payload):
                req = urllib.request.Request(
                    f"{url}/infer", data=json.dumps(payload).encode(),
                    method="POST")
                with urllib.request.urlopen(req, timeout=30) as r:
                    return json.loads(r.read())

            r1 = post({"prompt": list(range(3, 13)), "max_new_tokens": 4})
            r2 = post({"prompt": list(range(5, 15)), "max_new_tokens": 4})
            assert r1["status"] == r2["status"] == "completed"
            assert len(r1["tokens"]) == 4
            assert r1["stream_fingerprint"] is not None
            with urllib.request.urlopen(f"{url}/fleet/serve",
                                        timeout=30) as r:
                stats = json.loads(r.read())
            assert [x["role"] for x in stats["replicas"]] == \
                ["prefill", "decode"]
            assert stats["migrations"]["count"] == 2
            assert stats["replicas"][0]["pages_export_held"] == 0
        finally:
            srv.stop()
            router.stop()

    def test_requires_both_roles(self, model):
        clock = VirtualClock()
        with pytest.raises(ValueError, match="decode-capable"):
            DisaggRouter([make_engine(model, clock, role="prefill")])
        with pytest.raises(ValueError, match="prefill-capable"):
            DisaggRouter([make_engine(model, clock, role="decode")])

    def test_unknown_role_rejected(self, model):
        with pytest.raises(ValueError, match="unknown role"):
            make_engine(model, VirtualClock(), role="verifier")


class TestCorruptionEndToEnd:
    """The migration-integrity satellite: corrupt one exported page
    payload, one CRC, and one fingerprint sidecar (seeded) — each is
    detected, journaled with its named reason, and the request completes
    via re-prefill with its stream still bitwise correct."""

    CORRUPTIONS = [
        ("payload", "page_crc",
         lambda rec: rec.k_pages.__setitem__((0, 0, 0, 0, 0),
                                             rec.k_pages[0, 0, 0, 0, 0]
                                             + 1.0)),
        ("crc", "page_crc",
         lambda rec: rec.page_crcs.__setitem__(0, rec.page_crcs[0] ^ 1)),
        ("fingerprint", "fingerprint",
         lambda rec: setattr(rec, "fingerprint", rec.fingerprint ^ 1)),
    ]

    def _run(self, model, corrupt=None, victim=1):
        from hetu_tpu.serve.kv_cache import KVCachePool as Pool
        orig = Pool.export_pages
        if corrupt is not None:
            def patched(pool, sid):
                rec = orig(pool, sid)
                if sid == victim:
                    rec.k_pages = np.array(rec.k_pages)  # writable copy
                    corrupt(rec)
                return rec
            Pool.export_pages = patched
        try:
            clock = VirtualClock()
            jr = obs_journal.EventJournal(clock=clock)
            with obs_journal.use(jr):
                engines = [make_engine(model, clock, role="prefill"),
                           make_engine(model, clock, role="decode")]
                router = DisaggRouter(engines)
                hs = [router.submit(list(range(2 + i, 12 + i)), 6)
                      for i in range(3)]
                drain(router, clock)
            return streams_of(hs), jr, router
        finally:
            Pool.export_pages = orig

    @pytest.mark.parametrize("name,reason,corrupt", CORRUPTIONS,
                             ids=[c[0] for c in CORRUPTIONS])
    def test_detected_journaled_and_stream_bitwise(self, model, name,
                                                   reason, corrupt):
        base, _, _ = self._run(model)
        streams, jr, router = self._run(model, corrupt)
        fails = jr.of_kind("migrate_verify_failed")
        assert [e["reason"] for e in fails] == [reason]
        assert fails[0]["request_id"] == 1
        assert router.engines[1]._migrations["reprefill"] == 1
        # the request completed via re-prefill, stream bitwise correct
        assert streams == base
        for e in router.engines:
            s = e.pool.stats()
            assert s["exports_outstanding"] == 0
            assert s["sequences"] == 0


class TestReplay:
    def test_same_seed_replay_is_bitwise(self, model):
        """Full same-seed replay: placements, the migration journal
        (role_assign / kv_migrate / router_place, virtual ts and seq
        included), and every stream — bitwise across runs."""
        trace = generate_prefill_burst_load(
            37, 16, vocab=CFG.vocab_size, short_len=(2, 8),
            short_new=(4, 8), long_len=(20, 30), long_new=(1, 3),
            burst_every=5, burst_size=2, mean_gap_s=0.003)

        def run():
            from hetu_tpu.obs import compile as obs_compile
            obs_compile.configure_storm(None)
            clock = VirtualClock()
            jr = obs_journal.EventJournal(clock=clock)
            with obs_journal.use(jr):
                engines = [make_engine(model, clock, role="prefill",
                                       num_slots=2,
                                       queue_depth=len(trace) + 1,
                                       prompt_buckets=(8, 16, 32, 64)),
                           make_engine(model, clock, role="decode",
                                       num_slots=4,
                                       queue_depth=len(trace) + 1,
                                       prompt_buckets=(8, 16, 32, 64))]
                router = DisaggRouter(engines)
                handles, i = [], 0
                while i < len(trace) or not router.idle:
                    while i < len(trace) and \
                            trace[i].submit_at <= clock.t:
                        it = trace[i]
                        handles.append(router.submit(
                            list(it.prompt), it.max_new_tokens))
                        i += 1
                    router.step()
                    clock.advance(0.001)
            events = [{k: v for k, v in e.items() if k != "duration_s"}
                      for e in jr.events]
            return (router.placements, router.migrations,
                    streams_of(handles), events)

        p1, m1, s1, j1 = run()
        p2, m2, s2, j2 = run()
        assert p1 == p2
        assert m1 == m2 and len(m1) > 0
        assert s1 == s2
        assert j1 == j2
        kinds = {e["kind"] for e in j1}
        assert {"role_assign", "kv_migrate", "router_place"} <= kinds


class TestAcceptance:
    def test_disagg_beats_colocated_on_ttft_p99(self, model):
        """The tentpole's measured win, at equal chips in VIRTUAL time
        (one router tick steps every engine and advances the shared
        clock once — the N-chips deployment model; the prefill-cost
        model charges each prefill ceil(bucket/8) ticks of chip time,
        during which a COLOCATED engine can neither admit nor decode).

        Under the seeded prefill-burst trace, the colocated fleet's
        decode slots freeze behind every long-prompt prefill — slot
        turnover collapses and queued requests' TTFT blows out; the
        disaggregated decode worker never prefills (its slots budget is
        the HBM a colocated chip must reserve for prefill activations,
        hence 2x), and the prefill worker's slots recycle after ONE
        prefill each.  Disagg must win TTFT p99 WITHOUT losing
        tokens/s, with every stream bitwise identical between the two
        placements."""
        trace = generate_prefill_burst_load(
            29, 36, vocab=CFG.vocab_size, short_len=(2, 8),
            short_new=(12, 18), long_len=(40, 56), long_new=(1, 3),
            burst_every=6, burst_size=3, mean_gap_s=0.004)
        hist = obs_registry.get_registry().histogram(
            "hetu_serve_ttft_seconds").labels()

        def measure(roles, slots):
            handles, router, p99, makespan = run_fleet(
                model, trace, roles, slots, cost=1 / 8, hist=hist)
            assert all(h.status == "completed" for h in handles)
            tokens = sum(max(len(h.tokens) - 1, 0) for h in handles)
            # decode tokens per VIRTUAL second over the fleet's makespan
            # (same trace both runs, so this is the throughput A/B)
            return (tokens / makespan, p99, streams_of(handles), router)

        d_tps, d_p99, d_s, d_router = measure(
            ["prefill", "decode"], [2, 4])
        c_tps, c_p99, c_s, _ = measure(
            ["colocated", "colocated"], [2, 2])
        assert len(d_router.migrations) > 0
        # every migrated stream bitwise identical to its colocated twin
        assert d_s == c_s
        assert d_p99 < c_p99, (d_p99, c_p99)
        assert d_tps >= c_tps, (d_tps, c_tps)


@pytest.mark.slow
class TestFileFabricChaos:
    def test_multi_process_export_import_with_corruption(self, tmp_path):
        """The multi-process form: a child process exports seeded
        records through the atomic file fabric; the parent imports and
        verifies every one, then injects on-disk corruption (bitrot
        after the atomic write) and asserts the named detection."""
        script = r"""
import sys
import numpy as np
from hetu_tpu.layers import CacheSpec
from hetu_tpu.serve import KVCachePool, MigrationFileFabric

root = sys.argv[1]
fab = MigrationFileFabric(root)
rng = np.random.default_rng(7)
pool = KVCachePool(spec=CacheSpec.kv(1, 1, 2), num_pages=32,
                   page_size=4, max_seq_len=16)
for sid in range(4):
    pt = pool.alloc(sid, 4 * (1 + sid % 3))
    for p in pt.pages:
        pool.commit(
            pool.k.at[:, p].set(
                rng.standard_normal(pool.k.shape[2:]).astype(np.float32)),
            pool.v.at[:, p].set(
                rng.standard_normal(pool.v.shape[2:]).astype(np.float32)))
    pt.length = pt.capacity(pool.page_size)
    fab.export(pool.export_pages(sid))
    pool.free(sid)
stats = pool.stats()
assert stats["exports_outstanding"] == 4
assert stats["pages_free"] < pool.num_pages - 1  # holds pin the pages
print("EXPORTED", stats["exported_pages"])
"""
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, "-c", script, str(tmp_path)],
            capture_output=True, text=True, env=env,
            cwd=os.path.dirname(os.path.dirname(__file__)))
        assert out.returncode == 0, out.stderr
        assert "EXPORTED" in out.stdout

        fab = MigrationFileFabric(str(tmp_path))
        assert fab.pending() == [0, 1, 2, 3]
        dst = KVCachePool(spec=CacheSpec.kv(1, 1, 2), num_pages=32,
                          page_size=4, max_seq_len=16)
        for sid in fab.pending():
            rec = fab.read(sid)
            migrate_mod.verify_record(rec)
            dst.import_pages(rec)
            fab.ack(sid)
        assert fab.pending() == [] and fab.acked() == [0, 1, 2, 3]
        assert dst.stats()["imported_pages"] > 0
        dst.stats()  # invariants hold after all imports

        # bitrot chaos: flip one payload byte on disk post-write
        path = os.path.join(str(tmp_path), "kv", "seq_000001.kvmig")
        data = bytearray(open(path, "rb").read())
        data[-3] ^= 0x40
        with open(path, "wb") as f:
            f.write(data)
        with pytest.raises(MigrationIntegrityError) as e:
            migrate_mod.verify_record(fab.read(1))
        assert e.value.reason == "page_crc"
        # truncation (a torn tail) is the other named diagnosis
        with open(path, "wb") as f:
            f.write(bytes(data[:20]))
        with pytest.raises(MigrationIntegrityError) as e:
            fab.read(1)
        assert e.value.reason == "torn"
