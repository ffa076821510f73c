"""Request-scope serving observability: per-request timelines (exact
stage decomposition, decode-span-per-token), the trace ring/exemplar
buffer, the SLO engine (targets, burn rates, shed pressure), the XLA
compile-counting seams, and the chaos acceptance tying them together on
a seeded loadgen run — plus the tier-1 /slo and /trace/<id> smoke.
"""

import json
import math
import os
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from hetu_tpu import obs
from hetu_tpu.core import set_random_seed
from hetu_tpu.models.gpt import GPT, GPTConfig
from hetu_tpu.obs import compile as obs_compile
from hetu_tpu.obs import registry as obs_registry
from hetu_tpu.obs.reqtrace import STAGES, ReqTraceBuffer, RequestTimeline
from hetu_tpu.obs.slo import SLOEngine, SLOTargets
from hetu_tpu.serve import ServingEngine, generate_load, serve_engine

pytestmark = [pytest.mark.obs, pytest.mark.serve]


@pytest.fixture(autouse=True)
def _fresh_storm():
    # the storm detector is process-global with a real-time window;
    # isolate it so journal assertions are deterministic per test
    obs_compile.configure_storm(obs_compile.StormDetector())
    yield
    obs_compile.configure_storm(None)


def tiny_gpt(seed=0, **kw):
    set_random_seed(seed)
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2, num_heads=2,
                    max_seq_len=64, **kw)
    return GPT(cfg)


class VirtualClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ------------------------------------------------------------- timelines

class TestRequestTimeline:
    def lifecycle(self):
        tl = RequestTimeline(7, 1.0, prompt_len=3)
        tl.admit(1.25, slot=0)
        tl.prefill(1.25, 1.5, bucket=8)
        tl.decode(1.5)            # the prefill-sampled first token
        tl.decode(1.7, batch=2)
        tl.decode(1.9, batch=2)
        tl.close("completed", 2.0, tokens=3)
        return tl

    def test_stages_partition_wall_exactly(self):
        tl = self.lifecycle()
        st = tl.stage_seconds()
        assert set(st) == set(STAGES)
        assert st["queue"] == 0.25
        assert st["prefill"] == 0.25
        assert st["decode"] == pytest.approx(0.4)
        assert st["emit"] == pytest.approx(0.1)
        # the invariant the chaos acceptance scales up: stage times sum
        # to the accounted wall time EXACTLY, in float, by construction
        assert sum(st.values()) == tl.wall_s
        assert tl.summary()["wall_s"] == tl.wall_s

    def test_decode_span_per_token(self):
        tl = self.lifecycle()
        assert tl.decode_count() == 3
        decode = [s for s in tl.spans if s["name"] == "serve.decode"]
        # batch composition rides the span attributes
        assert decode[1]["attrs"]["batch"] == "2"
        assert decode[0]["attrs"]["iteration"] == "1"
        # every span is a child of the synthesized serve.request root
        root = [s for s in tl.spans if s["name"] == "serve.request"]
        assert len(root) == 1 and root[0]["parent_id"] is None
        assert all(s["parent_id"] == root[0]["span_id"]
                   for s in tl.spans if s is not root[0])

    def test_queue_only_expiry(self):
        tl = RequestTimeline(3, 5.0)
        tl.close("expired", 6.5, stage="queued")
        st = tl.stage_seconds()
        assert st["queue"] == 1.5
        assert st["prefill"] == st["decode"] == st["emit"] == 0.0
        assert sum(st.values()) == tl.wall_s == 1.5
        assert tl.decode_count() == 0

    def test_trace_id_derives_from_request_id(self):
        assert RequestTimeline(41, 0.0).trace_id == "req-41"

    def test_chrome_export_stitches(self):
        from hetu_tpu.obs.tracing import span_pid
        tl = self.lifecycle()
        buf = ReqTraceBuffer(capacity=4)
        buf.add(tl)
        ev = buf.to_chrome_events(worker=2)
        assert ev[0]["ph"] == "M" and ev[0]["pid"] == span_pid(2)
        assert {e["name"] for e in ev if e["ph"] == "X"} >= {
            "serve.queue", "serve.prefill", "serve.decode", "serve.request"}


class TestReqTraceBuffer:
    def timeline(self, rid, wall):
        tl = RequestTimeline(rid, 0.0)
        tl.admit(0.0)
        tl.prefill(0.0, 0.0)
        tl.close("completed", wall)
        return tl

    def test_ring_bounds_memory(self):
        buf = ReqTraceBuffer(capacity=4, slow_n=0)
        for i in range(10):
            buf.add(self.timeline(i, 0.1))
        assert buf.request_ids() == [6, 7, 8, 9]
        assert buf.get(2) is None and buf.get(9) is not None
        assert buf.completed == 10

    def test_exemplars_survive_ring_eviction(self):
        buf = ReqTraceBuffer(capacity=2, slow_n=2, window=8)
        # request 3 is the p99 offender of the first window
        walls = [0.1, 0.2, 0.1, 9.0, 0.1, 0.3, 0.1, 0.1]
        for i, w in enumerate(walls):
            buf.add(self.timeline(i, w))
        for i in range(100, 120):            # displace the ring entirely
            buf.add(self.timeline(i, 0.05))
        assert buf.get(3) is not None        # still queryable
        assert buf.exemplars()[0].request_id == 3  # slowest first
        # deterministic tie-break: equal walls retain the lower id
        buf2 = ReqTraceBuffer(capacity=1, slow_n=1, window=4)
        for i in range(4):
            buf2.add(self.timeline(i, 1.0))
        assert buf2.exemplars()[0].request_id == 0

    def test_capacity_validation(self):
        with pytest.raises(ValueError, match="capacity"):
            ReqTraceBuffer(capacity=0)


# ------------------------------------------------------------- SLO engine

class TestSLOEngine:
    def targets(self, **kw):
        kw.setdefault("ttft_s", 0.5)
        kw.setdefault("tpot_s", 0.1)
        kw.setdefault("queue_age_s", 0.25)
        kw.setdefault("objective", 0.9)
        return SLOTargets(**kw)

    def timeline(self, rid=0, queue=0.1, prefill=0.05, per_tok=0.02,
                 tokens=3, outcome="completed"):
        tl = RequestTimeline(rid, 0.0)
        tl.admit(queue)
        tl.prefill(queue, queue + prefill)
        t = queue + prefill
        tl.decode(t)              # the prefill-sampled first token
        for _ in range(tokens - 1):
            t += per_tok
            tl.decode(t)
        tl.close(outcome, t)
        return tl

    def test_targets_from_env(self, monkeypatch):
        monkeypatch.setenv("HETU_TPU_SLO_TTFT", "0.125")
        monkeypatch.setenv("HETU_TPU_SLO_OBJECTIVE", "0.95")
        t = SLOTargets.from_env(queue_age_s=2.0)
        assert t.ttft_s == 0.125 and t.objective == 0.95
        assert t.queue_age_s == 2.0      # explicit override wins
        assert t.tpot_s == SLOTargets().tpot_s

    def test_targets_validation(self):
        with pytest.raises(ValueError, match="objective"):
            SLOTargets(objective=1.0)
        with pytest.raises(ValueError, match="ttft_s"):
            SLOTargets(ttft_s=0.0)

    def test_grading_is_pure_and_exact(self):
        eng = SLOEngine(self.targets(), clock=lambda: 0.0,
                        registry=obs_registry.MetricsRegistry())
        g = eng.grade(self.timeline(queue=0.1, prefill=0.05, per_tok=0.02,
                                    tokens=3))
        assert g["ttft_s"] == pytest.approx(0.15)
        assert g["tpot_s"] == pytest.approx(0.02)
        assert g["violated"] == {"queue_age": False, "ttft": False,
                                 "tpot": False}
        assert eng.requests == 0         # grade() records nothing
        # a slow queue violates queue_age (and here also ttft)
        g2 = eng.grade(self.timeline(queue=0.6))
        assert g2["violated"]["queue_age"] and g2["violated"]["ttft"]
        # a never-admitted expiry violates queue_age BY DEFINITION
        tl = RequestTimeline(9, 0.0)
        tl.close("expired", 0.01, stage="queued")
        assert eng.grade(tl)["violated"] == {"queue_age": True,
                                             "ttft": False, "tpot": False}
        # but a RUNNING-stage expiry that was admitted instantly does
        # not — charging it to queue_age would point the burn rates at
        # admission when the regression is decode
        tl2 = self.timeline(rid=10, queue=0.01, prefill=0.05,
                            per_tok=0.5, tokens=3, outcome="expired")
        g3 = eng.grade(tl2)
        assert not g3["violated"]["queue_age"] and g3["violated"]["tpot"]

    def test_observe_counters_and_stage_totals(self):
        reg = obs_registry.MetricsRegistry()
        eng = SLOEngine(self.targets(), clock=lambda: 0.0, registry=reg)
        eng.observe(self.timeline(rid=0))
        eng.observe(self.timeline(rid=1, queue=0.6))
        snap = reg.snapshot()
        assert snap['hetu_slo_requests_total{verdict="ok"}'] == 1
        assert snap['hetu_slo_requests_total{verdict="violated"}'] == 1
        assert snap['hetu_slo_violations_total{target="queue_age"}'] == 1
        # the stage counter carries exactly the timelines' stage seconds
        # (per stage: same increments folded in the same order, so the
        # equality is exact in float)
        for stage in STAGES:
            key = f'hetu_slo_stage_seconds_total{{stage="{stage}"}}'
            assert snap.get(key, 0.0) == eng.stage_totals[stage]
        s = eng.summary()
        assert s["requests"] == 2
        assert sum(st["fraction"] for st in s["stages"].values()) == \
            pytest.approx(1.0)

    def test_burn_rates_and_shed_pressure_windows(self):
        clk = VirtualClock()
        eng = SLOEngine(self.targets(objective=0.9), clock=clk,
                        short_window_s=10.0, long_window_s=100.0,
                        shed_burn=2.0,
                        registry=obs_registry.MetricsRegistry())
        # 90 good requests spread over the long window
        for i in range(90):
            eng.observe(self.timeline(rid=i))
            clk.advance(1.0)
        assert eng.shed_pressure() == 0.0
        # a burst of 10 queue-age violations inside the short window
        for i in range(10):
            eng.observe(self.timeline(rid=100 + i, queue=0.6))
            clk.advance(0.1)
        rates = eng.burn_rates()
        # short window holds mostly violations; long window dilutes them
        assert rates["queue_age"]["short"] > rates["queue_age"]["long"] > 0
        # both windows burning -> pressure up (min(short,long)/shed_burn)
        expected = min(min(rates["queue_age"]["short"],
                           rates["queue_age"]["long"]) / 2.0, 1.0)
        assert eng.shed_pressure() == pytest.approx(expected)
        assert expected > 0
        # once the burst ages out of the short window the pressure drops
        # to zero even though the long window still remembers it — the
        # "both windows must burn" guard against paging on noise
        clk.advance(20.0)
        assert eng.burn_rates()["queue_age"]["long"] > 0
        assert eng.shed_pressure() == 0.0


# ------------------------------------------------------------ compile seam

class TestCompileSeam:
    def test_signature_and_str(self):
        sig = obs_compile.shape_signature(
            (jnp.zeros((2, 3)), 4), {"k": jnp.zeros(5, jnp.int32)})
        s = obs_compile.signature_str(sig)
        assert "float32[2,3]" in s and "int32[5]" in s and "py:int" in s

    def test_aot_counts_exactly_once_per_shape(self):
        journal = obs.EventJournal()
        fn = obs_compile.instrument(jax.jit(lambda x: x * 2),
                                    site="serve.test")
        with obs.use(journal):
            a = fn(jnp.ones(3))
            b = fn(jnp.ones(3) * 2)          # same shape: cached program
            assert fn.compile_count == 1
            fn(jnp.ones(4))                  # new shape: one recompile
            assert fn.compile_count == 2
        assert [float(v) for v in a] == [2.0, 2.0, 2.0]
        assert [float(v) for v in b] == [4.0, 4.0, 4.0]
        kinds = [e["kind"] for e in journal.events]
        assert kinds == ["compile", "recompile"]
        rec = journal.events[1]
        assert rec["site"] == "serve.test" and rec["programs"] == 2
        assert "float32[3] -> float32[4]" in rec["delta"]
        rep = fn.report()
        assert len(rep) == 2 and all(r["aot"] for r in rep.values())

    def test_a_signature_seen_before_compiles_nothing(self):
        journal = obs.EventJournal()
        fn = obs_compile.instrument(jax.jit(lambda x: x * 2),
                                    site="serve.test")
        with obs.use(journal):
            fn(jnp.ones(3))
            fn(jnp.ones(4))
            for _ in range(5):
                fn(jnp.ones(3))          # back to the first: jit's cache
            fn(jnp.ones(4))
        assert fn.compile_count == 2
        assert fn.slow_calls == 2
        assert [e["kind"] for e in journal.events] == ["compile",
                                                       "recompile"]

    def test_donated_arguments_count_journal_and_report_alike(self):
        def run(donate):
            journal = obs.EventJournal()
            fn = obs_compile.instrument(
                jax.jit(lambda x, y: (x + y, y),
                        donate_argnums=(0,) if donate else ()),
                site="serve.test")
            with obs.use(journal):
                for n in (3, 3, 5, 3, 5):
                    x = jnp.ones(n)
                    out, _ = fn(x, jnp.ones(n))
                    assert float(out[0]) == 2.0
                    assert x.is_deleted() == donate
            return fn, [{k: e[k] for k in ("kind", "programs", "sig", "aot")}
                        | ({"delta": e["delta"]} if "delta" in e else {})
                        for e in journal.events]

        plain, plain_events = run(False)
        donated, donated_events = run(True)
        assert donated_events == plain_events and len(plain_events) == 2
        assert donated.compile_count == plain.compile_count == 2
        assert donated.slow_calls == plain.slow_calls == 2
        rep, plain_rep = donated.report(), plain.report()
        assert list(rep) == list(plain_rep)
        for sig, prog in rep.items():
            assert prog["aot"] and plain_rep[sig]["aot"]
            want = dict(plain_rep[sig]["memory_bytes"])
            # the donated input is written in place: its bytes are aliased
            n = int(sig.split("[")[1].split("]")[0])
            assert prog["memory_bytes"]["alias"] == 4 * n
            assert want.get("alias", 0) == 0
            assert prog["memory_bytes"]["argument"] == want["argument"]

    def test_report_sizes_a_program_whose_first_call_was_traced(self):
        fn = obs_compile.instrument(jax.jit(lambda x: x @ x.T),
                                    site="serve.test")
        fn(jnp.ones((8, 4)))              # traced, compiled and run by jit
        (prog,) = fn.report().values()
        assert prog["aot"] is True
        assert prog["memory_bytes"]["argument"] == 8 * 4 * 4
        assert prog["memory_bytes"]["output"] == 8 * 8 * 4

    def test_a_compile_is_a_span_around_the_call_that_compiled(self):
        from hetu_tpu.obs import tracing
        tracer = tracing.get_tracer()
        fn = obs_compile.instrument(jax.jit(lambda x: x - 3),
                                    site="serve.test")
        tracer.reset()
        with tracer.collect():
            with tracer.span("serve.tick"):
                fn(jnp.ones(6))
                fn(jnp.ones(6))
        spans = {s.name: s for s in tracer.spans}
        assert [s.name for s in tracer.spans].count("compile.xla") == 1
        xla, tick = spans["compile.xla"], spans["serve.tick"]
        assert xla.parent_id == tick.span_id
        assert xla.attrs == {"site": "serve.test", "aot": True}
        assert tick.start <= xla.start < xla.end_time <= tick.end_time
        (prog,) = fn.report().values()
        assert xla.duration >= prog["compile_s"]
        tracer.reset()

    def test_tracer_stage_calls_pass_through(self):
        fn = obs_compile.instrument(jax.jit(lambda x: x + 1),
                                    site="serve.test")

        @jax.jit
        def outer(x):
            return fn(x) * 3

        assert float(outer(jnp.float32(1.0))) == 6.0
        assert fn.compile_count == 0     # the OUTER program owns it

    def test_watch_mode_counts_without_owning_dispatch(self):
        fn = obs_compile.watch(jax.jit(lambda x: x - 1), site="train.test")
        fn(jnp.ones(2))
        fn(jnp.ones(2))
        assert fn.compile_count == 1
        rep = fn.report()
        assert not any(r["aot"] for r in rep.values())

    def test_watch_disabled_is_passthrough(self):
        fn = obs_compile.watch(jax.jit(lambda x: x), site="train.test")
        obs.disable()
        try:
            fn(jnp.ones(2))
            assert fn.compile_count == 0   # nothing tracked while off
        finally:
            obs.enable()

    def test_non_jit_degrades_to_watch_and_keeps_counting(self):
        fn = obs_compile.instrument(lambda x: x * 10, site="serve.test")
        assert fn(3) == 30
        assert fn.aot is False and fn.compile_count == 1
        assert fn(4) == 40
        assert fn.compile_count == 1       # same py:int signature

    def test_storm_detector(self):
        clk = VirtualClock()
        journal = obs.EventJournal()
        det = obs_compile.StormDetector(threshold=3, window_s=10.0,
                                        clock=clk)
        with obs.use(journal):
            for _ in range(3):
                det.note("serve.test")
            assert not det._storming
            det.note("serve.test")         # 4 > 3: the storm begins
            assert det._storming
            det.note("serve.test")         # still storming: no new event
        storms = journal.of_kind("compile_storm")
        assert len(storms) == 1            # journaled once per crossing
        assert storms[0]["recent"] == 4
        clk.advance(11.0)                  # the window drains
        assert det.recent() == 0

    def test_storm_from_env(self, monkeypatch):
        monkeypatch.setenv("HETU_TPU_COMPILE_STORM_N", "5")
        monkeypatch.setenv("HETU_TPU_COMPILE_STORM_S", "30")
        det = obs_compile.StormDetector.from_env()
        assert det.threshold == 5 and det.window_s == 30.0


# -------------------------------------------------- the chaos acceptance

def _drive(model, trace, seed, **engine_kw):
    """One seeded loadgen run on a virtual clock; returns (engine,
    handles, registry delta)."""
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
    return eng, handles, reg.delta(reg.snapshot(), s0)


@pytest.mark.chaos
def test_request_accounting_chaos_acceptance():
    """Acceptance: on a seeded loadgen run (prompt lengths spanning a
    prefill-bucket boundary), (a) every request's stage decomposition
    sums to its wall time exactly and decode span count equals tokens
    generated, for 100% of completed requests; (b) trace ids in the ring
    are gapless; (c) hetu_compile_total equals the true number of XLA
    compilations — one prefill program per bucket USED, one paged-decode
    program, one sampler — with ZERO steady-state decode recompiles; (d)
    the whole thing is bitwise-identical across two same-seed runs."""
    model = tiny_gpt()
    trace = generate_load(23, 24, vocab=97, prompt_len=(2, 14),
                          max_new=(1, 6), mean_gap_s=0.0008)
    # the variance injection the compile assertion needs: prompts on
    # both sides of the 8-token bucket boundary
    lens = {len(t.prompt) for t in trace}
    assert any(n <= 8 for n in lens) and any(n > 8 for n in lens)
    kw = dict(num_slots=4, page_size=8, max_seq_len=64,
              prompt_buckets=(8, 16), queue_depth=32, sampling="top_k",
              top_k=5)

    def run():
        # fresh storm window per run: the two same-seed runs must note
        # the same compiles against the same detector state
        obs_compile.configure_storm(obs_compile.StormDetector())
        journal = obs.EventJournal()
        with obs.use(journal):
            eng, handles, d = _drive(model, trace, seed=7, **kw)
        summaries = [eng.trace_buffer.get(h.request_id).summary()
                     for h in handles.values()]
        return eng, handles, d, journal, summaries

    eng, handles, d, journal, summaries = run()
    assert all(h.status == "completed" for h in handles.values())

    # (a) exact per-request accounting, for every single request
    for h in handles.values():
        tl = eng.trace_buffer.get(h.request_id)
        st = tl.stage_seconds()
        assert sum(st.values()) == tl.wall_s            # exact, in float
        assert tl.wall_s == tl.finished_at - tl.arrival
        assert tl.decode_count() == len(h.tokens)       # span per token
        assert all(st[s] >= 0 for s in st)
    # the SLO engine folded exactly these stage seconds
    assert sum(eng.slo.stage_totals.values()) == pytest.approx(
        sum(tl.wall_s for tl in eng.trace_buffer.timelines()))
    assert eng.slo.requests == len(trace)

    # (b) gapless trace ids (completion order may interleave)
    assert sorted(eng.trace_buffer.request_ids()) == list(range(len(trace)))

    # (c) exact compile accounting through the counting seam
    buckets_used = {eng.batcher.bucket_for(len(t.prompt)) for t in trace}
    assert eng._step_fn.compile_count == len(buckets_used) == 2
    assert eng._paged_step_fn.compile_count == 1
    assert eng._sample_fn.compile_count == 1
    assert d['hetu_compile_total{site="serve.prefill_step"}'] == 2
    assert d['hetu_compile_total{site="serve.paged_decode"}'] == 1
    assert d['hetu_compile_total{site="serve.sample"}'] == 1
    # zero recompiles over steady-state decode: the decode program
    # compiled once, before any recompile event could name it
    assert not [e for e in journal.of_kind("recompile")
                if e["site"] == "serve.paged_decode"]
    # and the journal's compile records agree with the counters
    compiles = journal.of_kind("compile", "recompile")
    assert len(compiles) == 4

    # (d) bitwise-identical across two same-seed runs: timelines, stage
    # decompositions, journal kinds, and the registry delta
    eng2, handles2, d2, journal2, summaries2 = run()
    assert json.dumps(summaries, sort_keys=True) == \
        json.dumps(summaries2, sort_keys=True)
    assert [h.tokens for h in handles.values()] == \
        [h.tokens for h in handles2.values()]
    assert [(e["kind"], e.get("site")) for e in journal.events] == \
        [(e["kind"], e.get("site")) for e in journal2.events]
    # the registry is process-global, so a float counter's second-run
    # delta differs from the first at ulp level ((a+b)-a != b in float);
    # compile wall times are real-clock (XLA caches lowerings, so run 2
    # compiles faster) — everything else must agree, counts exactly.
    # hetu_tenant_compile_seconds is the same wall time attributed per
    # tenant (billing data, deliberately outside the replay surfaces).
    skip = ("hetu_compile_seconds", "hetu_tenant_compile_seconds")
    assert {k for k in d if not k.startswith(skip)} == \
        {k for k in d2 if not k.startswith(skip)}
    for k, v in d.items():
        if k.startswith(skip):
            continue
        if float(v).is_integer() and float(d2[k]).is_integer():
            assert v == d2[k], k
        else:
            assert v == pytest.approx(d2[k]), k


def test_running_deadline_cuts_at_next_tick():
    """Satellite: a request past its deadline while DECODING is retired
    at the next scheduler tick with the tokens it has — counted under
    stage="running", journaled as request_expired, error on the handle."""
    reg = obs.get_registry()
    clk = VirtualClock()
    journal = obs.EventJournal()
    m = tiny_gpt()
    with obs.use(journal):
        eng = ServingEngine(m, num_slots=1, page_size=8, max_seq_len=64,
                            prompt_buckets=(8,), seed=0, clock=clk)
        s0 = reg.snapshot()
        h = eng.submit([1, 2, 3], 40, deadline_s=0.05)
        eng.step()                       # admit + prefill + first decode
        assert not h.done
        clk.advance(0.1)                 # deadline passes mid-decode
        eng.step()
        assert h.done and h.status == "expired"
        assert len(h.tokens) >= 1        # keeps what was generated
        assert "deadline" in h.error and "decoding" in h.error
        d = reg.delta(reg.snapshot(), s0)
    assert d['hetu_serve_deadline_expired_total{stage="running"}'] == 1
    exp = journal.of_kind("request_expired")
    assert len(exp) == 1 and exp[0]["stage"] == "running"
    assert exp[0]["tokens_generated"] == len(h.tokens)
    # the timeline resolved as expired, with its decode spans intact
    tl = eng.trace_buffer.get(h.request_id)
    assert tl.outcome == "expired"
    assert tl.decode_count() == len(h.tokens)
    assert sum(tl.stage_seconds().values()) == tl.wall_s


def test_timelines_fold_into_recording_tracer():
    """Finished request timelines ride the process tracer (and so the
    fleet snapshot) while it records — stitchable with runtime spans."""
    tracer = obs.get_tracer()
    tracer.reset()
    eng = ServingEngine(tiny_gpt(), num_slots=1, page_size=8,
                        max_seq_len=32, prompt_buckets=(8,), seed=0,
                        clock=VirtualClock())
    with tracer.collect():
        h = eng.submit([1, 2, 3], 2)
        eng.run_until_idle()
    assert h.status == "completed"
    names = {s["name"] for s in tracer.span_dicts()}
    assert {"serve.request", "serve.queue", "serve.prefill",
            "serve.decode"} <= names
    tracer.reset()
    assert tracer.span_dicts() == []     # reset clears the folds too


def test_slo_and_trace_endpoints_smoke():
    """Tier-1 smoke (satellite): /slo and /trace/<id> on a 2-request
    engine run, every field validated."""
    eng = ServingEngine(tiny_gpt(), num_slots=2, page_size=8,
                        max_seq_len=32, prompt_buckets=(8,), seed=1)
    srv = serve_engine(eng)
    try:
        rids = []
        for p in ([1, 2, 3], [4, 5, 6, 7]):
            req = urllib.request.Request(
                srv.url + "/infer",
                data=json.dumps({"prompt": p, "max_new_tokens": 3,
                                 "timeout_s": 120}).encode(),
                method="POST")
            with urllib.request.urlopen(req, timeout=120) as r:
                out = json.loads(r.read())
            assert out["status"] == "completed"
            # MIGRATING note: /infer responses now carry the trace id
            assert out["trace_id"] == f"req-{out['request_id']}"
            rids.append(out["request_id"])

        with urllib.request.urlopen(srv.url + "/slo", timeout=10) as r:
            slo = json.loads(r.read())
        assert set(slo) == {"targets", "windows_s", "requests",
                            "violations", "stages", "burn_rates",
                            "shed_pressure"}
        assert slo["requests"] == 2
        assert set(slo["stages"]) == set(STAGES)
        assert sum(s["fraction"] for s in slo["stages"].values()) == \
            pytest.approx(1.0)
        assert set(slo["burn_rates"]) == {"ttft", "tpot", "queue_age"}
        for r_ in slo["burn_rates"].values():
            assert set(r_) == {"short", "long"}
        assert 0.0 <= slo["shed_pressure"] <= 1.0

        with urllib.request.urlopen(srv.url + "/trace", timeout=10) as r:
            index = json.loads(r.read())
        assert sorted(index["ring"]) == sorted(rids)
        for rid in rids:
            with urllib.request.urlopen(srv.url + f"/trace/{rid}",
                                        timeout=10) as r:
                t = json.loads(r.read())
            assert t["request_id"] == rid
            assert t["outcome"] == "completed"
            assert set(t["stages_s"]) == set(STAGES)
            assert t["wall_s"] == pytest.approx(sum(t["stages_s"].values()))
            assert t["decode_spans"] == 3
            assert len(t["spans"]) >= t["decode_spans"] + 3
            for sp in t["spans"]:
                assert sp["trace_id"] == f"req-{rid}"
                assert sp["end"] >= sp["start"]
        # unknown id -> 404, garbage -> 400 (never a 500)
        for path, code in (("/trace/12345", 404), ("/trace/bogus", 400)):
            try:
                urllib.request.urlopen(srv.url + path, timeout=10)
                pytest.fail("expected HTTPError")
            except urllib.error.HTTPError as e:
                assert e.code == code
        # /stats carries the shed pressure and the compile report
        with urllib.request.urlopen(srv.url + "/stats", timeout=10) as r:
            stats = json.loads(r.read())
        assert 0.0 <= stats["shed_pressure"] <= 1.0
        assert stats["compile"]["serve.prefill_step"]["programs"] == 1
    finally:
        srv.stop()
        eng.stop()


def test_rejected_request_timeline_is_forensic_not_graded():
    eng = ServingEngine(tiny_gpt(), num_slots=1, page_size=8,
                        max_seq_len=32, prompt_buckets=(8,), seed=0,
                        clock=VirtualClock())
    h = eng.submit([], 4)                # empty prompt: rejected
    assert h.status == "rejected" and h.error == "empty prompt"
    tl = eng.trace_buffer.get(h.request_id)
    assert tl.outcome == "rejected" and tl.wall_s == 0.0
    assert eng.slo.requests == 0         # no SLO budget consumed
