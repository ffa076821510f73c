"""Numerics observability: deterministic tensor fingerprints, the
flight recorder, NaN provenance, and cross-replica divergence detection.

The acceptance bar is the issue's chaos scenario: a 4-worker gang under
a seeded plan with one worker-targeted ``grad_nan`` and one post-reduce
``bit_flip`` must journal ``replica_divergence`` naming the exact
step/worker/shard, NaN provenance must name where the poison entered,
the flight-recorder dump must be bitwise-identical across two same-seed
runs, and a clean run must journal ZERO numerics events.
"""

import json
import os
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu import obs
from hetu_tpu.core import set_random_seed
from hetu_tpu.exec import (ElasticGang, PartialReduceConfig, ResilientTrainer,
                           Trainer, faults, gang)
from hetu_tpu.models import MLP
from hetu_tpu.obs import divergence as obs_divergence
from hetu_tpu.obs import journal as obs_journal
from hetu_tpu.obs import numerics as obs_numerics
from hetu_tpu.optim import SGDOptimizer
from hetu_tpu.ops import softmax_cross_entropy_sparse

pytestmark = pytest.mark.numerics


# ---------------------------------------------------------------- helpers

def make_trainer(donate=False):
    set_random_seed(0)
    model = MLP((8, 16, 3))

    def loss_fn(model, batch, key):
        logits = model(batch["x"])
        return softmax_cross_entropy_sparse(logits, batch["y"]).mean(), {}

    return Trainer(model, SGDOptimizer(0.1), loss_fn, donate=donate)


def make_batch(seed=0, n=16):
    rng = np.random.default_rng(seed)
    return {"x": jnp.asarray(rng.standard_normal((n, 8)), jnp.float32),
            "y": jnp.asarray(rng.integers(0, 3, (n,)), jnp.int32)}


def make_data(n=40, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        x = rng.standard_normal((16, 8)).astype(np.float32)
        out.append({"x": x, "y": (x[:, 0] > 0).astype(np.int32)})
    return out


@pytest.fixture(autouse=True)
def _isolated_storm():
    """The compile StormDetector is process-wide with a real-time window:
    mid-suite it can cross its threshold from OTHER tests' compiles and
    journal a nondeterministic ``compile_storm`` (breaking the bitwise
    replay comparisons) or flag /healthz.  Give this module its own quiet
    detector and restore the shared one after."""
    from hetu_tpu.obs import compile as obs_compile
    prev = obs_compile.get_storm()
    obs_compile.configure_storm(obs_compile.StormDetector(threshold=10**6))
    yield
    obs_compile.configure_storm(prev)


@pytest.fixture
def recorder():
    rec = obs_numerics.FlightRecorder(capacity=8)
    obs_numerics.install(rec)
    obs_divergence.reset_detected()
    yield rec
    obs_numerics.install(None)
    obs_divergence.reset_detected()


@pytest.fixture
def journal():
    j = obs_journal.EventJournal(clock=lambda: 0.0)
    obs_journal.set_journal(j)
    yield j
    obs_journal.set_journal(None)


def numerics_events(j):
    return [e for e in j.events if e["kind"] in
            ("replica_divergence", "nan_provenance", "flight_dump")]


def strip(events):
    return [{k: v for k, v in e.items() if k != "ts"} for e in events]


# ----------------------------------------------------- fingerprint laws

class TestFingerprint:
    DTYPES = (np.float32, np.float16, np.int32, np.int8)

    def test_host_matches_device_bitwise(self):
        rng = np.random.default_rng(0)
        for dtype in self.DTYPES:
            if np.issubdtype(dtype, np.floating):
                a = rng.standard_normal(53).astype(dtype)
            else:
                a = rng.integers(-100, 100, 53).astype(dtype)
            dev = int(jax.jit(obs_numerics.fingerprint)(jnp.asarray(a)))
            assert dev == obs_numerics.host_fingerprint(a), dtype

    def test_host_matches_device_bf16(self):
        a = jnp.asarray(np.random.default_rng(1).standard_normal(31),
                        jnp.bfloat16)
        dev = int(jax.jit(obs_numerics.fingerprint)(a))
        assert dev == obs_numerics.host_fingerprint(np.asarray(a))

    def test_single_bit_flip_always_changes_it(self):
        """Property: flipping ANY single bit changes the fingerprint —
        the odd position weights guarantee the weighted delta
        ``(2i+1) * 2**k`` is never 0 mod 2**32."""
        rng = np.random.default_rng(2)
        a = rng.standard_normal(64).astype(np.float32)
        base = obs_numerics.host_fingerprint(a)
        for trial in range(200):
            i = int(rng.integers(a.size))
            k = int(rng.integers(32))
            b = a.copy()
            b.view(np.uint32)[i] ^= np.uint32(1 << k)
            assert obs_numerics.host_fingerprint(b) != base, (i, k)

    def test_invariant_to_summation_order(self):
        """The modular weighted sum commutes: accumulating per-chunk
        partial sums in any chunk order gives the same fingerprint."""
        rng = np.random.default_rng(3)
        a = rng.standard_normal(1024).astype(np.float32)
        want = obs_numerics.host_fingerprint(a)
        bits = a.view(np.uint32).astype(np.uint64)
        w = (np.arange(a.size, dtype=np.uint64) * 2 + 1) & 0xFFFFFFFF
        terms = (w * bits) & 0xFFFFFFFF
        for perm_seed in range(5):
            order = np.random.default_rng(perm_seed).permutation(16)
            acc = 0
            for c in order:
                acc = (acc + int(terms[c * 64:(c + 1) * 64].sum())) \
                    & 0xFFFFFFFF
            assert acc == want

    def test_invariant_to_pjit_sharding_layout(self):
        """The same logical array sharded across the 8-device mesh
        fingerprints identically to the unsharded copy — modular
        integer addition is exact under any partitioning."""
        from jax.sharding import Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()), ("d",))
        x = jnp.asarray(np.random.default_rng(4)
                        .standard_normal((64, 16)).astype(np.float32))
        f = jax.jit(obs_numerics.fingerprint)
        plain = int(f(x))
        for spec in (P("d", None), P(None, "d")):
            xs = jax.device_put(x, NamedSharding(mesh, spec))
            assert int(f(xs)) == plain, spec
        assert plain == obs_numerics.host_fingerprint(np.asarray(x))

    def test_stable_across_same_seed_replays(self):
        """Two same-seed training runs publish identical per-step
        post-update parameter fingerprints."""
        def run():
            rec = obs_numerics.FlightRecorder(capacity=16)
            obs_numerics.install(rec)
            try:
                tr = make_trainer()
                for s in range(4):
                    tr.step(make_batch(seed=s))
                return [
                    {g: int(np.asarray(v)) for g, v in
                     st["param_fp"].items()}
                    for _s, st in rec._ring]
            finally:
                obs_numerics.install(None)
        assert run() == run()

    def test_group_stats_values(self):
        tree = {"blocks": {"0": {"w": jnp.ones((4, 4))},
                           "1": {"w": jnp.zeros((3,))}},
                "embed": {"w": jnp.asarray([np.nan, 2.0], jnp.float32)}}
        stats = jax.jit(lambda t: obs_numerics.group_stats(t))(tree)
        conv = obs_numerics.FlightRecorder._to_host
        assert conv(stats["blocks.0"]["norm"]) == pytest.approx(4.0)
        assert conv(stats["blocks.1"]["zero_frac"]) == 1.0
        assert conv(stats["embed"]["nonfinite"]) == 1
        assert conv(stats["blocks.0"]["max_abs"]) == 1.0
        # host mirror agrees bitwise on the fingerprints
        host = obs_numerics.host_group_stats(
            {"blocks.0.w": np.ones((4, 4), np.float32),
             "blocks.1.w": np.zeros((3,), np.float32),
             "embed.w": np.asarray([np.nan, 2.0], np.float32)})
        for g in host:
            assert host[g]["fingerprint"] == conv(stats[g]["fingerprint"])

    def test_token_stream_fingerprint_order_sensitive(self):
        f = obs_numerics.host_fingerprint_ints
        assert f([1, 2, 3]) != f([3, 2, 1])
        assert f([1, 2, 3]) == f([1, 2, 3])


# --------------------------------------------------------- NaN provenance

class TestProvenance:
    def test_names_the_op_that_bore_the_nan(self):
        rep = obs_numerics.first_nonfinite(
            lambda x: jnp.log(x - 10.0).sum(), jnp.ones((3,)))
        assert rep["op"] == "log" and rep["origin"] == "op"
        assert rep["site"] and "test_numerics" in rep["site"]

    def test_names_a_poisoned_input_leaf(self):
        rep = obs_numerics.first_nonfinite(
            lambda m: (m["a"] * 2).sum(),
            {"a": jnp.full((3,), jnp.nan), "b": jnp.ones((2,))})
        assert rep["origin"] == "input" and "a" in rep["leaf"]

    def test_finite_program_returns_none(self):
        assert obs_numerics.first_nonfinite(
            lambda x: (x * 2).sum(), jnp.ones((3,))) is None

    def test_covers_the_backward_pass(self):
        """A NaN born only in the gradient (sqrt'(0) = inf) is named —
        the interpreter walks value_and_grad's jaxpr, not the forward
        alone."""
        def loss_fn(m, b, k):
            return jnp.sqrt(jnp.abs(m["w"]).sum()), {}
        rep = obs_numerics.loss_provenance(
            loss_fn, {"w": jnp.zeros((3,))}, {}, None)
        assert rep is not None and rep["origin"] in ("op", "propagated")


# ------------------------------------------ trainer seam + flight recorder

class TestTrainerSeam:
    def test_stats_ride_the_step_without_recorder_nothing_traces(self):
        tr = make_trainer()
        m = tr.step(make_batch())
        assert "_numerics" not in m
        assert obs_numerics.get_recorder() is None

    def test_recorder_rings_device_scalars_no_sync(self, recorder):
        tr = make_trainer()
        m = tr.step(make_batch())
        assert "_numerics" not in m          # popped before the caller
        assert recorder.steps == 1
        _s, stats = list(recorder._ring)[0]
        g = next(iter(stats["grad"]))
        # the overhead contract's second half: the enabled path adds no
        # device sync to Trainer.step — the ring holds unfetched device
        # scalars, fetched only by an explicit cold-path dump
        assert isinstance(stats["grad"][g]["norm"], jax.Array)
        assert isinstance(
            stats["param_fp"][next(iter(stats["param_fp"]))], jax.Array)

    def test_ring_is_bounded(self, recorder):
        tr = make_trainer()
        for s in range(12):
            tr.step(make_batch(seed=s))
        assert recorder.steps == 12 and len(recorder._ring) == 8

    def test_disabled_path_one_global_load_and_branch(self):
        """Overhead guard: with NO recorder installed, Trainer.step must
        be statistically indistinguishable from the bare step (the seam
        is one module-global load + branch), and the traced program must
        carry no numerics outputs."""
        tr = make_trainer()
        b = make_batch()
        tr.step(b)

        def timed(fn, n=30):
            out = []
            for _ in range(n):
                t0 = time.perf_counter()
                fn()
                out.append(time.perf_counter() - t0)
            return out

        instrumented, bare = [], []
        for _ in range(4):
            instrumented += timed(lambda: tr.step(b))
            bare += timed(lambda: tr._step_impl(b))
        ratio = np.median(instrumented) / np.median(bare)
        assert ratio < 1.5, f"no-recorder step is {ratio:.2f}x bare"

    def test_dump_fires_flight_dump_journal(self, recorder, journal):
        tr = make_trainer()
        tr.step(make_batch())
        rec = obs_numerics.dump("nan_skip", step=1)
        ev, = journal.of_kind("flight_dump")
        assert ev["reason"] == "nan_skip" and ev["step"] == 1
        assert len(ev["records"]) == 1
        g = next(k for k in ev["records"][0]["grad"])
        assert isinstance(ev["records"][0]["grad"][g]["norm"], float)
        assert rec == recorder.last_dump

    def test_streak_accounting(self, recorder):
        recorder.note_outcome(False)
        recorder.note_outcome(False)
        assert recorder.nonfinite_streak == 2
        recorder.note_outcome(True)
        assert recorder.nonfinite_streak == 0


# ----------------------------------------- resilience-layer post-mortem

class TestResilienceWiring:
    def run_poisoned(self, tmp_path, tag):
        j = obs_journal.EventJournal(clock=lambda: 0.0)
        obs_journal.set_journal(j)
        rec = obs_numerics.FlightRecorder(capacity=8)
        obs_numerics.install(rec)
        try:
            tr = make_trainer()
            rt = ResilientTrainer(tr, str(tmp_path / tag), save_every=0)
            plan = faults.FaultPlan([(2, "grad_nan")])
            with faults.inject(plan):
                for s in range(1, 4):
                    rt.step(make_batch(seed=s))
            rt.close()
            return j
        finally:
            obs_numerics.install(None)
            obs_journal.set_journal(None)

    def test_nan_skip_dumps_and_names_the_poisoned_leaf(self, tmp_path):
        j = self.run_poisoned(tmp_path, "a")
        kinds = [e["kind"] for e in j.events]
        assert "nan_skip" in kinds
        dump, = j.of_kind("flight_dump")
        assert dump["reason"] == "nan_skip" and dump["records"]
        prov, = j.of_kind("nan_provenance")
        # the fault hook NaN-poisons the batch: provenance stops at the
        # program boundary and names the poisoned input leaf
        assert prov["origin"] == "input" and "batch.x" in prov["leaf"]
        assert prov["step"] == 2

    def test_provenance_without_recorder_names_poisoned_leaf(
            self, tmp_path):
        """nan_provenance is default-on and recorder-independent: with NO
        flight recorder installed, the post-mortem must still replay the
        fault-hook-poisoned batch (the stashed step inputs) and name the
        leaf — not silently interpret a clean batch and find nothing."""
        j = obs_journal.EventJournal(clock=lambda: 0.0)
        obs_journal.set_journal(j)
        try:
            assert obs_numerics.get_recorder() is None
            tr = make_trainer()
            rt = ResilientTrainer(tr, str(tmp_path / "norec"), save_every=0)
            with faults.inject(faults.FaultPlan([(2, "grad_nan")])):
                for s in range(1, 4):
                    rt.step(make_batch(seed=s))
            rt.close()
            prov, = j.of_kind("nan_provenance")
            assert prov["origin"] == "input" and "batch.x" in prov["leaf"]
            assert not j.of_kind("flight_dump")   # dump needs a recorder
        finally:
            obs_journal.set_journal(None)

    def test_flight_dump_bitwise_identical_across_replays(self, tmp_path):
        d1 = strip(self.run_poisoned(tmp_path, "r1").of_kind("flight_dump"))
        d2 = strip(self.run_poisoned(tmp_path, "r2").of_kind("flight_dump"))
        assert json.dumps(d1, sort_keys=True) == \
            json.dumps(d2, sort_keys=True)

    def test_rollback_dumps_the_ring(self, tmp_path, recorder, journal):
        tr = make_trainer()
        rt = ResilientTrainer(tr, str(tmp_path), save_every=1,
                              max_consecutive_anomalies=2)
        rt.step(make_batch(seed=0))   # checkpoint lands at step 1
        # a skipped step's number is reused, so consecutive anomalies are
        # scheduled at the SAME step (the test_resilience convention)
        plan = faults.FaultPlan([(2, "grad_nan"), (2, "grad_nan")])
        with faults.inject(plan):
            rt.step(make_batch(seed=1))
            m = rt.step(make_batch(seed=2))
        rt.close()
        assert m.get("rolled_back_to") == 1
        reasons = [e["reason"] for e in journal.of_kind("flight_dump")]
        assert reasons == ["nan_skip", "rollback"]


# -------------------------------------------------- divergence detection

class TestDivergence:
    def test_detector_names_step_worker_shard(self, journal):
        det = obs_divergence.DivergenceDetector()
        out = det.check(7, {0: {"layers.0": 5, "layers.1": 9},
                            1: {"layers.0": 6, "layers.1": 9},
                            2: {"layers.0": 5, "layers.1": 9}})
        assert out == [{"step": 7, "worker": 1, "shard": "layers.0",
                        "fingerprint": 6, "expected": 5}]
        ev, = journal.of_kind("replica_divergence")
        assert (ev["step"], ev["worker"], ev["shard"]) == (7, 1, "layers.0")
        assert obs_divergence.detected()
        obs_divergence.reset_detected()

    def test_lingering_divergence_journals_once(self, journal):
        """A corrupted replica stays divergent every later step; the
        journal entry, stored event, and flight dump fire once per
        (worker, shard) — repeats only tick the counter."""
        det = obs_divergence.DivergenceDetector()
        for s in (1, 2, 3):
            out = det.check(s, {0: {"g": 1}, 1: {"g": 2}})
            assert len(out) == 1    # still reported to the caller
        assert len(journal.of_kind("replica_divergence")) == 1
        assert len(det.events) == 1 and det.first["step"] == 1
        # a NEW shard diverging later still journals
        det.check(4, {0: {"g": 1, "h": 5}, 1: {"g": 2, "h": 6}})
        assert len(journal.of_kind("replica_divergence")) == 2
        obs_divergence.reset_detected()

    def test_agreeing_replicas_journal_nothing(self, journal):
        det = obs_divergence.DivergenceDetector()
        assert det.check(1, {0: {"g": 3}, 1: {"g": 3}}) == []
        assert not journal.of_kind("replica_divergence")
        assert not obs_divergence.detected()

    def test_fingerprint_board_roundtrip(self, tmp_path, journal):
        board = obs_divergence.FingerprintBoard(str(tmp_path))
        fps = {"layers.0": 11, "layers.1": 22}
        for r in range(3):
            board.post(4, r, fps if r != 2
                       else {"layers.0": 99, "layers.1": 22})
        det = obs_divergence.DivergenceDetector()
        out = board.compare(4, [0, 1, 2], det, timeout_s=2.0)
        assert out[0]["worker"] == 2 and out[0]["shard"] == "layers.0"
        board.prune(keep_after=4)
        assert board.take(4, 0) is None
        obs_divergence.reset_detected()

    def test_two_worker_gang_divergence_smoke(self, tmp_path, journal,
                                              recorder):
        """Tier-1 smoke: a 2-worker gang with one injected post-reduce
        bit flip journals replica_divergence naming the exact
        step/worker/shard; the same gang without the fault journals
        nothing."""
        data = make_data()
        tr = make_trainer()
        g = ElasticGang(tr, str(tmp_path / "flip"), world_size=2,
                        data_fn=lambda s: data[s - 1],
                        global_batch_size=16, seed=0, save_every=0,
                        numerics=True)
        plan = faults.FaultPlan([(2, faults.Fault("bit_flip", worker=1,
                                                  arg=5))])
        with faults.inject(plan):
            g.run_until(3)
        ev, = journal.of_kind("replica_divergence")
        assert ev["step"] == 2 and ev["worker"] == 1
        assert ev["shard"]  # names the parameter group
        assert g.divergence.first["worker"] == 1
        assert not plan.remaining()

    def test_manifest_records_fingerprints_beside_crcs(self, tmp_path):
        sd = {"layers.0.w": np.arange(12, dtype=np.float32),
              "layers.1.w": np.ones((4,), np.float32)}
        d = str(tmp_path)
        for r in range(2):
            gang.save_shard(d, r, 2, 3, sd)
        gang.write_manifest(d, 3, 0, 2)
        man = gang.read_manifest(gang.manifest_path(d, 3))
        for r in range(2):
            ent = man["shards"][str(r)]
            own = {k: v for k, v in sd.items()
                   if gang.shard_owner(k, 2) == r}
            assert ent["crc32"] is not None
            assert ent["fingerprint"] == \
                obs_numerics.host_state_fingerprint(own)
            assert ent["fingerprint_groups"] == \
                obs_numerics.host_tree_fingerprints(own)

    def test_old_manifests_without_fingerprints_stay_loadable(
            self, tmp_path):
        """MIGRATING contract: a manifest written without the sidecar
        (pre-PR-10 build) has no fingerprint field and must still load."""
        sd = {"layers.0.w": np.arange(8, dtype=np.float32)}
        d = str(tmp_path)
        for r in range(2):
            p = gang.save_shard(d, r, 2, 5, sd)
            os.remove(p + ".fp.json")   # simulate the old writer
        gang.write_manifest(d, 5, 0, 2)
        man = gang.read_manifest(gang.manifest_path(d, 5))
        assert "fingerprint" not in man["shards"]["0"]
        step, generation, loaded, _extra, _rep = \
            gang.load_gang_checkpoint(d)
        assert step == 5 and set(loaded) == set(sd)

    def test_fleet_comparison_over_published_snapshots(self, tmp_path):
        """/fleet/divergence: two workers publish fingerprint gauges at
        the same step with one disagreeing group; a third lags a step
        and is unsynchronized, not divergent."""
        from hetu_tpu.obs import MetricsRegistry
        from hetu_tpu.obs.fleet import FleetAggregator, SnapshotPublisher

        def publish(rank, step, fps):
            reg = MetricsRegistry()
            fam = reg.gauge("hetu_numerics_param_fingerprint", "fp",
                            ("group",))
            for g, v in fps.items():
                fam.labels(group=g).set(float(v))
            reg.gauge("hetu_numerics_fingerprint_step", "step").set(
                float(step))
            SnapshotPublisher(str(tmp_path), rank, registry=reg,
                              journal=obs_journal.EventJournal(
                                  clock=lambda: 0.0),
                              clock=lambda: 100.0).publish()

        publish(0, 6, {"layers.0": 10, "layers.1": 20})
        publish(1, 6, {"layers.0": 77, "layers.1": 20})
        publish(2, 5, {"layers.0": 10, "layers.1": 20})
        agg = FleetAggregator(str(tmp_path), clock=lambda: 100.0)
        agg.refresh()
        rep = agg.divergence()
        assert rep["divergent"] and rep["unsynchronized"]
        f, = rep["findings"]
        assert (f["step"], f["worker"], f["shard"]) == (6, 1, "layers.0")
        # the finding also flags /fleet/healthz
        hz = agg.healthz()
        assert hz["status"] == "degraded"
        assert any(fl["flag"] == "replica_divergence"
                   for fl in hz["flags"])


# ------------------------------------------------- chaos acceptance (4w)

class TestChaosAcceptance:
    PLAN = [(3, ("grad_nan", 2)), (5, ("bit_flip", 1, 7))]

    def run(self, tmp_path, tag):
        obs_divergence.reset_detected()
        data = make_data()
        j = obs_journal.EventJournal(clock=lambda: 0.0)
        obs_journal.set_journal(j)
        rec = obs_numerics.FlightRecorder(capacity=8)
        obs_numerics.install(rec)
        try:
            tr = make_trainer()
            g = ElasticGang(tr, str(tmp_path / tag), world_size=4,
                            data_fn=lambda s: data[s - 1],
                            global_batch_size=16, seed=0, save_every=2,
                            partial=PartialReduceConfig(deadline=0.0,
                                                        tau=4),
                            numerics=True)
            events = [(3, faults.Fault("grad_nan", worker=2)),
                      (5, faults.Fault("bit_flip", worker=1, arg=7))]
            plan = faults.FaultPlan(events)
            with faults.inject(plan):
                g.run_until(8)
            assert not plan.remaining()
            return g, j
        finally:
            obs_numerics.install(None)
            obs_journal.set_journal(None)

    def test_detector_names_exact_step_worker_shard(self, tmp_path):
        g, j = self.run(tmp_path, "a")
        div, = j.of_kind("replica_divergence")
        assert (div["step"], div["worker"]) == (5, 1)
        assert div["shard"].startswith("layers.")
        assert div["fingerprint"] != div["expected"]
        # NaN provenance names where the poison entered (the batch leaf
        # the worker-targeted grad_nan poisoned)
        prov, = j.of_kind("nan_provenance")
        assert prov["step"] == 3 and prov["origin"] == "input"
        assert "batch.x" in prov["leaf"]
        # the divergence triggered a flight dump
        reasons = [e["reason"] for e in j.of_kind("flight_dump")]
        assert "divergence" in reasons
        # the reducer excluded the poisoned contribution
        assert any(e["reason"] == "nonfinite_contribution"
                   for e in j.of_kind("stale_drop"))

    def test_flight_dump_bitwise_identical_same_seed(self, tmp_path):
        _g1, j1 = self.run(tmp_path, "r1")
        _g2, j2 = self.run(tmp_path, "r2")
        s1 = json.dumps(strip(j1.of_kind("flight_dump")), sort_keys=True)
        s2 = json.dumps(strip(j2.of_kind("flight_dump")), sort_keys=True)
        assert s1 == s2
        assert strip(numerics_events(j1)) == strip(numerics_events(j2))

    def test_clean_run_journals_zero_numerics_events(self, tmp_path):
        obs_divergence.reset_detected()
        data = make_data()
        j = obs_journal.EventJournal(clock=lambda: 0.0)
        obs_journal.set_journal(j)
        rec = obs_numerics.FlightRecorder(capacity=8)
        obs_numerics.install(rec)
        try:
            tr = make_trainer()
            g = ElasticGang(tr, str(tmp_path / "clean"), world_size=4,
                            data_fn=lambda s: data[s - 1],
                            global_batch_size=16, seed=0, save_every=2,
                            partial=PartialReduceConfig(deadline=0.0,
                                                        tau=4),
                            numerics=True)
            g.run_until(8)
            assert numerics_events(j) == []
            assert not obs_divergence.detected()
            assert g.divergence.checks == 8
        finally:
            obs_numerics.install(None)
            obs_journal.set_journal(None)


# ------------------------------------------------------- serving seam

class TestServingFingerprints:
    def make_engine(self, seed=0):
        from hetu_tpu.models.gpt import GPT, GPTConfig
        from hetu_tpu.serve import ServingEngine
        set_random_seed(0)
        cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                        num_heads=2, max_seq_len=64)
        return ServingEngine(GPT(cfg), num_slots=2, page_size=4,
                             sampling="top_k", top_k=5, seed=seed)

    def run_stream(self):
        eng = self.make_engine()
        h = eng.submit([1, 2, 3], max_new_tokens=6)
        eng.run_until_idle()
        assert h.status == "completed"
        return h

    def test_stream_fingerprint_matches_tokens_and_replays(self):
        h1 = self.run_stream()
        assert h1.stream_fingerprint == \
            obs_numerics.host_fingerprint_ints(h1.tokens)
        h2 = self.run_stream()
        assert h2.tokens == h1.tokens
        assert h2.stream_fingerprint == h1.stream_fingerprint

    def test_infer_response_carries_stream_fingerprint(self):
        from hetu_tpu.serve import serve_engine
        eng = self.make_engine()
        srv = serve_engine(eng)
        try:
            req = urllib.request.Request(
                srv.url + "/infer",
                data=json.dumps({"prompt": [1, 2, 3],
                                 "max_new_tokens": 4}).encode(),
                method="POST")
            with urllib.request.urlopen(req, timeout=30) as r:
                body = json.loads(r.read())
            assert body["stream_fingerprint"] == \
                obs_numerics.host_fingerprint_ints(body["tokens"])
        finally:
            srv.stop()
            eng.stop()


# ------------------------------------------------------- endpoints/flags

class TestEndpoints:
    def test_healthz_red_flags_and_numerics_endpoint(self, recorder):
        from hetu_tpu.obs.server import serve
        srv = serve()

        def get(p):
            with urllib.request.urlopen(srv.url + p, timeout=10) as r:
                return json.loads(r.read())
        try:
            assert get("/healthz")["status"] == "ok"
            recorder.note_outcome(False)
            h = get("/healthz")
            assert h["status"] == "unhealthy"
            assert h["flags"][0] == {"flag": "nonfinite_streak",
                                     "streak": 1}
            recorder.note_outcome(True)
            assert get("/healthz")["status"] == "ok"
            # a detected divergence flags it too
            det = obs_divergence.DivergenceDetector()
            det.check(1, {0: {"g": 1}, 1: {"g": 2}})
            h = get("/healthz")
            assert any(f["flag"] == "replica_divergence"
                       for f in h["flags"])
            obs_divergence.reset_detected()
            # /numerics: the recorder surface
            tr = make_trainer()
            tr.step(make_batch())
            n = get("/numerics")
            assert n["recorder"]["steps"] == 1
            assert n["param_fingerprints"]["fingerprints"]
        finally:
            srv.stop()
