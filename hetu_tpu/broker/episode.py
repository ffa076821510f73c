"""One deterministic diurnal episode: gang + fleet + broker on a
virtual clock.

The acceptance test (tests/test_broker.py) runs this driver: a seeded
diurnal trace (:func:`~hetu_tpu.serve.loadgen.generate_diurnal_load`)
is served by a fleet while an :class:`~hetu_tpu.exec.gang.ElasticGang`
trains on the remaining chips, and a :class:`CapacityBroker` (when
enabled) moves chips between them.  Training goodput is WORLD-AWARE:
each tick accrues ``live_world * tick_s`` chip-seconds of budget and a
step costs ``chip_seconds_per_step`` — so a lent chip is chip-time the
gang visibly loses and a reclaimed chip is chip-time it wins back,
which is exactly the trade the (SLO violations, training goodput)
dominance claim prices.

The day ends with an "overnight" phase of coarse ticks: traffic is
gone, the SLO burn windows drain, pressure releases past hysteresis,
and the broker reclaims its leases LIFO — the gang finishes the night
at full width.

Everything runs on one virtual clock and one private journal, so a
same-seed episode replays bitwise: lease journal, plan shas,
placements, token streams, loss trajectory (the returned dict carries
them all for exact comparison).

Part of the broker package, so the plan-determinism lint applies: no
wall clocks, no ambient randomness, no unordered dict walks.
"""

from __future__ import annotations

import os

import numpy as np

from hetu_tpu.broker.broker import BrokerConfig, CapacityBroker
from hetu_tpu.obs import journal as _journal
from hetu_tpu.obs import registry as _obs
from hetu_tpu.obs.slo import SLOTargets

__all__ = ["run_broker_episode", "EpisodeResult"]


class _VClock:
    """The episode's shared virtual clock."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


class _Rows:
    """The smallest PR 15 snapshot surface: a host row store with
    ``pull``/``set_rows`` — the training-side source and the lent
    chip's serving-side target both wear it."""

    def __init__(self, rows: int, dim: int):
        self.rows = int(rows)
        self.dim = int(dim)
        self.data = np.zeros((self.rows, self.dim), np.float32)

    def pull(self, ids):
        return self.data[np.asarray(ids, np.int64)]

    def set_rows(self, ids, rows):
        self.data[np.asarray(ids, np.int64)] = \
            np.asarray(rows, np.float32)


def _make_data_fn(seed: int, batch: int, dim: int):
    """Per-step seeded batches — deterministic for ANY step index, so
    the uninterrupted comparison run never outruns a data list."""
    def data_fn(s: int) -> dict:
        rng = np.random.default_rng(seed * 100003 + s)
        x = rng.standard_normal((batch, dim)).astype(np.float32)
        return {"x": x, "y": (x[:, 0] > 0).astype(np.int32)}
    return data_fn


class EpisodeResult(dict):
    """A plain dict with attribute sugar for the fields the dominance
    assertions read most."""

    @property
    def violations(self) -> int:
        return self["violations"]

    @property
    def goodput(self) -> int:
        return self["train_steps"]


def run_broker_episode(workdir: str, *, seed: int = 0,
                       brokered: bool = True, dry_run: bool = False,
                       train_world: int = 4, serve_replicas: int = 1,
                       n_requests: int = 96,
                       peak_gap_s: float = 0.033, tick_s: float = 0.05,
                       chip_seconds_per_step: float = 2.0,
                       overnight_ticks: int = 60,
                       overnight_tick_s: float = 2.0,
                       config: BrokerConfig = None,
                       max_ticks: int = 10000) -> EpisodeResult:
    """Run one seeded diurnal episode; returns the full evidence dict.

    ``brokered=False`` is a STATIC split (the A/B baselines): the same
    day with the broker disabled — pass the split's ``train_world`` /
    ``serve_replicas``.  ``dry_run=True`` runs the broker in decision-
    only mode (journals identical first decisions, actuates nothing).
    """
    from hetu_tpu.core import set_random_seed
    from hetu_tpu.embed.stream import SnapshotFollower, SnapshotWriter
    from hetu_tpu.exec.executor import Trainer
    from hetu_tpu.exec.gang import ElasticGang
    from hetu_tpu.models import MLP
    from hetu_tpu.models.gpt import GPT, GPTConfig
    from hetu_tpu.optim import SGDOptimizer
    from hetu_tpu.ops import softmax_cross_entropy_sparse
    from hetu_tpu.plan.apply import PlanApplier
    from hetu_tpu.plan.search import DeploymentPlanner
    from hetu_tpu.plan.spec import DeploymentSpec
    from hetu_tpu.serve.engine import ServingEngine
    from hetu_tpu.serve.fleet.router import FleetRouter
    from hetu_tpu.serve.loadgen import generate_diurnal_load
    from hetu_tpu.serve.tenant import Tenant, TenantPolicy

    clk = _VClock()
    gang_dir = os.path.join(workdir, "gang")
    snap_dir = os.path.join(workdir, "snap")
    os.makedirs(snap_dir, exist_ok=True)

    # construction order is part of the seed contract: MLP then GPT,
    # each drawing from the freshly reset global stream — every
    # scenario (brokered, static splits, the uninterrupted comparison)
    # reaches its first gang step at the identical RNG seqnum
    set_random_seed(seed)
    mlp = MLP((8, 16, 3))
    gpt = GPT(GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                        num_heads=2, max_seq_len=64))

    def loss_fn(model, batch, key):
        logits = model(batch["x"])
        return (softmax_cross_entropy_sparse(logits, batch["y"]).mean(),
                {})

    trainer = Trainer(mlp, SGDOptimizer(0.1), loss_fn, donate=False)
    data_fn = _make_data_fn(seed, 16, 8)

    policy = TenantPolicy([Tenant(id="interactive", klass="latency"),
                           Tenant(id="batch", klass="batch")])
    targets = SLOTargets(ttft_s=0.5, tpot_s=0.5, queue_age_s=0.25)
    trace = generate_diurnal_load(
        seed, n_requests, vocab=97, peak_gap_s=peak_gap_s,
        prompt_len=(2, 10), max_new=(1, 6),
        tenants=[{"id": "interactive", "share": 0.7,
                  "deadline_s": 0.3},
                 {"id": "batch", "share": 0.3, "max_new": (4, 8)}])

    def make_engine() -> ServingEngine:
        return ServingEngine(gpt, num_slots=2, page_size=4, seed=0,
                             clock=clk, queue_depth=64, tenants=policy,
                             slo_targets=targets)

    # the PR 15 warm-up surface: the training side streams versioned
    # snapshots of this row store; a granted chip's follower catches up
    # on the latest gated version before the replica may serve
    src = _Rows(32, 4)
    writer = SnapshotWriter(src, snap_dir, name="embed")

    journal = _journal.EventJournal(clock=clk)
    with _journal.use(journal):
        writer.publish(full=True)
        fleet = FleetRouter([make_engine()
                             for _ in range(serve_replicas)])
        spec = DeploymentSpec(
            n_devices=train_world + serve_replicas,
            serve_devices=serve_replicas)
        applier = PlanApplier(DeploymentPlanner(spec), dry_run=dry_run)

        broker = None
        gang_kwargs = {}
        if brokered:
            def factory(lease, plan):
                # the trainer's tables moved on since the last publish:
                # stamp a row with the current step and publish the
                # gated version the lent chip must catch up to
                src.set_rows([lease.lease_id % src.rows],
                             np.full((1, src.dim),
                                     float(gang.step_count),
                                     np.float32))
                writer.publish(full=True)
                engine = make_engine()
                target = _Rows(src.rows, src.dim)
                follower = SnapshotFollower(target, snap_dir,
                                            name="embed", clock=clk)

                def warm() -> bool:
                    follower.poll()
                    if follower.lag() == 0 and follower.installed > 0:
                        follower.gate()  # never serve stale weights
                        return True
                    return False

                return engine, warm

            broker = CapacityBroker(
                config if config is not None else BrokerConfig(
                    dry_run=dry_run, grant_on=0.9, grant_off=0.1,
                    sustain_ticks=2, cooldown_ticks=8,
                    chips_per_grant=1, min_train_world=3),
                fleet=fleet, planner=applier, replica_factory=factory,
                clock=clk, registry=_obs.MetricsRegistry())
            gang_kwargs["broker"] = broker

        gang = ElasticGang(trainer, gang_dir, world_size=train_world,
                           data_fn=data_fn, global_batch_size=16,
                           seed=seed, save_every=5, **gang_kwargs)

        submitted: list = []
        world_by_tick: list = []
        budget = 0.0

        def one_tick(dt: float) -> None:
            nonlocal budget
            fleet.step()
            if broker is not None:
                broker.tick()
            budget += gang.live_world * dt
            while budget >= chip_seconds_per_step:
                gang.run_until(gang.step_count + 1)
                budget -= chip_seconds_per_step
            world_by_tick.append(gang.live_world)

        # -- the day: trace submission + serving + training -----------
        i = 0
        ticks = 0
        while i < len(trace) or not fleet.idle:
            ticks += 1
            if ticks > max_ticks:
                raise RuntimeError(f"episode did not drain in "
                                   f"{max_ticks} ticks")
            while i < len(trace) and trace[i].submit_at <= clk.t:
                item = trace[i]
                handle = fleet.submit(list(item.prompt),
                                      item.max_new_tokens,
                                      deadline_s=item.deadline_s,
                                      tenant=item.tenant)
                submitted.append((i, item.tenant, item.phase, handle))
                i += 1
            one_tick(tick_s)
            clk.t += tick_s

        # -- overnight: windows drain, leases come home ----------------
        for _ in range(overnight_ticks):
            one_tick(overnight_tick_s)
            clk.t += overnight_tick_s

    # -- the evidence ---------------------------------------------------------
    violations = 0
    statuses: dict = {}
    for engine in fleet.engines:
        violations += sum(v for _t, v
                          in sorted(engine.slo.violations.items()))
    streams = {}
    for idx, _tenant, _phase, handle in submitted:
        statuses[handle.status] = statuses.get(handle.status, 0) + 1
        streams[idx] = [int(tok) for tok in
                        getattr(handle, "tokens", ()) or ()]
    events = list(journal.events)

    return EpisodeResult(
        seed=seed, brokered=brokered, dry_run=dry_run,
        violations=int(violations),
        train_steps=int(gang.step_count),
        final_world=int(gang.live_world),
        losses_by_step=dict(gang.losses_by_step),
        statuses=statuses,
        streams=streams,
        placements=list(fleet.placements),
        membership=fleet.membership,
        world_by_tick=world_by_tick,
        events=events,
        # journal seq counts compile events too, whose cache behaviour
        # is process-global — the broker record itself is deterministic
        lease_events=_journal.stable_events(
            [e for e in events
             if e.get("kind") in ("lease_grant", "lease_reclaim")]),
        decisions=_journal.stable_events(
            [e for e in events
             if e.get("kind") == "broker_decision"]),
        plan_shas=[e["sha256"] for e in events
                   if e.get("kind") == "plan_emit"],
        leases=([lease.as_dict() for lease in broker.leases]
                if broker is not None else []),
        chips_lent=(broker.lent() if broker is not None else 0),
        broker_summary=(broker.summary() if broker is not None
                        else None),
    )
