"""Executor / Trainer — the user-facing run loop.

The reference's ``Executor`` (reference: python/hetu/gpu_ops/executor.py:430)
owns named subgraphs ({'train': ..., 'validate': ...}), a ``run(feed_dict)``
loop that walks a topo order calling kernels, manual stream/event overlap, a
memory planner, and checkpoint save/load.  Under XLA the topo walk, memory
plan, and stream overlap are the compiler's job, so the TPU-native executor
is thin: it jits step functions, carries a functional ``TrainState``, applies
the sharding strategy (hetu_tpu/parallel), and keeps API parity with
``run('train', feed_dict)``.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from hetu_tpu.core.module import Module, trainable_mask
from hetu_tpu.core.rng import next_key
from hetu_tpu.obs import compile as _obs_compile
from hetu_tpu.obs import goodput as _obs_goodput
from hetu_tpu.obs import memledger as _obs_memledger
from hetu_tpu.obs import numerics as _obs_numerics
from hetu_tpu.obs import registry as _obs
from hetu_tpu.obs import tracing as _obs_tracing
from hetu_tpu.optim.optimizers import Optimizer

__all__ = ["TrainState", "Trainer", "Executor"]

# Fault-injection seam (exec.faults.install wires this up; None in
# production).  Called with ("grad", batch) before each train step; a
# non-None return replaces the batch — the deterministic NaN-poisoning
# path of the chaos harness (a NaN input poisons every gradient).
_fault_hook = None

# Train-loop metric families, built on first instrumented step (never
# while telemetry is disabled — the disabled path must register nothing).
_step_metrics = None


def _step_m() -> dict:
    global _step_metrics
    if _step_metrics is None:
        reg = _obs.get_registry()
        _step_metrics = {
            "latency": reg.histogram(
                "hetu_step_latency_seconds",
                "Trainer.step wall latency (host-side, dispatch-"
                "inclusive; device time is exec.profiler's job)"),
            "steps": reg.counter(
                "hetu_train_steps_total",
                "train steps by outcome (ok, or skipped by the anomaly "
                "guard)", ("outcome",)),
            "examples": reg.counter(
                "hetu_train_examples_total",
                "examples consumed by committed train steps"),
            "eps": reg.gauge(
                "hetu_examples_per_second",
                "throughput of the most recent committed step"),
            "grad_norm": reg.gauge(
                "hetu_grad_norm",
                "global gradient L2 norm of the last committed step "
                "(guarded trainers only — the plain program carries no "
                "grad_norm)"),
        }
    return _step_metrics


def _batch_examples(batch) -> int:
    """Leading dim of the first array-ish leaf — the batch size for
    throughput accounting (0 when the batch carries no arrays)."""
    for leaf in jax.tree_util.tree_leaves(batch):
        shape = getattr(leaf, "shape", None)
        if shape:
            return int(shape[0])
    return 0


def _global_grad_norm(grads):
    """Global L2 norm over every floating grad leaf — the anomaly signal
    the resilience layer watches (a single NaN/Inf anywhere in the grads
    makes it non-finite).  float32 accumulation so bf16 models do not
    overflow the sum of squares."""
    total = jnp.zeros((), jnp.float32)
    for g in jax.tree_util.tree_leaves(grads):
        if hasattr(g, "dtype") and jnp.issubdtype(g.dtype, jnp.floating):
            total = total + jnp.sum(jnp.square(g.astype(jnp.float32)))
    return jnp.sqrt(total)


def _apply_refreshes(model):
    """Fold HBM-cached embeddings' pending refresh leaves into their cache
    (embed.HBMCachedEmbedding.apply_refresh) — inside jit, so the scatter
    rides the step's dispatch and the merged cache persists in the new
    state."""
    is_hbm = lambda x: getattr(x, "is_hbm_cached_embedding", False)  # noqa
    return jax.tree_util.tree_map(
        lambda m: m.apply_refresh() if is_hbm(m) else m, model,
        is_leaf=is_hbm)


def _find_staged(tree) -> list:
    """Collect StagedHostEmbedding modules (duck-typed via the
    ``is_staged_host_embedding`` class marker, avoiding an import of
    hetu_tpu.embed).  Uses jax's own flatten order so the list pairs up with
    the same walk over the traced grads tree."""
    def is_staged(x):
        return getattr(x, "is_staged_host_embedding", False)

    return [x for x in jax.tree_util.tree_leaves(tree, is_leaf=is_staged)
            if is_staged(x)]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    model: Any
    opt_state: Any

    @property
    def step(self):
        return self.opt_state["step"]


class Trainer:
    """Builds and jits the train/eval step.

    ``loss_fn(model, batch, key) -> (loss, aux)`` where ``aux`` is a dict of
    scalars; if the model carries functional state (BatchNorm), ``aux`` may
    include the updated model under the reserved key ``"model"`` (it is
    extracted, not treated as a metric).
    """

    def __init__(self, model: Module, optimizer: Optimizer,
                 loss_fn: Callable, *, strategy=None, donate: bool = True,
                 memory_plan=None):
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.strategy = strategy
        # mem.planner.MemoryPlan (or None): the planner's (policy,
        # microbatch) decision this trainer is expected to run under.
        # Stored for audit and published to the metrics registry so
        # /metrics shows planned-vs-actual peak bytes side by side; the
        # policy itself lives in the model config (maybe_remat reads it).
        self.memory_plan = memory_plan
        if memory_plan is not None and _obs.enabled():
            from hetu_tpu.mem.estimator import record_memory_gauges
            record_memory_gauges(
                predicted=memory_plan.predicted_peak_bytes)
        # Recorded so wrappers (exec.resilience) can tell whether the
        # pre-step state survives the jitted call; strategies always jit
        # with donation (strategies.py install).
        self.donate = bool(donate) or strategy is not None
        # Optional commit gate: ``grad_guard(metrics) -> bool`` runs after
        # the jitted step but BEFORE the new state is committed and staged
        # embedding grads are pushed; returning False discards the update
        # (metrics come back with ``skipped=True``).  The resilience
        # layer's NaN/Inf anomaly policy hangs here — rejecting before the
        # staged push matters, because a NaN pushed to a parameter server
        # cannot be rolled back.  Attach BEFORE the first step: the guard's
        # ``grad_norm`` metric is added at trace time.
        self.grad_guard: Optional[Callable[[dict], bool]] = None
        # (batch, key) of the last step that carried numerics stats —
        # the NaN-provenance post-mortem replays these exact inputs
        self._last_step_inputs: Optional[tuple] = None
        self._state = TrainState(model, optimizer.init(model))
        # Non-trainable state (BatchNorm statistics) must not see weight decay
        # or moment updates; the mask is static model structure, closed over.
        param_mask = trainable_mask(model)
        # Staged host embeddings (embed.StagedHostEmbedding): the step must
        # hand their rows-gradients back to the host engine (SparsePush).
        self._has_staged = bool(_find_staged(model))
        if self._has_staged and strategy is not None:
            raise ValueError(
                "StagedHostEmbedding is incompatible with sharding "
                "strategies that repartition the model (each worker owns "
                "its own host store, like the reference's PS workers); "
                "drop the strategy or use the io_callback HostEmbedding")

        # the scope names the step's operations in a device trace
        @jax.named_scope("train.step")
        def train_step(state: TrainState, batch, key):
            def wrapped(model):
                loss, aux = loss_fn(model, batch, key)
                new_model = aux.pop("model", None)
                return loss, (aux, new_model)

            model0 = (_apply_refreshes(state.model) if self._has_staged
                      else state.model)
            (loss, (aux, new_model)), grads = jax.value_and_grad(
                wrapped, has_aux=True
            )(model0)
            base = new_model if new_model is not None else model0
            params, opt_state = optimizer.update(
                grads, state.opt_state, base, mask=param_mask
            )
            metrics = {"loss": loss, **aux}
            # trace-time check: only guarded trainers (exec.resilience
            # attaches grad_guard before the first step) pay for the
            # all-gradients reduction; a plain Trainer's program — and the
            # benchmarked scan_steps path — is unchanged
            if self.grad_guard is not None:
                metrics["grad_norm"] = _global_grad_norm(grads)
            # trace-time check, same rule as grad_guard: only trainers
            # built while a flight recorder is installed
            # (obs.numerics.install) trace the tensor stats — per-group
            # grad norms/max-abs/nonfinite/zero-fraction plus the
            # deterministic bitcast-uint32 fingerprints of the UPDATED
            # params — into the step program.  They ride the step's
            # outputs as device scalars, so recording adds no host sync;
            # a plain Trainer's program is unchanged.
            if _obs_numerics.recording():
                metrics["_numerics"] = {
                    "grad": _obs_numerics.group_stats(grads),
                    "param_fp": _obs_numerics.tree_fingerprints(params),
                }
            if self._has_staged:
                metrics["_staged_rows_grads"] = [
                    m.rows for m in _find_staged(grads)]
            return TrainState(params, opt_state), metrics

        def eval_step(state: TrainState, batch):
            loss, aux = loss_fn(state.model, batch, None)
            aux.pop("model", None)
            return {"loss": loss, **aux}

        if strategy is not None:
            train_step, eval_step, self._state = strategy.install(
                train_step, eval_step, self._state
            )
        else:
            # staged host embeddings: NEVER donate the state.  stage()
            # re-installs leaf objects from the previous state (the reused
            # zeros ``rows`` buffer, the HBM cache between refreshes), so
            # donating hands XLA buffers the host-side staging protocol
            # still references — observed as a use-after-free when the
            # persistent compile cache serves the step executable (the
            # deserialized aliasing config bypasses the compile-time
            # "donated buffer not usable" rejection that masked this).
            donate_args = (0,) if donate and not self._has_staged else ()
            train_step = jax.jit(train_step, donate_argnums=donate_args)
            eval_step = jax.jit(eval_step)
        # compile-counting seams (obs.compile watch mode: the wrapped jit
        # keeps dispatching — donation/sharding strategies unchanged — and
        # the disabled path stays one global load + branch).  A recompile
        # here is a shape-signature change the journal names.
        self._train_step = _obs_compile.watch(train_step, site="train.step")
        self._eval_step = _obs_compile.watch(eval_step, site="train.eval")
        # memory-ledger seam: weights/optimizer bytes of the initial
        # state (re-posted whenever the state is rebound — the setter)
        _obs_memledger.note_train_state(self._state)

    @property
    def state(self) -> TrainState:
        return self._state

    @state.setter
    def state(self, s: TrainState):
        self._state = s
        # a rebind (checkpoint restore, rescale) may change leaf shapes/
        # dtypes: re-post the ledger's train-state bytes
        _obs_memledger.note_train_state(s)

    @property
    def model(self):
        return self._state.model

    def staged_modules(self) -> list:
        """StagedHostEmbedding modules of the CURRENT model (re-walk every
        step: optimizer updates replace the module objects).  Call
        ``m.stage(ids)`` on each before ``step``; the gradient push back to
        the host engine happens automatically inside ``step``."""
        return _find_staged(self._state.model)

    def step(self, batch, key=None) -> dict:
        """One train step.  With telemetry enabled (the default) the
        step's wall latency, outcome, and throughput land in the process
        metrics registry, and — when the tracer is recording — the step
        becomes a ``train.step`` span that parents any PS RPC spans
        issued inside it, with two children: ``train.step.dispatch`` (the
        call of the jitted step) and ``train.step.host`` (everything
        after it).  With telemetry disabled the cost over the
        bare step is one module-global load and branch."""
        if not _obs.enabled():
            return self._step_impl(batch, key)
        t0 = time.perf_counter()
        with _obs_tracing.span("train.step"):
            batch_in, key = self._step_inputs(batch, key)
            with _obs_tracing.span("train.step.dispatch"):
                new_state, metrics = self._train_step(self._state, batch_in,
                                                      key)
            with _obs_tracing.span("train.step.host"):
                metrics = self._commit(new_state, metrics, batch_in, key)
                self._record_step(batch, metrics, time.perf_counter() - t0)
        return metrics

    def _record_step(self, batch, metrics: dict, dt: float) -> None:
        """The step's latency, outcome and throughput into the registry."""
        m = _step_m()
        skipped = bool(metrics.get("skipped"))
        m["steps"].labels(outcome="skipped" if skipped else "ok").inc()
        m["latency"].observe(dt)
        # online goodput accounting: one global load + branch when no
        # meter is installed (obs.goodput.install_meter), same contract
        # as the rest of this seam
        _obs_goodput.record_step(dt, skipped=skipped)
        if not skipped:
            n = _batch_examples(batch)
            if n:
                m["examples"].inc(n)
                if dt > 0:
                    m["eps"].set(n / dt)
            if "grad_norm" in metrics:
                # guarded trainers already fetched this to the host in
                # grad_guard, so the float() here is a cached read, not a
                # fresh device sync
                m["grad_norm"].set(float(metrics["grad_norm"]))

    def _step_impl(self, batch, key=None) -> dict:
        """The bare step: what :meth:`step` does, less the telemetry."""
        batch, key = self._step_inputs(batch, key)
        new_state, metrics = self._train_step(self._state, batch, key)
        return self._commit(new_state, metrics, batch, key)

    def _step_inputs(self, batch, key) -> tuple:
        """(batch, key) as the jitted step takes them."""
        if key is None:
            key = next_key()
        if _fault_hook is not None:
            poisoned = _fault_hook("grad", batch)
            if poisoned is not None:
                batch = poisoned
        if self._has_staged:
            # validate freshness BEFORE the jitted step runs: a step on
            # stale rows would advance the dense params on wrong gradients
            # before push_grads could catch the mistake
            for m in _find_staged(self._state.model):
                if not m.is_fresh():
                    raise RuntimeError(
                        "staged host embedding has no fresh rows: call "
                        "stage(ids) on every module from staged_modules() "
                        "before each training step")
        return batch, key

    def _commit(self, new_state, metrics: dict, batch, key) -> dict:
        """What follows the dispatch: numerics ring, commit gate, the new
        state, staged pushes."""
        ns = metrics.pop("_numerics", None)
        if ns is not None:
            # ring the device scalars as-is (no fetch, no sync)
            _obs_numerics.observe(ns)
        if ns is not None or self.grad_guard is not None:
            # post-fault-hook batch/key stashed so the resilience layer's
            # NaN-provenance post-mortem replays the EXACT inputs —
            # including a fault-hook-poisoned batch.  Guarded trainers
            # stash with or without a flight recorder: provenance is
            # default-on and must not silently replay a clean batch.
            self._last_step_inputs = (batch, key)
        if self.grad_guard is not None and not self.grad_guard(metrics):
            # rejected update: keep the pre-step state, drop the staged
            # grads (never push an anomalous gradient to the host/PS
            # stores — there is no undo on that side)
            metrics.pop("_staged_rows_grads", None)
            metrics["skipped"] = True
            return metrics
        self._state = new_state
        if self._has_staged:
            gs = metrics.pop("_staged_rows_grads")
            for m, g in zip(_find_staged(self._state.model), gs):
                m.push_grads(g)
        return metrics

    def evaluate(self, batch) -> dict:
        """Eval step.  With staged host embeddings (StagedHostEmbedding) the
        caller must ``stage`` the EVAL batch's ids on each module from
        ``staged_modules()`` first — the jitted program reads the staged
        rows leaf, not the batch ids."""
        return self._eval_step(self._state, batch)

    def scan_steps(self, n_steps: int):
        """Compile ``n_steps`` train steps into ONE program (a ``lax.scan``
        over the step body) and return ``run(state, batch, key) ->
        (new_state, last_metrics)`` — the final step's full metrics dict
        (loss plus whatever the loss_fn's aux carries, e.g. MoE routing
        stats), so a compiled loop costs no extra per-metric dispatch.

        Two uses: (1) amortizing per-dispatch host cost when batches repeat
        or are generated on-device — the reference's SubExecutor batches
        kernel launches per run() for the same reason (executor.py:430);
        (2) device-time benchmarking: timing run(k) and run(2k) and
        differencing cancels the fixed dispatch overhead exactly, leaving
        pure device time per step.

        The batch is FIXED across the n steps; the RNG key is split once
        per step inside the scan, so dropout stays honest.  Feed the
        returned state back in (the state argument is donated).  Not
        supported with staged host embeddings: their per-step host
        push/stage cannot live inside a compiled loop."""
        if self._has_staged:
            raise ValueError(
                "scan_steps cannot run staged host embeddings: stage()/"
                "push_grads() are per-step host work (use the io_callback "
                "HostEmbedding or the plain step loop)")
        train_step = self._train_step  # inlined when traced under jit

        def run(state: TrainState, batch, key):
            def body(carry, _):
                st, k = carry
                k, sub = jax.random.split(k)
                st, metrics = train_step(st, batch, sub)
                return (st, k), metrics

            (state, _), stacked = jax.lax.scan(
                body, (state, key), None, length=n_steps)
            return state, jax.tree_util.tree_map(lambda x: x[-1], stacked)

        # the step watcher passes tracer-stage calls through (the scan's
        # program owns the compile), so the scan gets its own counted site
        return _obs_compile.watch(jax.jit(run, donate_argnums=(0,)),
                                  site="train.scan")

    def profile(self, batch, key=None, iters: int = 10) -> dict:
        """Wall-time + cost profile of one train step on the given batch
        (reference executor.profile, executor.py:501).  Includes the
        compiled step's ``memory_analysis()`` byte sizes
        (``argument_bytes``/``output_bytes``/``temp_bytes``) and — with
        telemetry enabled — publishes them as ``hetu_mem_xla_*`` gauges
        on /metrics, next to the planner's predicted peak."""
        from hetu_tpu.exec.profiler import profile_fn
        if key is None:
            key = next_key()
        prof = profile_fn(self._train_step, self._state, batch, key,
                          iters=iters)
        if self.memory_plan is not None:
            prof["memory_plan"] = self.memory_plan.describe()
            prof["predicted_peak_bytes"] = \
                self.memory_plan.predicted_peak_bytes
        if _obs.enabled() and prof.get("temp_bytes") is not None:
            from hetu_tpu.mem.estimator import (reconcile,
                                                record_memory_gauges)
            record_memory_gauges(xla=prof)
            # reconcile the planner's predicted device peak against the
            # compiled step's own memory_analysis bytes: publishes the
            # hetu_mem_estimator_error_ratio gauge, journals
            # mem_estimate_drift outside the 25% band, and feeds the
            # installed calibration store (the measured correction
            # plan_memory(calibration=) later divides by)
            if self.memory_plan is not None:
                xla_peak = (float(prof.get("argument_bytes") or 0.0)
                            + float(prof.get("temp_bytes") or 0.0))
                r = reconcile(self.memory_plan.predicted_peak_bytes,
                              xla_peak, model_sig="train.step")
                prof["estimator_error_ratio"] = r["ratio"]
        return prof


class Executor:
    """Named-subgraph facade for reference API parity (executor.py:430).

    ``Executor({'train': trainer.step, 'validate': trainer.evaluate}})`` —
    or construct from a Trainer directly: ``Executor.from_trainer(trainer)``.
    ``run(name, feed_dict)`` invokes the named step with the feeds.
    """

    def __init__(self, subgraphs: dict, logger=None):
        self.subgraphs = dict(subgraphs)
        self.logger = logger

    @classmethod
    def from_trainer(cls, trainer: Trainer, logger=None) -> "Executor":
        return cls({"train": trainer.step, "validate": trainer.evaluate},
                   logger=logger)

    def run(self, name: str, feed_dict=None, **kw):
        fn = self.subgraphs[name]
        out = fn(feed_dict, **kw) if feed_dict is not None else fn(**kw)
        if self.logger is not None and isinstance(out, dict):
            for k, v in out.items():
                self.logger.log(k, v)
            self.logger.step()
        return out
