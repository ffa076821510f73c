"""Resilient training driver: the survival layer over ``exec.Trainer``.

Hetu's headline features are survival features — the cache-enabled PS
tolerates worker churn (HET, VLDB'22) and partial reduce rides out
stragglers (SIGMOD'21) — and the repo already has the low-level pieces
(PS reconnect/backoff in ``embed/net.py``, atomic/async checkpoints in
``exec/checkpoint.py``).  ``ResilientTrainer`` composes them into a
training loop that actually survives faults:

1. **Periodic async checkpointing** with rolling retention, a CRC32
   integrity footer on every file (``checkpoint._atomic_write``), and
   **auto-resume** that scans ``ckpt.step_*`` files newest-first and skips
   corrupt/torn ones with a clear ``CheckpointCorrupt``/``CheckpointError``
   diagnosis.
2. **NaN/Inf anomaly policy** on loss and grad-norm: skip-step (the update
   is rejected BEFORE it is committed or staged-embedding grads are pushed
   — via ``Trainer.grad_guard``), then rollback-to-last-checkpoint after
   ``max_consecutive_anomalies`` anomalies in a row.  A skipped step also
   rewinds the global RNG seqnum, so the surviving steps replay the exact
   key sequence of an uninjected run — fault-injected lineage stays bitwise
   identical (the chaos tests assert this).
3. **Preemption handling**: SIGTERM/SIGINT set a flag; at the next step
   boundary the driver performs a final SYNCHRONOUS save and raises
   :class:`Preempted` — the TPU-preemption shape (the maintenance notice
   arrives as SIGTERM, the process has seconds, the checkpoint must land).
4. **Per-step watchdog**: the device program runs under a deadline; a hang
   raises :class:`BackendUnresponsive` instead of wedging forever — the
   ``backend_unreachable`` failure in ``BENCH_r05.json`` sat for 240 s with
   no watchdog; this is that watchdog.

Faults are injected deterministically by ``exec.faults`` (the plan's step
counter is advanced here, at the top of every step).
"""

from __future__ import annotations

import os
import re
import signal
import threading
import time
from typing import Any, Optional

import jax.numpy as jnp
import jax.tree_util as jtu
import numpy as np

from hetu_tpu.core import get_seed_status, next_key, reset_seed_seqnum
from hetu_tpu.core.module import named_parameters
from hetu_tpu.exec import controller as _controller
from hetu_tpu.exec import faults as _faults
from hetu_tpu.exec.checkpoint import (AsyncCheckpointer, CheckpointError,
                                      load_checkpoint, load_state_dict,
                                      save_checkpoint)
from hetu_tpu.exec.partial import split_state_entries as _split_partial
from hetu_tpu.obs import goodput as _obs_goodput
from hetu_tpu.obs import journal as _obs_journal
from hetu_tpu.obs import numerics as _obs_numerics
from hetu_tpu.obs import registry as _obs

__all__ = ["ResilientTrainer", "BackendUnresponsive", "Preempted",
           "TrainingDiverged", "list_checkpoints", "latest_good_checkpoint",
           "checkpoint_path"]


class BackendUnresponsive(RuntimeError):
    """The device program did not complete within the watchdog deadline —
    a hung backend (lost device, wedged collective), not a slow step."""


class Preempted(Exception):
    """Raised at the step boundary after the final synchronous save that a
    SIGTERM/SIGINT triggered.  ``step`` is the last completed driver step;
    the checkpoint for it is on disk when this propagates."""

    def __init__(self, step: int, signum: int):
        super().__init__(
            f"preempted by signal {signum} at step {step}; final "
            f"checkpoint saved — restart and resume() to continue")
        self.step = step
        self.signum = signum


class TrainingDiverged(RuntimeError):
    """Anomalies kept coming after a rollback was impossible (no usable
    checkpoint) — the run cannot make progress."""


_CKPT_RE = re.compile(r"^ckpt\.step_(\d+)$")

# Resilience-event counters (the journal carries the full records; these
# are the scrapeable aggregates).  Built on first event, never while
# telemetry is disabled.
_res_metrics = None


def _res_m() -> dict:
    global _res_metrics
    if _res_metrics is None:
        reg = _obs.get_registry()
        _res_metrics = {
            "anomalies": reg.counter(
                "hetu_anomaly_skips_total",
                "train steps rejected by the NaN/Inf anomaly policy"),
            "rollbacks": reg.counter(
                "hetu_rollbacks_total",
                "checkpoint rollbacks after consecutive anomalies"),
            "watchdog": reg.counter(
                "hetu_watchdog_fires_total",
                "steps abandoned by the per-step watchdog"),
            "preemptions": reg.counter(
                "hetu_preemptions_total",
                "SIGTERM/SIGINT preemptions honored at a step boundary"),
        }
    return _res_metrics


def checkpoint_path(ckpt_dir: str, step: int) -> str:
    return os.path.join(ckpt_dir, f"ckpt.step_{step:08d}")


def list_checkpoints(ckpt_dir: str) -> list:
    """All ``ckpt.step_*`` files, ascending by step: ``[(step, path)]``."""
    out = []
    try:
        names = os.listdir(ckpt_dir)
    except FileNotFoundError:
        return out
    for name in names:
        m = _CKPT_RE.match(name)
        if m:
            out.append((int(m.group(1)), os.path.join(ckpt_dir, name)))
    out.sort()
    return out


def latest_good_checkpoint(ckpt_dir: str, restore_rng: bool = True):
    """Scan ``ckpt.step_*`` newest-first, skipping corrupt/torn files.

    Returns ``(step, path, state, extra, report)`` for the newest loadable
    checkpoint, or ``(None, None, None, None, report)`` when none loads.
    ``report`` lists every file examined as ``(step, path, diagnosis)``
    where diagnosis is ``None`` for the good one and the
    ``CheckpointError`` message (corrupt vs torn, from the CRC footer) for
    the skipped ones."""
    report = []
    for step, path in reversed(list_checkpoints(ckpt_dir)):
        try:
            state, extra = load_checkpoint(path, restore_rng=restore_rng)
        except CheckpointError as e:
            report.append((step, path, str(e)))
            continue
        except OSError as e:  # vanished between listdir and open
            report.append((step, path, f"unreadable: {e!r}"))
            continue
        report.append((step, path, None))
        return step, path, state, extra, report
    return None, None, None, None, report


def _staged_prefixes(tree) -> list:
    """Dotted-path prefixes of every StagedHostEmbedding subtree (in the
    model AND in optimizer moment trees, which mirror its structure).
    Their leaves are transient staging buffers whose shape tracks the last
    batch — the durable table state lives host/server-side and is
    checkpointed by the table's own save/autosave, so these are excluded
    from resilience checkpoints."""
    def is_staged(x):
        return getattr(x, "is_staged_host_embedding", False)

    prefixes = []
    for path, leaf in jtu.tree_flatten_with_path(
            tree, is_leaf=is_staged)[0]:
        if is_staged(leaf):
            name = ".".join(
                str(getattr(k, "name", getattr(k, "idx",
                                               getattr(k, "key", k))))
                for k in path)
            prefixes.append(name + ".")
    return prefixes


def _to_device(tree):
    # only lift numpy leaves: a python-scalar leaf must keep its weak
    # dtype, or resumed jit programs would promote differently and break
    # bitwise lineage
    return jtu.tree_map(
        lambda x: jnp.asarray(x) if isinstance(x, np.ndarray) else x, tree)


class ResilientTrainer:
    """Fault-surviving driver around a built :class:`~hetu_tpu.exec.Trainer`.

    ::

        tr = Trainer(model, opt, loss_fn, donate=False)
        rt = ResilientTrainer(tr, "ckpts/", save_every=100, keep=3,
                              step_timeout=300.0, handle_signals=True)
        start = rt.resume() or 0            # picks up after a crash
        for step, batch in enumerate(data, start + 1):
            metrics = rt.step(batch)        # may raise Preempted/
                                            #   BackendUnresponsive

    ``donate=False`` on the Trainer is REQUIRED when the anomaly policy is
    active: skip-step keeps the pre-step state alive, which donation would
    have handed to XLA.

    Knobs: ``save_every`` (checkpoint cadence in steps; 0 disables),
    ``keep`` (rolling retention), ``anomaly_policy`` (``"skip"`` |
    ``"raise"`` | ``"off"``), ``max_consecutive_anomalies`` (K: rollback to
    the last checkpoint after K rejected steps in a row),
    ``step_timeout`` (watchdog deadline in seconds; None disables — the
    deadline covers whatever the step does, INCLUDING the first step's jit
    compilation: warm the trainer up first or size it for compile+run),
    ``handle_signals`` (install SIGTERM/SIGINT final-save handlers),
    ``gang`` (a :class:`~hetu_tpu.exec.gang.GangCheckpointer`: saves
    become this worker's shard + ring replica + — on the manifest writer
    — the signed gang manifest, and resume/rollback compose the newest
    intact manifest instead of scanning monolithic files),
    ``partial`` (a :class:`~hetu_tpu.exec.partial.PartialReducer`: the
    reducer's pending late-gradient correction terms become part of
    every checkpoint — as reserved ``partialreduce.*`` state entries, so
    with ``gang=`` they are sharded, ring-replicated, and
    manifest-signed — and resume/rollback restore them, keeping
    kill/recover replays bitwise even mid-fold).

    Composition of partial reduce with the NaN/Inf anomaly policy: a
    non-finite *late fold* is rolled back by the reducer itself — the
    fold, not the step (``stale_drop`` with ``reason="nonfinite"`` in
    the journal) — so the guard here only ever skips steps whose own
    gradients are anomalous; checkpoints taken with ``partial=`` remain
    loadable by a partial-less trainer (the reserved entries are split
    out before ``load_state_dict``).

    ``resume()`` auto-detects the checkpoint format either way: gang
    manifests in ``ckpt_dir`` are preferred when present, and monolithic
    ``ckpt.step_*`` files remain loadable (including as the fallback when
    every manifest is torn).

    With PS-backed embeddings (``RemoteHostEmbedding``) note the division
    of labor: skip-step protects the server too (anomalous grads are
    rejected before the push), but checkpoint ROLLBACK only rewinds worker
    state — pair it with the table's own ``autosave``/``restore_path`` for
    server-side state.
    """

    def __init__(self, trainer, ckpt_dir: str, *, save_every: int = 100,
                 keep: int = 3, anomaly_policy: str = "skip",
                 max_consecutive_anomalies: int = 3,
                 step_timeout: Optional[float] = None,
                 handle_signals: bool = False, gang=None, partial=None,
                 nan_provenance: bool = True):
        if anomaly_policy not in ("skip", "raise", "off"):
            raise ValueError(
                f"anomaly_policy must be 'skip', 'raise' or 'off', "
                f"got {anomaly_policy!r}")
        if anomaly_policy != "off" and getattr(trainer, "donate", False):
            raise ValueError(
                "the anomaly policy must keep the pre-step state alive "
                "across a rejected update: build the Trainer with "
                "donate=False (and no sharding strategy, which always "
                "donates)")
        self.trainer = trainer
        self.ckpt_dir = ckpt_dir
        self.save_every = int(save_every)
        self.keep = int(keep)
        self.anomaly_policy = anomaly_policy
        self.max_consecutive_anomalies = int(max_consecutive_anomalies)
        self.step_timeout = step_timeout
        self.gang = gang
        self.partial = partial
        # numerics post-mortem: on the FIRST anomaly of a streak, dump
        # the flight-recorder ring (obs.numerics.install) and interpret
        # the step's jaxpr to name the first non-finite producer.  Cold
        # path only — a healthy run never pays for it.
        self.nan_provenance = bool(nan_provenance)
        if gang is not None and (os.path.normpath(gang.gang_dir)
                                 != os.path.normpath(ckpt_dir)):
            # save() writes where the gang points but resume()/rollback
            # scan ckpt_dir — a silent mismatch would lose every
            # checkpoint on restart
            raise ValueError(
                f"gang.gang_dir {gang.gang_dir!r} must be ckpt_dir "
                f"{ckpt_dir!r}: saves would land in one directory and "
                f"resume would scan the other")
        os.makedirs(ckpt_dir, exist_ok=True)
        self._ck = AsyncCheckpointer()
        self._step = 0
        self._consec = 0
        self._saved = [p for _s, p in list_checkpoints(ckpt_dir)]
        self._preempt_signum: Optional[int] = None
        self._old_handlers: dict = {}
        # watchdog bookkeeping: each guarded step runs under an epoch; a
        # timed-out epoch is abandoned, and the guard rejects its late
        # commit so a zombie step thread can never mutate trainer state
        # (or push staged grads) behind the caller's back.  The fence lock
        # makes guard-passage and abandonment mutually exclusive: a step
        # whose guard already passed is PAST the point of no return
        # (_committing), and the timeout path then waits for its commit
        # instead of falsely reporting that nothing was committed.
        self._epoch = 0
        self._abandoned: set = set()
        self._committing: set = set()
        self._fence_lock = threading.Lock()
        self._warned_loss_only = False
        self._tls = threading.local()
        # observability for tests/operators
        self.anomalies: list = []    # [(step, loss, grad_norm)]
        self.rollbacks: list = []    # [(at_step, to_step)]
        self.resume_report: list = []
        # the guard is installed even with the anomaly policy off: it is
        # also the commit gate that fences abandoned (timed-out) steps
        trainer.grad_guard = self._guard
        if handle_signals:
            self._install_signals()

    # -- lifecycle ----------------------------------------------------------

    @property
    def step_count(self) -> int:
        """Driver step counter (1-based; checkpoint names use it)."""
        return self._step

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def close(self):
        """Wait out any in-flight save, restore signal handlers, and
        detach the commit gate so the trainer returns to plain
        semantics."""
        for sig, old in self._old_handlers.items():
            signal.signal(sig, old)
        self._old_handlers = {}
        # == not `is`: each self._guard access builds a fresh bound method
        if getattr(self.trainer, "grad_guard", None) == self._guard:
            self.trainer.grad_guard = None
        self._ck.wait()

    def _install_signals(self):
        for sig in (signal.SIGTERM, signal.SIGINT):
            self._old_handlers[sig] = signal.signal(sig, self._on_signal)

    def _on_signal(self, signum, frame):
        # only flag it: the handler may run at any bytecode boundary, and
        # saving device state mid-step would snapshot garbage.  The step
        # loop finishes the current step, saves synchronously, raises.
        self._preempt_signum = signum

    # -- resume -------------------------------------------------------------

    def _latest_gang_state(self):
        """(step, sd, extra, report) from the newest intact gang manifest
        in ``ckpt_dir`` — or (None, None, None, report).  Tried whenever a
        gang checkpointer is attached OR manifests are present (format
        auto-detection); keeps the gang generation in sync."""
        from hetu_tpu.exec import gang as _gang
        if self.gang is None and not _gang.list_manifests(self.ckpt_dir):
            return None, None, None, []
        step, generation, sd, extra, report = _gang.load_gang_checkpoint(
            self.ckpt_dir)
        if step is not None and self.gang is not None:
            # never LOWER the generation: after a rescale the newest
            # manifest usually predates the bump, and regressing would
            # void the generation fence (an evicted zombie could sign
            # manifests indistinguishable from the survivors')
            self.gang.generation = max(self.gang.generation,
                                       int(generation))
        return step, sd, extra, report

    def resume(self) -> Optional[int]:
        """Load the newest intact checkpoint (skipping corrupt/torn files
        with a diagnosis in ``resume_report``), restore trainer state and
        the RNG stream, and return the resumed step — or None for a fresh
        start.  Gang manifests (sharded + ring-replicated checkpoints)
        are auto-detected and preferred; monolithic ``ckpt.step_*`` files
        remain the fallback."""
        step, sd, extra, report = self._latest_gang_state()
        if step is not None:
            self.resume_report = report
            self._load_into_trainer(sd, consider_splits=True)
            self._step = int((extra or {}).get("step", step))
            self._consec = 0
            _obs_journal.record("resume", step=self._step, format="gang")
            return self._step
        mstep, path, state, mextra, mreport = latest_good_checkpoint(
            self.ckpt_dir)
        self.resume_report = report + mreport
        if mstep is None:
            return None
        self._load_into_trainer(state)
        self._step = int(mextra.get("step", mstep))
        self._consec = 0
        _obs_journal.record("resume", step=self._step, path=path)
        return self._step

    def _capture(self) -> dict:
        """Flat {dotted.path: array} view of the trainer state — NOT a
        pickled tree: the tree may carry unpicklable static metadata
        (e.g. RemoteHostEmbedding's ctypes PS clients), and a flat dict
        also reloads across a re-built (even re-sharded) trainer of the
        same architecture.  Staged-embedding staging buffers are dropped
        (see ``_staged_prefixes``).

        Leaves are NOT copied here: the checkpoint layer's payload
        snapshot (``_make_payload``) does the one host copy — doing it in
        both layers would double per-save copy time and peak memory."""
        sd = dict(named_parameters(self.trainer.state))
        prefixes = _staged_prefixes(self.trainer.state)
        if prefixes:
            sd = {k: v for k, v in sd.items()
                  if not any(k.startswith(p) for p in prefixes)}
        if self.partial is not None:
            # pending correction terms are training state: losing them on
            # a kill would silently forget late gradients the replay then
            # cannot reproduce
            sd.update(self.partial.state_entries())
        return sd

    def _load_into_trainer(self, sd: dict,
                           consider_splits: bool = False) -> None:
        sd, corr = _split_partial(sd)
        if self.partial is not None:
            self.partial.load_state_entries(corr)
        self.trainer.state = _to_device(load_state_dict(
            self.trainer.state, sd, consider_splits=consider_splits))

    # -- checkpointing ------------------------------------------------------

    def save(self, sync: bool = False) -> str:
        """Checkpoint the current state (async by default) and prune the
        rolling retention window.  With a gang checkpointer attached the
        save is this worker's shard + ring replica (+ manifest on the
        writer rank) and is synchronous: the manifest must not sign a
        shard that is still in flight."""
        if self.gang is not None:
            self._ck.wait()  # order after any in-flight monolithic save
            return self.gang.save(self._step, self._capture(),
                                  extra={"step": self._step})
        path = checkpoint_path(self.ckpt_dir, self._step)
        self._ck.save(path, self._capture(), extra={"step": self._step})
        if sync:
            self._ck.wait()
        if path not in self._saved:
            self._saved.append(path)
        while self.keep > 0 and len(self._saved) > self.keep:
            old = self._saved.pop(0)
            try:
                os.remove(old)
            except OSError:
                pass  # already gone (or never landed) — retention is
                #       best-effort, never fatal
        return path

    def _rollback(self) -> int:
        # the in-flight async save (if any) holds a pre-anomaly snapshot;
        # make it durable before scanning so we roll back as little as
        # possible
        t0 = time.perf_counter()
        self._ck.wait()
        gstep, gsd, gextra, greport = self._latest_gang_state()
        if gstep is not None:
            step, state, extra, report = gstep, gsd, gextra or {}, greport
        else:
            step, _path, state, extra, report = latest_good_checkpoint(
                self.ckpt_dir)
        if step is None:
            raise TrainingDiverged(
                f"{self._consec} consecutive anomalous steps and no intact "
                f"checkpoint to roll back to in {self.ckpt_dir!r} "
                f"(scanned: {[(s, d) for s, _p, d in greport + report]})")
        self._load_into_trainer(state, consider_splits=gstep is not None)
        self.rollbacks.append((self._step, int(extra.get("step", step))))
        if _obs.enabled():
            _res_m()["rollbacks"].inc()
            _obs_journal.record("rollback", at_step=self._step,
                                to_step=int(extra.get("step", step)))
        # the flight recorder's ring holds the steps that led here — dump
        # it before the restore makes them unreconstructable (no-op with
        # no recorder installed)
        _obs_numerics.dump("rollback", step=self._step)
        # the restore itself is lost time: bill it to the goodput
        # "rollback" bucket (the rejected steps were billed there by the
        # Trainer.step seam as they happened)
        _obs_goodput.record_event("rollback", time.perf_counter() - t0)
        self._step = int(extra.get("step", step))
        return self._step

    # -- the guarded step ---------------------------------------------------

    def _guard(self, metrics: dict) -> bool:
        """``Trainer.grad_guard`` hook: accept the update only when loss
        and grad-norm are finite AND the step was not abandoned by the
        watchdog.  Runs before the state commit and before staged/PS
        gradient pushes."""
        # a zombie thread whose step already blew the deadline must not
        # commit: the driver has moved on (resume/rollback) and a late
        # commit — worse, a late PS push — would corrupt the lineage.
        # Under the fence lock so the decision is atomic against the
        # timeout path: either this step is already abandoned (reject), or
        # it is marked committing and the timeout path waits for it.
        epoch = getattr(self._tls, "epoch", None)
        if epoch is not None:
            with self._fence_lock:
                if epoch in self._abandoned:
                    self._abandoned.discard(epoch)
                    return False
                self._committing.add(epoch)
        if self.anomaly_policy == "off":
            return True
        if "grad_norm" not in metrics and not self._warned_loss_only:
            # the Trainer was jitted before the guard attached, so the
            # cached program carries no grad_norm — detection degrades to
            # loss-only.  Say so once instead of silently weakening.
            self._warned_loss_only = True
            import warnings
            warnings.warn(
                "ResilientTrainer anomaly detection is LOSS-ONLY for this "
                "trainer: it ran a step before ResilientTrainer wrapped "
                "it, so the jitted program has no grad_norm metric.  Wrap "
                "the Trainer before its first step for full NaN/Inf "
                "gradient detection.", RuntimeWarning, stacklevel=2)
        loss = float(metrics.get("loss", 0.0))
        gnorm = float(metrics.get("grad_norm", 0.0))
        finite = bool(np.isfinite(loss) and np.isfinite(gnorm))
        # streak accounting from values already fetched to host — the
        # hetu_numerics_nonfinite_streak gauge costs no extra sync (and
        # is one global load + branch with no recorder installed)
        _obs_numerics.note_outcome(finite, step=self._step)
        if finite:
            return True
        if self.anomaly_policy == "raise":
            raise TrainingDiverged(
                f"non-finite training signal at step {self._step}: "
                f"loss={loss}, grad_norm={gnorm}")
        self.anomalies.append((self._step, loss, gnorm))
        if _obs.enabled():
            _res_m()["anomalies"].inc()
            _obs_journal.record("nan_skip", step=self._step, loss=loss,
                                grad_norm=gnorm)
        return False

    def _run_step(self, batch, key):
        def body():
            _faults.fire("step_begin")  # deterministic hang injection
            return self.trainer.step(batch, key)

        if self.step_timeout is None:
            return body()
        box: dict = {}
        self._epoch += 1
        epoch = self._epoch

        def target():
            self._tls.epoch = epoch  # read back by _guard for fencing
            try:
                box["out"] = body()
            except BaseException as e:  # surfaced on the caller thread
                box["err"] = e

        th = threading.Thread(target=target, daemon=True,
                              name=f"resilient-step-{self._step}")
        th.start()
        th.join(self.step_timeout)
        if th.is_alive():
            # abandon-or-wait, atomic against the guard: if the guard
            # already passed (epoch in _committing) the step is mid-commit
            # — wait it out rather than falsely report nothing committed;
            # otherwise abandon it so the eventual guard call rejects.
            with self._fence_lock:
                committing = epoch in self._committing
                if not committing:
                    self._abandoned.add(epoch)
            if _obs.enabled():
                _res_m()["watchdog"].inc()
                _obs_journal.record("watchdog_fired", step=self._step,
                                    timeout_s=self.step_timeout,
                                    committing=committing)
            if not committing:
                last = self._saved[-1] if self._saved else None
                raise BackendUnresponsive(
                    f"train step {self._step} did not complete within "
                    f"{self.step_timeout}s — hung device program or dead "
                    f"backend (the BENCH_r05 'backend_unreachable' "
                    f"shape); if this was the first step, jit compilation "
                    f"may have blown the deadline — warm the trainer up "
                    f"or raise step_timeout; last checkpoint: "
                    f"{last or 'none'}; nothing was committed")
            th.join(self.step_timeout)
            if th.is_alive():
                # past the commit gate, so the state swap / staged PS push
                # is merely BLOCKED, not fenced — it may still land when
                # the link unblocks.  Be explicit: this process must be
                # restarted, not resumed in place.
                raise BackendUnresponsive(
                    f"train step {self._step} passed its commit gate but "
                    f"the commit (state swap / staged PS push) is still "
                    f"blocked after another {self.step_timeout}s — "
                    f"stalled PS/host link; the commit MAY still land "
                    f"when it unblocks, so restart the process instead "
                    f"of resuming in-place")
        with self._fence_lock:
            self._committing.discard(epoch)
        if "err" in box:
            raise box["err"]
        return box["out"]

    def step(self, batch, key=None) -> dict:
        """One guarded training step.

        Returns the metrics dict; a rejected (anomalous) step returns with
        ``skipped=True`` and leaves trainer state AND the global RNG stream
        exactly as before the call, so the surviving lineage is bitwise
        identical to an uninjected run.  After
        ``max_consecutive_anomalies`` rejections in a row the state is
        rolled back to the newest intact checkpoint (``rolled_back_to`` in
        the metrics).  Raises :class:`Preempted` after the final save when
        a SIGTERM/SIGINT arrived, and :class:`BackendUnresponsive` when the
        step blows the watchdog deadline."""
        self._maybe_preempt()
        self._step += 1
        plan = _faults.active_plan()
        if plan is not None:
            plan.advance(self._step)
        rng0 = get_seed_status()
        if key is None:
            # draw on the driver thread: a watchdog-abandoned step thread
            # must never touch the global RNG stream after the driver has
            # resumed/rolled back (it would shift every later key)
            key = next_key()
        metrics = self._run_step(batch, key)
        if metrics.get("skipped"):
            # un-consume the step: RNG seqnum back, driver step back (the
            # skipped number is reused), anomaly accounting forward
            reset_seed_seqnum(*rng0)
            self._step -= 1
            self._consec += 1
            if self._consec == 1:
                # first anomaly of a streak: numerics post-mortem (flight
                # dump + jaxpr provenance) before any rollback mutates
                # the state the NaN was born under
                self._numerics_postmortem(self._step + 1, batch, key)
            if self._consec >= self.max_consecutive_anomalies:
                metrics["rolled_back_to"] = self._rollback()
                self._consec = 0
        else:
            self._consec = 0
            if self.save_every > 0 and self._step % self.save_every == 0:
                self.save()
        # closed-loop remediation (exec.controller): an installed
        # controller re-evaluates the partial-reduce deadline from this
        # trainer's reducer lag EWMAs — one global load + branch when
        # none is installed (the obs seam contract)
        _controller.maybe_after_train_step(self, self._step, metrics)
        self._maybe_preempt()
        return metrics

    def _numerics_postmortem(self, step: int, batch, key) -> None:
        """First-anomaly-of-a-streak forensics: dump the flight-recorder
        ring (``flight_dump``, no-op without an installed recorder) and
        interpret the step's ``value_and_grad`` jaxpr to journal
        ``nan_provenance`` naming the first non-finite producer.  The
        trainer's stashed post-fault-hook inputs are preferred so an
        injected poison is replayed exactly."""
        _obs_numerics.dump("nan_skip", step=step)
        if not (self.nan_provenance and _obs.enabled()):
            return
        stashed = getattr(self.trainer, "_last_step_inputs", None)
        if stashed is not None:
            batch, key = stashed
        try:
            rep = _obs_numerics.loss_provenance(
                self.trainer.loss_fn, self.trainer.state.model, batch,
                key)
        except Exception as e:
            _obs_journal.record("nan_provenance", step=step,
                                op="provenance_error", origin="error",
                                error=str(e))
            return
        if rep is not None:
            _obs_journal.record(
                "nan_provenance", step=step, op=rep["op"],
                origin=rep["origin"], site=rep.get("site"),
                **({"leaf": rep["leaf"]} if "leaf" in rep else {}))

    def _maybe_preempt(self):
        if self._preempt_signum is None:
            return
        signum, self._preempt_signum = self._preempt_signum, None
        self._ck.wait()  # order after any in-flight periodic save
        if self.gang is not None:
            self.gang.save(self._step, self._capture(),
                           extra={"step": self._step})
        else:
            save_checkpoint(checkpoint_path(self.ckpt_dir, self._step),
                            self._capture(), extra={"step": self._step})
        if _obs.enabled():
            _res_m()["preemptions"].inc()
            _obs_journal.record("preemption", step=self._step,
                                signum=signum)
        raise Preempted(self._step, signum)
