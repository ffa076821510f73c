"""Execution-layer profiling: per-section timers, per-primitive graph
profiles, compiled-cost analysis, and trace capture.

Reference surfaces being covered (SURVEY §5.1):
- ``HetuTimer`` — the timer subexecutor's per-node/per-type accumulation
  (timer_subexecutor.py:21, ``logOut`` with node/type granularity);
- ``HetuProfiler`` — per-op re-execution profiling behind
  ``executor.profile(...)`` (profiler.py:55, executor.py:501);
- XLA-native extras the reference lacks: ``compiled_cost`` reads the
  compiler's own flop/byte analysis, ``trace`` captures a profile for
  TensorBoard/XProf (jax.profiler), which replaces CUDA-event timing.
"""

from __future__ import annotations

import contextlib
import os
import time
from collections import defaultdict
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["HetuTimer", "audit_donation", "audit_serving_donation",
           "device_op_breakdown", "timed_scan_diff",
           "profile_fn", "compiled_cost", "primitive_counts", "trace"]


class HetuTimer:
    """Named-section wall timer with accumulation.

    >>> timer = HetuTimer()
    >>> with timer("forward"):
    ...     out = model(x)
    >>> timer.log_out()
    """

    def __init__(self, sync: bool = True):
        self.totals: dict = defaultdict(float)
        self.counts: dict = defaultdict(int)
        self.sync = sync
        self._last_result: Any = None

    @contextlib.contextmanager
    def __call__(self, name: str, result: Any = None):
        t0 = time.perf_counter()
        try:
            yield self
        finally:
            if self.sync and self._last_result is not None:
                jax.block_until_ready(self._last_result)
                self._last_result = None
            self.totals[name] += time.perf_counter() - t0
            self.counts[name] += 1

    def observe(self, result: Any) -> Any:
        """Register a jax value to block on at section exit (async dispatch
        means exit-time sync is needed for honest timings)."""
        self._last_result = result
        return result

    def mean(self, name: str) -> float:
        return self.totals[name] / max(self.counts[name], 1)

    def log_out(self, printer: Callable = print) -> dict:
        """Per-section totals/means (timer_subexecutor logOut)."""
        stats = {name: {"total_s": self.totals[name],
                        "count": self.counts[name],
                        "mean_s": self.mean(name)}
                 for name in sorted(self.totals)}
        for name, s in stats.items():
            printer(f"[hetu-timer] {name}: total {s['total_s']*1e3:.2f}ms "
                    f"count {s['count']} mean {s['mean_s']*1e3:.3f}ms")
        return stats

    def reset(self):
        self.totals.clear()
        self.counts.clear()


def primitive_counts(fn: Callable, *example_args) -> dict:
    """Per-primitive equation counts + analytic flops where known — the
    node/type granularity of the reference's timer subexecutor, read off
    the jaxpr instead of timed per-op replays."""
    closed = jax.make_jaxpr(fn)(*example_args)
    counts: dict = defaultdict(int)
    flops: dict = defaultdict(float)

    def visit(jaxpr):
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            inner = (eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr"))
            if inner is not None and prim in (
                    "pjit", "jit", "closed_call", "core_call",
                    "custom_jvp_call", "custom_vjp_call", "remat",
                    "remat2", "checkpoint"):
                visit(inner.jaxpr if hasattr(inner, "jaxpr") else inner)
                continue
            counts[prim] += 1
            if prim == "dot_general":
                ((lc, _rc), (lb, _rb)) = eqn.params["dimension_numbers"]
                lhs = eqn.invars[0].aval
                out = eqn.outvars[0].aval
                k = np.prod([lhs.shape[d] for d in lc], initial=1.0)
                flops[prim] += 2.0 * k * np.prod(out.shape, initial=1.0)
            elif prim == "conv_general_dilated":
                rhs = eqn.invars[1].aval
                out = eqn.outvars[0].aval
                # 2 * out_elems * (kernel spatial * in_channels)
                per_out = 2.0 * np.prod(rhs.shape, initial=1.0) / rhs.shape[
                    eqn.params["dimension_numbers"][1][0]]
                flops[prim] += per_out * np.prod(out.shape, initial=1.0)

    visit(closed.jaxpr)
    return {"counts": dict(counts), "flops": dict(flops),
            "total_flops": float(sum(flops.values()))}


def compiled_cost(fn: Callable, *example_args, static_argnums=()) -> dict:
    """XLA's own cost analysis of the compiled executable (flops, bytes
    accessed, peak memory when the backend reports it)."""
    lowered = jax.jit(fn, static_argnums=static_argnums).lower(*example_args)
    compiled = lowered.compile()
    out: dict = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        out["flops"] = float(ca.get("flops", 0.0))
        out["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
    except Exception:  # backend without cost analysis
        pass
    out.update(_memory_stats(compiled))
    return out


def _memory_stats(compiled) -> dict:
    """argument/output/alias/temp byte sizes of a compiled executable
    (empty dict on backends without memory analysis)."""
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return {}
    if mem is None:
        return {}
    return {
        "argument_bytes": float(getattr(mem, "argument_size_in_bytes", 0)),
        "output_bytes": float(getattr(mem, "output_size_in_bytes", 0)),
        "aliased_bytes": float(getattr(mem, "alias_size_in_bytes", 0)),
        "temp_bytes": float(getattr(mem, "temp_size_in_bytes", 0)),
    }


def audit_donation(trainer, batch, key=None) -> dict:
    """Donation/aliasing audit of the trainer's compiled train step — the
    TPU-rebuild replacement SURVEY §5.2 prescribes for the reference's
    manual CUDA stream/event race discipline (executor.py:1227-1246):
    XLA's dataflow semantics remove stream races, and what remains worth
    auditing is whether the train state's buffers are actually DONATED
    (aliased input→output) or silently copied.  A sharding change, dtype
    drift between ``opt.init`` and ``opt.update``, or a state leaf that
    stops being returned all break donation quietly — at BERT-large that
    is gigabytes of extra peak HBM.

    Returns {"argument_bytes", "output_bytes", "aliased_bytes",
    "temp_bytes", "donated_fraction", "unusable": [messages]} where
    ``unusable`` captures XLA's "donated buffers were not usable"
    warnings.  Numeric keys are 0.0 when the step cannot be lowered or
    compiled (the failure is recorded under "error") or the backend
    reports no memory analysis — the report degrades, it never raises.
    """
    key = jax.random.key(0) if key is None else key
    out: dict = {"argument_bytes": 0.0, "output_bytes": 0.0,
                 "aliased_bytes": 0.0, "temp_bytes": 0.0,
                 "donated_fraction": 0.0, "unusable": []}
    lower = getattr(trainer._train_step, "lower", None)
    if lower is None:
        return out
    try:
        compiled, out["unusable"] = _compile_fresh(
            lambda: lower(trainer.state, batch, key))
    except Exception as e:  # honor the degrade-don't-raise contract
        out["error"] = f"{type(e).__name__}: {e}"
        return out
    out.update(_memory_stats(compiled))
    if out["argument_bytes"]:
        out["donated_fraction"] = out["aliased_bytes"] / out["argument_bytes"]
    return out


def _compile_fresh(lower) -> tuple:
    """Compile what ``lower()`` returns with the persistent compilation
    cache out of the way; returns ``(compiled, unusable)`` where
    ``unusable`` holds XLA's "donated buffers were not usable" warnings.

    A warm persistent cache serves a deserialized executable whose
    memory_analysis reports zero aliased bytes, and those warnings only
    fire on a real compile — a donation audit must observe one.
    Unsetting the dir alone is not enough: the cache instance is created
    once at first use and later config changes are ignored, so reset it
    (it lazily re-initializes from the restored config on the next cached
    compile)."""
    import warnings

    # private, and the only way to make a cache-directory change take
    # effect: if it moves, this import fails loudly instead of the audit
    # silently reading a deserialized executable
    from jax._src.compilation_cache import reset_cache as _reset_cache

    cache_dir_was = jax.config.jax_compilation_cache_dir
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        try:
            jax.config.update("jax_compilation_cache_dir", None)
            _reset_cache()
            compiled = lower().compile()
        finally:
            jax.config.update("jax_compilation_cache_dir", cache_dir_was)
            _reset_cache()
    return compiled, [str(w.message) for w in caught
                      if "donated" in str(w.message).lower()]


def audit_serving_donation(engine, *,
                           spec_k: Optional[int] = None) -> dict:
    """Donation audit of a :class:`~hetu_tpu.serve.ServingEngine`'s step
    programs, in the manner of :func:`audit_donation`: each program is
    lowered on shapes (nothing runs, no array is consumed) and compiled
    fresh.  Every serving program takes the K/V pool donated, so each
    must alias at least the pool's bytes; one that does not copies the
    whole pool on every call.

    Programs: ``prefill`` at the smallest bucket, ``decode``
    (the engine's own: paged or gather), and ``verify``, the paged
    program at the speculative chain's ``slots x (spec_k + 1)`` rows,
    when the engine speculates or ``spec_k`` is given.  Returns
    ``{"pool_bytes", "programs": {name: {"argument_bytes",
    "output_bytes", "aliased_bytes", "temp_bytes", "unusable"}}}`` and
    raises what the compiler raises."""
    pool = engine.pool
    slots = engine.batcher.num_slots
    bucket = engine.batcher.prompt_buckets[0]
    if spec_k is None and engine.spec is not None:
        spec_k = engine.spec.k

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    cache = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in pool.arrays]

    def step(rows, width):
        return lambda: engine._step_fn.lower(
            engine._model_leaves, *cache, pool.table_shapes(rows), i32(rows),
            i32(rows, width), None if width == 1 else i32(rows))

    def paged(rows, *prev):
        return lambda: engine._paged_step_fn.lower(
            engine._model_leaves, *cache, pool.table_shapes(rows), i32(rows),
            i32(rows, 1), i32(rows), i32(rows), *prev)

    # the decode tick also takes the last step's tokens; the verify does not
    lowers = {"prefill": step(1, bucket),
              "decode": paged(slots, i32(slots)) if engine.paged_decode
              else step(slots, 1)}
    if spec_k is not None:
        lowers["verify"] = paged(slots * (spec_k + 1))
    programs = {}
    for name, lower in lowers.items():
        compiled, unusable = _compile_fresh(lower)
        programs[name] = {**_memory_stats(compiled), "unusable": unusable}
    return {"pool_bytes": pool.nbytes, "programs": programs}


def profile_fn(fn: Callable, *example_args, iters: int = 10,
               warmup: int = 2) -> dict:
    """Wall-time + cost profile of a jitted function — the
    ``executor.profile(feed_shapes, ...)`` capability (executor.py:501).

    Returns {mean_s, p50_s, min_s, flops, achieved_flops, counts...}.
    """
    jitted = jax.jit(fn)
    for _ in range(max(warmup, 1)):
        out = jitted(*example_args)
    jax.block_until_ready(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = jitted(*example_args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    prof = {"mean_s": float(np.mean(times)),
            "p50_s": float(np.median(times)),
            "min_s": float(np.min(times)),
            "iters": iters}
    prof.update(compiled_cost(fn, *example_args))
    prims = primitive_counts(fn, *example_args)
    prof["primitive_counts"] = prims["counts"]
    if "flops" not in prof or not prof["flops"]:
        prof["flops"] = prims["total_flops"]
    if prof.get("flops"):
        prof["achieved_flops"] = prof["flops"] / prof["p50_s"]
    return prof


@contextlib.contextmanager
def trace(logdir: str):
    """Capture an XProf/TensorBoard trace of the enclosed block
    (replaces the reference's CUDA-event timing paths on TPU)."""
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def timed_scan_diff(trainer, batch, *, k: int, reps: int = 4,
                    key=None) -> dict:
    """Device seconds per train step, measured as a differenced compiled
    scan: run(k steps) and run(2k steps) are each ONE dispatch, so
    (t_2k - t_k)/k cancels the fixed dispatch cost (same number of host
    round trips on both sides of the difference).  Sync is float(loss): the
    fetch waits for the device exactly as block_until_ready does.  The
    trainer's state advances (3*k*(reps+1) real steps) and is handed
    back, so subsequent use sees the trained state."""
    run_k = trainer.scan_steps(k)
    run_2k = trainer.scan_steps(2 * k)
    key = jax.random.key(1) if key is None else key
    state = trainer.state
    last = {}

    def call(run):
        nonlocal state, last
        t0 = time.perf_counter()
        state, last = run(state, batch, key)
        float(last["loss"])
        return time.perf_counter() - t0

    call(run_k)
    call(run_2k)  # compile + warm both programs
    call(run_k)
    call(run_2k)  # one throwaway pair: the first post-compile execution
    # of a program can run ~30% slow (autotune/cache residue) and a
    # polluted t_k skews the whole differenced pair (seen on the
    # autoparallel config: rep-0 diff 64 ms vs steady 108 ms)
    diffs, fixed = [], []
    for _ in range(reps):
        t1 = call(run_k)
        t2 = call(run_2k)
        diffs.append((t2 - t1) / k)
        fixed.append(2 * t1 - t2)  # per-dispatch overhead estimate
    trainer.state = state
    med, mn = float(np.median(diffs)), float(min(diffs))
    return {"median_s": med, "min_s": mn,
            "spread": round(med / mn, 4) if mn > 0 else None,
            "dispatch_ms": round(float(np.median(fixed)) * 1e3, 1),
            "last_metrics": last,  # final step's full metrics, no extra
            # dispatch or compile (scan_steps returns them)
            "timing": "scan-diff-device"}


def device_op_breakdown(logdir: str, *, steps: int = 1, top: int = 0):
    """Parse the newest ``*.trace.json.gz`` under ``logdir`` (written by
    ``trace()``) into per-op device time — the analysis loop behind the
    round-4 attention-layout and non-MXU-residue findings (ROADMAP 4b/4c),
    promoted from a script to API.

    Groups device-timeline events by XLA's ``deduplicated_name`` (repeats
    of the same fusion across layers aggregate), filters host frames and
    program envelopes, and divides by ``steps`` (trace ``steps``
    iterations for stable numbers).  Returns ``(per_op, totals)``:
    ``per_op`` maps op name -> seconds/step (all ops, or the ``top``
    largest), ``totals`` has ``device_s`` and ``copy_s`` (relayout
    ``copy.*``/``copy_fusion*`` ops — the layout-health number;
    ``transpose_jvp*``-style SCOPE names are not data transposes and are
    not counted).
    """
    import glob
    import gzip
    import json

    paths = glob.glob(os.path.join(logdir, "**", "*.trace.json.gz"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no trace under {logdir}")
    with gzip.open(sorted(paths)[-1], "rt") as f:
        events = json.load(f).get("traceEvents", [])
    dev_pids = {ev.get("pid") for ev in events
                if ev.get("ph") == "M" and ev.get("name") == "process_name"
                and any(s in ev.get("args", {}).get("name", "")
                        for s in ("TPU", "Tensor", "Device", "/device"))}
    per = defaultdict(float)
    for ev in events:
        if ev.get("ph") != "X" or "dur" not in ev:
            continue
        if dev_pids and ev.get("pid") not in dev_pids:
            continue
        name = (ev.get("args", {}).get("deduplicated_name")
                or ev.get("name", ""))
        if (not name or name.isdigit() or name.startswith(("$", "jit_"))
                or "(" in name):
            continue  # host python frames / program envelopes
        per[name] += ev["dur"] / 1e6 / steps
    totals = {
        "device_s": sum(per.values()),
        "copy_s": sum(v for k, v in per.items()
                      if k.startswith(("copy.", "copy_fusion"))),
    }
    ranked = dict(sorted(per.items(), key=lambda kv: -kv[1]))
    if top:
        ranked = dict(list(ranked.items())[:top])
    # calibration seam: the parsed per-op device table is a measured
    # signal — fold it into the installed profile store (one global
    # load + branch when none is installed)
    from hetu_tpu.obs.calibration import note_op_breakdown
    note_op_breakdown(per, totals)
    return ranked, totals
