"""Jaxpr live-range estimator: predict a program's peak device bytes
without compiling it.

The reference's memory planner walks its op DAG and assigns BFC-allocator
blocks ahead of execution (src/memory_pool/); XLA does that job here, so
the *planning* problem becomes prediction: given a step function, how many
temp bytes will XLA's buffer assignment peak at?  This module answers by
simulating buffer live ranges over the traced jaxpr:

- every equation output allocates its aval's bytes at the equation and
  frees after its last use (ideal liveness — XLA's buffer assignment
  reuses dead buffers the same way);
- XLA's fusion makes most *cheap elementwise* values never materialize:
  an output of a fusible elementwise primitive with a single consumer is
  fused into that consumer and costs nothing; view-like primitives
  (reshape/convert/broadcast-of-scalar) alias and always cost nothing;
- nested jaxprs (pjit, checkpoint/remat, scan, cond) are *scoped*: their
  internal peak is charged while the equation runs, and only their
  declared outputs (e.g. a remat region's policy-saved residuals) stay
  live after — which is exactly how ``jax.checkpoint`` policies reduce
  peak memory.

Cross-checked against ``compiled.memory_analysis()`` (tests assert the
prediction lands within 25% of XLA's own number on GPT and BERT training
steps).  Rematerialized programs are *relatively* ordered correctly but
systematically flattered: XLA schedules remat regions less tightly than
ideal liveness assumes, so treat remat predictions as lower bounds (the
planner's budget is the guard rail).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import numpy as np

__all__ = ["MemoryEstimate", "estimate_peak_bytes", "estimate_train_peak",
           "cross_check", "record_memory_gauges", "reconcile",
           "ERROR_BAND"]

#: The estimator's documented accuracy band vs ``memory_analysis()``:
#: the tests assert predictions land within 25% of XLA's number, and
#: :func:`reconcile` journals ``mem_estimate_drift`` when a production
#: cross-check leaves it — the band is a runtime contract now, not just
#: a test constant.
ERROR_BAND = 0.25


# Elementwise primitives XLA freely duplicates into consumers: with one
# consumer the value fuses away and never materializes.
_CHEAP_ELEMENTWISE = frozenset({
    "add", "sub", "mul", "neg", "max", "min", "select_n", "and", "or",
    "not", "xor", "eq", "ne", "lt", "le", "gt", "ge", "sign",
    "broadcast_in_dim", "integer_pow", "iota", "abs", "floor", "ceil",
    "round", "is_finite", "pow", "square", "clamp",
})

# View-like / freely elided primitives: never materialize a new buffer.
_ALIASING = frozenset({
    "reshape", "squeeze", "expand_dims", "stop_gradient", "copy",
    "convert_element_type",
})


def _aval_bytes(aval) -> int:
    try:
        return int(np.prod(aval.shape, initial=1)) * aval.dtype.itemsize
    except Exception:  # abstract tokens, effects
        return 0


def _sub_jaxprs(eqn):
    """Inner jaxprs of a higher-order equation ([] for first-order)."""
    p = eqn.params
    out = []
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr", "cond_jaxpr",
                "body_jaxpr"):
        j = p.get(key)
        if j is not None:
            out.append(j.jaxpr if hasattr(j, "jaxpr") else j)
    for b in p.get("branches", ()) or ():
        out.append(b.jaxpr if hasattr(b, "jaxpr") else b)
    return out


def _simulate(jaxpr) -> int:
    """Peak temp bytes of one jaxpr body (invars live externally)."""
    from jax.extend.core import Var

    last_use: dict = {}
    fanout: dict = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if isinstance(v, Var):
                last_use[v] = i
                fanout[v] = fanout.get(v, 0) + 1
    for v in jaxpr.outvars:
        if isinstance(v, Var):
            last_use[v] = len(jaxpr.eqns)
            fanout[v] = fanout.get(v, 0) + 1

    # free-list index: eqn i -> vars whose last use is i (O(eqns + vars),
    # not a full last_use rescan per equation)
    frees: dict = {}
    for v, li in last_use.items():
        frees.setdefault(li, []).append(v)

    live = 0
    peak = 0
    alive: dict = {}
    for i, eqn in enumerate(jaxpr.eqns):
        prim = eqn.primitive.name
        inner_peak = 0
        for sub in _sub_jaxprs(eqn):
            inner_peak = max(inner_peak, _simulate(sub))
        for v in eqn.outvars:
            if isinstance(v, Var) and v in last_use:
                b = _aval_bytes(v.aval)
                if prim in _ALIASING or (prim in _CHEAP_ELEMENTWISE
                                         and fanout.get(v, 0) <= 1):
                    b = 0
                alive[v] = b
                live += b
        if live + inner_peak > peak:
            peak = live + inner_peak
        for v in frees.get(i, ()):
            if v in alive:
                live -= alive.pop(v)
    return peak


@dataclasses.dataclass(frozen=True)
class MemoryEstimate:
    """Predicted per-device memory of one traced program."""

    argument_bytes: int      # inputs resident for the whole program
    output_bytes: int        # outputs (alias arguments under donation)
    temp_peak_bytes: int     # predicted peak of XLA temp allocations
    n_eqns: int

    @property
    def device_peak_bytes(self) -> int:
        """Conservative resident peak: arguments + temps (outputs alias
        donated arguments in a well-formed train step)."""
        return self.argument_bytes + self.temp_peak_bytes

    def describe(self) -> str:
        return (f"args={self.argument_bytes / 1e6:.1f}MB "
                f"out={self.output_bytes / 1e6:.1f}MB "
                f"temp_peak={self.temp_peak_bytes / 1e6:.1f}MB "
                f"device_peak={self.device_peak_bytes / 1e6:.1f}MB")


def estimate_peak_bytes(fn: Callable, *example_args, **example_kwargs
                        ) -> MemoryEstimate:
    """Trace ``fn`` to a jaxpr and simulate buffer live ranges.

    Deterministic: same function and example avals -> same numbers (pure
    jaxpr walk, no compilation, no clock).
    """
    import jax

    closed = jax.make_jaxpr(fn)(*example_args, **example_kwargs)
    jaxpr = closed.jaxpr
    args = sum(_aval_bytes(v.aval) for v in jaxpr.invars)
    args += sum(_aval_bytes(getattr(c, "aval", None) or _FakeAval(c))
                for c in closed.consts)
    outs = sum(_aval_bytes(v.aval) for v in jaxpr.outvars
               if hasattr(v, "aval"))
    return MemoryEstimate(int(args), int(outs), int(_simulate(jaxpr)),
                          len(jaxpr.eqns))


class _FakeAval:
    """Shape/dtype view over a raw constant (closed-jaxpr consts are
    concrete arrays, not avals)."""

    def __init__(self, c):
        self.shape = getattr(c, "shape", ())
        self.dtype = getattr(c, "dtype", np.dtype(np.float32))


def estimate_train_peak(loss_fn: Callable, *example_args) -> MemoryEstimate:
    """Estimate for the full training step ``value_and_grad(loss_fn)`` —
    the number the planner budgets against (params + grads + activation
    residuals + transients)."""
    import jax

    return estimate_peak_bytes(jax.value_and_grad(loss_fn), *example_args)


def cross_check(fn: Callable, *example_args) -> dict:
    """Predicted vs XLA-reported memory for ``fn`` — compiles once and
    reads ``compiled.memory_analysis()``.  Publishes both sides as obs
    gauges (``hetu_mem_predicted_peak_bytes`` / ``hetu_mem_xla_*``) so
    /metrics shows prediction drift in production.

    Returns {predicted_temp_bytes, xla_temp_bytes, xla_argument_bytes,
    xla_output_bytes, ratio}; XLA keys are 0.0 on backends without
    memory analysis (the ratio is then 0.0 too — absent, not infinite).
    """
    import jax

    from hetu_tpu.exec.profiler import _memory_stats

    est = estimate_peak_bytes(fn, *example_args)
    out = {"predicted_temp_bytes": float(est.temp_peak_bytes),
           "predicted_device_peak_bytes": float(est.device_peak_bytes),
           "xla_temp_bytes": 0.0, "xla_argument_bytes": 0.0,
           "xla_output_bytes": 0.0, "ratio": 0.0}
    try:
        compiled = jax.jit(fn).lower(*example_args).compile()
        stats = _memory_stats(compiled)  # the one XLA memory-stats reader
    except Exception:
        stats = {}
    if stats:
        out["xla_temp_bytes"] = stats.get("temp_bytes", 0.0)
        out["xla_argument_bytes"] = stats.get("argument_bytes", 0.0)
        out["xla_output_bytes"] = stats.get("output_bytes", 0.0)
        if out["xla_temp_bytes"]:
            out["ratio"] = reconcile(out["predicted_temp_bytes"],
                                     out["xla_temp_bytes"])["ratio"]
    record_memory_gauges(predicted=est.temp_peak_bytes, xla=out)
    return out


def reconcile(predicted_bytes: float, xla_bytes: float, *,
              band: float = ERROR_BAND, model_sig: str = "") -> dict:
    """Reconcile an estimator prediction against XLA's own
    ``memory_analysis`` bytes — the measure→calibrate closing move for
    the memory model:

    - publishes the ``hetu_mem_estimator_error_ratio`` gauge
      (predicted / XLA-reported; 1.0 = perfect);
    - journals ``mem_estimate_drift`` when the ratio leaves the
      ``band`` (default the tests' 25% cross-check band — until now
      that band only existed inside tests);
    - feeds the installed calibration
      :class:`~hetu_tpu.obs.calibration.ProfileStore` a ``mem`` record,
      which ``fit_calibration`` turns into the ``mem_error_ratio``
      constant ``plan_memory(calibration=...)`` corrects by.

    Returns ``{"ratio", "within_band"}``; a non-positive ``xla_bytes``
    yields ratio 0.0 (absent, not infinite) and no drift event."""
    predicted_bytes = float(predicted_bytes)
    xla_bytes = float(xla_bytes)
    if xla_bytes <= 0.0:
        return {"ratio": 0.0, "within_band": True}
    ratio = predicted_bytes / xla_bytes
    within = abs(ratio - 1.0) <= float(band)
    from hetu_tpu.obs import registry as _obs
    if _obs.enabled():
        _mem_gauges()["error_ratio"].set(ratio)
    if not within:
        from hetu_tpu.obs import journal as _obs_journal
        _obs_journal.record(
            "mem_estimate_drift", predicted_bytes=predicted_bytes,
            xla_bytes=xla_bytes, ratio=round(ratio, 6),
            band=float(band))
    from hetu_tpu.obs.calibration import note_mem
    note_mem(predicted_bytes, xla_bytes, ratio, model_sig=model_sig)
    return {"ratio": ratio, "within_band": within}


# ---------------------------------------------------------------------------
# Observability
# ---------------------------------------------------------------------------

_gauges = None


def _mem_gauges():
    global _gauges
    if _gauges is None:
        from hetu_tpu.obs import registry as _obs
        reg = _obs.get_registry()
        _gauges = {
            "predicted": reg.gauge(
                "hetu_mem_predicted_peak_bytes",
                "estimator-predicted peak temp bytes of the last "
                "estimated program (mem.estimator)"),
            "xla_temp": reg.gauge(
                "hetu_mem_xla_temp_bytes",
                "XLA-reported temp bytes of the last profiled/cross-"
                "checked executable (compiled.memory_analysis)"),
            "xla_args": reg.gauge(
                "hetu_mem_xla_argument_bytes",
                "XLA-reported argument bytes of the last profiled "
                "executable"),
            "xla_out": reg.gauge(
                "hetu_mem_xla_output_bytes",
                "XLA-reported output bytes of the last profiled "
                "executable"),
            "error_ratio": reg.gauge(
                "hetu_mem_estimator_error_ratio",
                "estimator-predicted / XLA-reported bytes of the last "
                "reconciled program (1.0 = perfect; leaving the 25% "
                "band journals mem_estimate_drift)"),
        }
    return _gauges


def record_memory_gauges(predicted=None, xla: dict | None = None) -> None:
    """Publish predicted / XLA-reported peak bytes to the metrics
    registry (no-op with telemetry disabled)."""
    from hetu_tpu.obs import registry as _obs
    if not _obs.enabled():
        return
    g = _mem_gauges()
    if predicted is not None:
        g["predicted"].set(float(predicted))
    if xla:
        # first PRESENT key wins; a reported 0 is a real value and must
        # overwrite the previous program's gauge, not leave it stale
        for gauge, keys in (("xla_temp", ("xla_temp_bytes", "temp_bytes")),
                            ("xla_args", ("xla_argument_bytes",
                                          "argument_bytes")),
                            ("xla_out", ("xla_output_bytes",
                                         "output_bytes"))):
            for k in keys:
                if xla.get(k) is not None:
                    g[gauge].set(float(xla[k]))
                    break
