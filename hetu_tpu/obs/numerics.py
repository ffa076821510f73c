"""Numerics observability: tensor-stats flight recorder + NaN provenance.

Every subsystem's acceptance bar is *bitwise-identical replay* — the
gang's kill/recover lineage, partial-reduce's correction folds, the
serving samplers' token streams — yet until now nothing watched the
numbers themselves: a silently divergent replica, a corrupted shard, or
a NaN born three layers before ``grad_guard`` fires was invisible until
a run was already wasted.  This module makes numerical health a scrape:

1. **Deterministic tensor fingerprint** — :func:`fingerprint`: bitcast
   the array to uint32 words and take the position-weighted modular sum
   ``sum((2*i + 1) * word_i) mod 2**32``.  Modular integer addition is
   exact, associative, and commutative, so the result is invariant to
   summation order and pjit sharding layout; the odd weights make it
   sensitive to any single bit flip (flipping bit k of word i changes
   the sum by ``(2*i+1) * 2**k mod 2**32``, which is never 0 — an odd
   number times a power of two below 2**32).  One uint32 scalar per
   tensor, computed on device INSIDE the already-jitted step — no host
   sync.  :func:`host_fingerprint` is the bit-identical numpy mirror
   (checkpoint manifests, token streams, gang-side comparisons), and a
   property test pins the two implementations to each other.

2. **Per-parameter-group stats** — :func:`group_stats`: grad/param
   norms, max-abs, nonfinite counts, zero fraction, and the combined
   group fingerprint, grouped by dotted-path prefix (default depth 2:
   ``blocks.0``, not one bucket for the whole model).

3. **Flight recorder** — :class:`FlightRecorder`: a bounded per-step
   ring of those stats.  ``observe`` stores the DEVICE scalars the
   jitted step returned — nothing is fetched, so recording adds no
   sync to ``Trainer.step``; :meth:`dump` (fired on ``nan_skip`` /
   ``rollback`` / ``replica_divergence``) fetches the ring to host,
   journals a ``flight_dump`` event, and keeps the record readable at
   ``/numerics``.  Installed process-wide via :func:`install`; with no
   recorder installed (or ``HETU_OBS=0``) every seam is one module-
   global load + branch — the ``Trainer.step`` overhead contract.

4. **NaN provenance** — :func:`first_nonfinite` interprets a step's
   jaxpr equation by equation (the ``mem/estimator.py`` jaxpr-walk
   idiom, evaluating instead of simulating) and names the first op
   whose outputs go non-finite: primitive name, equation index, source
   site, and whether the NaN was *born* there (finite inputs) or
   arrived with an already-poisoned input (naming the argument leaf).
   :func:`loss_provenance` is the trainer-shaped entry point
   ``ResilientTrainer`` runs on the first anomaly of a streak — a
   post-mortem harness, never on the hot path.

Metric families: ``hetu_numerics_nonfinite_total{signal}``,
``hetu_numerics_nonfinite_streak``, ``hetu_numerics_flight_dumps_total
{reason}``, ``hetu_numerics_param_fingerprint{group}`` (+ the step
gauge the fleet comparator aligns on).  Journal kinds: ``flight_dump``,
``nan_provenance`` (``replica_divergence`` lives in
:mod:`~hetu_tpu.obs.divergence`).
"""

from __future__ import annotations

import collections
import threading
from typing import Callable, Dict, Optional

import numpy as np

from hetu_tpu.obs import journal as _journal
from hetu_tpu.obs import registry as _obs

__all__ = ["fingerprint", "combine", "tree_fingerprints", "group_stats",
           "host_fingerprint", "host_combine", "host_tree_fingerprints",
           "host_group_stats", "host_fingerprint_ints", "host_state_fingerprint",
           "FlightRecorder",
           "install", "install_recorder", "get_recorder", "recording", "observe",
           "note_outcome", "dump", "flush_fingerprints",
           "first_nonfinite", "loss_provenance"]

_MASK = 0xFFFFFFFF
# odd multiplier (Knuth) for the ordered cross-array combine: position in
# the sorted-name walk matters, summation order within an array does not
_GOLDEN = 2654435761


# ---------------------------------------------------------- device side

def _as_words(x):
    """Bitcast any array to uint32 words (jnp path, trace-safe).  16-bit
    dtypes zero-extend; 64-bit dtypes XOR-fold the high half into the low
    so a flip of any bit still changes its word."""
    import jax
    import jax.numpy as jnp
    x = jnp.ravel(x)
    nbytes = np.dtype(x.dtype).itemsize
    if x.dtype == jnp.bool_:
        return x.astype(jnp.uint32)
    if nbytes == 1:
        return jax.lax.bitcast_convert_type(x, jnp.uint8).astype(jnp.uint32)
    if nbytes == 2:
        return jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
    if nbytes == 4:
        return jax.lax.bitcast_convert_type(x, jnp.uint32)
    b = jax.lax.bitcast_convert_type(x, jnp.uint64)
    return ((b & _MASK) ^ (b >> 32)).astype(jnp.uint32)


def fingerprint(x):
    """Deterministic uint32 fingerprint of one array, computed on device
    (trace-safe: call it inside the jitted step).  Invariant to summation
    order and sharding layout (modular arithmetic is exact), sensitive to
    any single bit flip (odd position weights)."""
    import jax.numpy as jnp
    w = _as_words(x)
    idx = jnp.arange(w.size, dtype=jnp.uint32) * jnp.uint32(2) \
        + jnp.uint32(1)
    return jnp.sum(idx * w, dtype=jnp.uint32)


def combine(fps):
    """Ordered fold of per-array fingerprints into one uint32 scalar
    (callers pass them in sorted-name order, so the combine is
    deterministic)."""
    import jax.numpy as jnp
    acc = jnp.uint32(0)
    for fp in fps:
        acc = acc * jnp.uint32(_GOLDEN) + jnp.asarray(fp, jnp.uint32)
    return acc


def _named_floating(tree) -> list:
    """Sorted ``(dotted.path, leaf)`` pairs for every floating leaf —
    the walk both the grouped stats and the fingerprints share."""
    import jax.numpy as jnp
    from hetu_tpu.core.module import named_parameters
    out = []
    for name, leaf in named_parameters(tree):
        if hasattr(leaf, "dtype") and jnp.issubdtype(
                jnp.asarray(leaf).dtype, jnp.floating):
            out.append((name, leaf))
    out.sort(key=lambda e: e[0])
    return out


def _group_of(name: str, depth: int) -> str:
    """Dotted-path prefix naming the parameter group: the first ``depth``
    components, or — for short paths — everything but the leaf field, so
    a group name never collides with a full parameter name."""
    parts = name.split(".")
    if len(parts) > depth:
        return ".".join(parts[:depth])
    if len(parts) > 1:
        return ".".join(parts[:-1])
    return parts[0]


def tree_fingerprints(tree, depth: int = 2) -> Dict[str, object]:
    """Per-group combined fingerprints of a pytree's floating leaves
    (device scalars; trace-safe)."""
    groups: dict = {}
    for name, leaf in _named_floating(tree):
        groups.setdefault(_group_of(name, depth), []).append(leaf)
    return {g: combine([fingerprint(x) for x in leaves])
            for g, leaves in sorted(groups.items())}


def group_stats(tree, depth: int = 2) -> Dict[str, dict]:
    """Per-parameter-group health stats of a pytree (device scalars;
    trace-safe — this is what rides the jitted train step): L2 ``norm``,
    ``max_abs``, ``nonfinite`` count, ``zero_frac``, and the group
    ``fingerprint``.  float32 accumulation so bf16 trees do not
    overflow."""
    import jax.numpy as jnp
    groups: dict = {}
    for name, leaf in _named_floating(tree):
        groups.setdefault(_group_of(name, depth), []).append(leaf)
    out = {}
    for g, leaves in sorted(groups.items()):
        sq = jnp.zeros((), jnp.float32)
        mx = jnp.zeros((), jnp.float32)
        nonfinite = jnp.zeros((), jnp.int32)
        zeros = jnp.zeros((), jnp.int32)
        count = 0
        for x in leaves:
            xf = jnp.asarray(x).astype(jnp.float32)
            sq = sq + jnp.sum(jnp.square(xf))
            mx = jnp.maximum(mx, jnp.max(jnp.abs(xf)))
            nonfinite = nonfinite + jnp.sum(
                (~jnp.isfinite(xf)).astype(jnp.int32))
            zeros = zeros + jnp.sum((xf == 0).astype(jnp.int32))
            count += int(np.prod(x.shape, initial=1))
        out[g] = {"norm": jnp.sqrt(sq), "max_abs": mx,
                  "nonfinite": nonfinite,
                  "zero_frac": zeros / np.float32(max(count, 1)),
                  "fingerprint": combine(
                      [fingerprint(x) for x in leaves])}
    return out


# ------------------------------------------------------------ host side

def host_fingerprint(x) -> int:
    """Bit-identical numpy mirror of :func:`fingerprint` — checkpoint
    manifests and gang-side comparisons run here, off-device."""
    a = np.asarray(x)
    flat = a.ravel()
    if a.dtype == np.bool_:
        words = flat.astype(np.uint64)
    elif a.dtype.itemsize == 1:
        words = flat.view(np.uint8).astype(np.uint64)
    elif a.dtype.itemsize == 2:
        words = flat.view(np.uint16).astype(np.uint64)
    elif a.dtype.itemsize == 4:
        words = flat.view(np.uint32).astype(np.uint64)
    else:
        b = flat.view(np.uint64)
        words = (b & _MASK) ^ (b >> np.uint64(32))
    n = words.size
    w = (np.arange(n, dtype=np.uint64) * 2 + 1) & _MASK
    return int(((w * words) & _MASK).sum(dtype=np.uint64) & _MASK)


def host_combine(fps) -> int:
    acc = 0
    for fp in fps:
        acc = (acc * _GOLDEN + (int(fp) & _MASK)) & _MASK
    return acc


def host_fingerprint_ints(seq) -> int:
    """Fingerprint of an integer sequence (serving token streams): each
    value taken mod 2**32 as one word.  Pure host arithmetic — the
    per-request cost is O(tokens) numpy, no device work."""
    words = (np.asarray(list(seq), dtype=np.int64)
             .astype(np.uint64) & _MASK)
    n = words.size
    w = (np.arange(n, dtype=np.uint64) * 2 + 1) & _MASK
    return int(((w * words) & _MASK).sum(dtype=np.uint64) & _MASK)


def _host_floating(flat: dict) -> list:
    out = []
    for name in sorted(flat):
        a = np.asarray(flat[name])
        if np.issubdtype(a.dtype, np.floating) or a.dtype.kind == "V" \
                or a.dtype.name in ("bfloat16", "float16"):
            out.append((name, a))
    return out


def host_tree_fingerprints(flat: dict, depth: int = 2) -> Dict[str, int]:
    """Per-group fingerprints of a flat ``{dotted.path: array}`` state
    dict — the gang/manifest form."""
    groups: dict = {}
    for name, a in _host_floating(flat):
        groups.setdefault(_group_of(name, depth), []).append(a)
    return {g: host_combine([host_fingerprint(a) for a in leaves])
            for g, leaves in sorted(groups.items())}


def host_state_fingerprint(flat: dict) -> int:
    """One scalar over a whole flat state dict (sorted-name walk) — the
    per-shard manifest fingerprint recorded beside the CRC32."""
    return host_combine(host_fingerprint(a) for _n, a in
                        _host_floating(flat))


def _finite_all(a: np.ndarray) -> bool:
    try:
        return bool(np.isfinite(a).all())
    except TypeError:  # exotic dtype without an isfinite ufunc
        return bool(np.isfinite(a.astype(np.float32)).all())


def host_group_stats(flat: dict, depth: int = 2) -> Dict[str, dict]:
    """Host mirror of :func:`group_stats` over a flat state dict (the
    gang's partial-reduce gradients arrive as host numpy)."""
    groups: dict = {}
    for name, a in _host_floating(flat):
        groups.setdefault(_group_of(name, depth), []).append(a)
    out = {}
    for g, leaves in sorted(groups.items()):
        sq = 0.0
        mx = 0.0
        nonfinite = 0
        zeros = 0
        count = 0
        for a in leaves:
            af = a.astype(np.float32)
            sq += float(np.sum(np.square(af), dtype=np.float32))
            mx = max(mx, float(np.max(np.abs(af))) if af.size else 0.0)
            nonfinite += int(np.sum(~np.isfinite(af)))
            zeros += int(np.sum(af == 0))
            count += int(af.size)
        out[g] = {"norm": float(np.sqrt(np.float32(sq))), "max_abs": mx,
                  "nonfinite": nonfinite,
                  "zero_frac": float(np.float32(zeros)
                                     / np.float32(max(count, 1))),
                  "fingerprint": host_combine(
                      [host_fingerprint(a) for a in leaves])}
    return out


# ------------------------------------------------------------- telemetry

_num_metrics = None


def _num_m() -> dict:
    global _num_metrics
    if _num_metrics is None:
        reg = _obs.get_registry()
        _num_metrics = {
            "nonfinite": reg.counter(
                "hetu_numerics_nonfinite_total",
                "non-finite training signals observed, by signal (step = "
                "a guarded step's loss/grad-norm went NaN/Inf; "
                "contribution = a partial-reduce gradient arrival was "
                "non-finite)", ("signal",)),
            "streak": reg.gauge(
                "hetu_numerics_nonfinite_streak",
                "consecutive non-finite steps right now (0 while the run "
                "is healthy) — the /healthz red flag"),
            "dumps": reg.counter(
                "hetu_numerics_flight_dumps_total",
                "flight-recorder ring dumps, by the event that triggered "
                "them (nan_skip, rollback, divergence)", ("reason",)),
            "fp": reg.gauge(
                "hetu_numerics_param_fingerprint",
                "post-update parameter fingerprint per parameter group "
                "(uint32, exact in a float64 gauge) — published at the "
                "snapshot cadence so cross-replica comparison rides the "
                "fleet plane", ("group",)),
            "fp_step": reg.gauge(
                "hetu_numerics_fingerprint_step",
                "train step the published parameter fingerprints were "
                "computed at — the fleet comparator only compares "
                "workers whose fingerprint steps match"),
        }
    return _num_metrics


# -------------------------------------------------------- flight recorder

class FlightRecorder:
    """Bounded per-step ring of tensor stats, dumped on anomalies.

    ``observe`` appends the stats dict the jitted step computed — device
    scalars, deliberately NOT fetched (no host sync on the hot path).
    ``dump`` is the cold path: fetch the ring, journal ``flight_dump``,
    remember the record for ``/numerics``.  ``note_outcome`` maintains
    the non-finite streak from values the caller already has on host
    (``ResilientTrainer``'s guard fetched loss/grad-norm anyway), so the
    streak gauge costs no extra sync either."""

    def __init__(self, capacity: int = 16, depth: int = 2):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = int(capacity)
        self.depth = int(depth)
        self.steps = 0                    # host-side step counter
        self.nonfinite_streak = 0
        self.last_dump: Optional[dict] = None
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self._latest_param_fp: Optional[dict] = None
        self._lock = threading.Lock()

    # -- hot path -----------------------------------------------------------

    def observe(self, stats: dict, step: Optional[int] = None) -> None:
        """Ring one step's stats (device scalars stay device scalars)."""
        with self._lock:
            self.steps += 1
            s = self.steps if step is None else int(step)
            self._ring.append((s, stats))
            fp = stats.get("param_fp")
            if fp is not None:
                self._latest_param_fp = (s, fp)

    def note_outcome(self, finite: bool, *, step: Optional[int] = None,
                     signal: str = "step") -> None:
        if finite:
            self.nonfinite_streak = 0
        else:
            self.nonfinite_streak += 1
        if _obs.enabled():
            m = _num_m()
            if not finite:
                m["nonfinite"].labels(signal=signal).inc()
            m["streak"].set(float(self.nonfinite_streak))

    # -- cold path ----------------------------------------------------------

    @staticmethod
    def _to_host(v):
        a = np.asarray(v)
        if a.dtype.kind in "ui":
            return int(a)
        if a.dtype.kind == "b":
            return bool(a)
        return float(np.asarray(a, np.float64))

    def _host_record(self, step: int, stats: dict) -> dict:
        def conv(node):
            if isinstance(node, dict):
                return {k: conv(v) for k, v in sorted(node.items())}
            return self._to_host(node)
        return {"step": int(step), **conv(stats)}

    def dump(self, reason: str, *, step: Optional[int] = None,
             **ctx) -> Optional[dict]:
        """Fetch the ring to host and journal it as one ``flight_dump``
        event.  Returns the record (also kept as ``last_dump`` for the
        ``/numerics`` endpoint)."""
        with self._lock:
            ring = list(self._ring)
        records = [self._host_record(s, st) for s, st in ring]
        rec = {"reason": reason, "records": records,
               **({"step": int(step)} if step is not None else {}), **ctx}
        self.last_dump = rec
        if _obs.enabled():
            _num_m()["dumps"].labels(reason=reason).inc()
        _journal.record("flight_dump", reason=reason,
                        step=int(step) if step is not None else None,
                        records=records)
        return rec

    def flush_fingerprints(self) -> Optional[dict]:
        """Fetch the LATEST observed post-update parameter fingerprints
        to host and publish them as ``hetu_numerics_param_fingerprint
        {group}`` gauges (+ the step gauge).  Called at the snapshot-
        publication cadence — a heartbeat-rate sync, never per step."""
        with self._lock:
            latest = self._latest_param_fp
        if latest is None or not _obs.enabled():
            return None
        step, fps = latest
        host = {g: int(np.asarray(v)) for g, v in sorted(fps.items())}
        m = _num_m()
        for g, v in host.items():
            m["fp"].labels(group=g).set(float(v))
        m["fp_step"].set(float(step))
        return {"step": int(step), "fingerprints": host}

    # -- read side ----------------------------------------------------------

    def tail(self, n: int = 8) -> list:
        """Host view of the newest ``n`` ring entries (syncs: scrape/
        debug path only)."""
        with self._lock:
            ring = list(self._ring)[-int(n):]
        return [self._host_record(s, st) for s, st in ring]

    def snapshot(self) -> dict:
        """The ``/numerics`` payload body."""
        return {"steps": self.steps, "capacity": self.capacity,
                "nonfinite_streak": self.nonfinite_streak,
                "ring": self.tail(self.capacity),
                "last_dump": self.last_dump}


# --------------------------------------------- process-wide installation

_recorder: Optional[FlightRecorder] = None


def install(recorder: Optional[FlightRecorder]) -> Optional[FlightRecorder]:
    """Install ``recorder`` as the process-wide flight recorder (None
    uninstalls).  Install BEFORE the trainer's first step: the stats ride
    the traced program, so a trainer jitted without a recorder keeps its
    stat-free program (the ``grad_guard`` attach-before-first-step
    rule)."""
    global _recorder
    _recorder = recorder
    return recorder


#: obs-namespace alias (``obs.install_recorder``): ``install`` alone is
#: ambiguous next to ``faults.install``.
def install_recorder(recorder: Optional[FlightRecorder]
                     ) -> Optional[FlightRecorder]:
    return install(recorder)


def get_recorder() -> Optional[FlightRecorder]:
    return _recorder


def recording() -> bool:
    """Trace-time check the instrumented step uses: stats are traced into
    the program only when a recorder is installed AND telemetry is on."""
    return _recorder is not None and _obs.enabled()


def observe(stats: dict, step: Optional[int] = None) -> None:
    """Hot-path seam: one module-global load + branch when no recorder
    is installed."""
    r = _recorder
    if r is None:
        return
    r.observe(stats, step=step)


def note_outcome(finite: bool, *, step: Optional[int] = None,
                 signal: str = "step") -> None:
    r = _recorder
    if r is None:
        return
    r.note_outcome(finite, step=step, signal=signal)


def dump(reason: str, *, step: Optional[int] = None,
         **ctx) -> Optional[dict]:
    r = _recorder
    if r is None:
        return None
    return r.dump(reason, step=step, **ctx)


def flush_fingerprints() -> Optional[dict]:
    r = _recorder
    if r is None:
        return None
    return r.flush_fingerprints()


# --------------------------------------------------------- NaN provenance

def _eqn_site(eqn) -> Optional[str]:
    """``file.py:line (function)`` of the user frame that traced this
    equation, or None when the equation carries no user frame."""
    import os as _os

    import jax._src.source_info_util as _siu
    frame = _siu.user_frame(eqn.source_info.traceback)
    if frame is None:
        return None
    return (f"{_os.path.basename(frame.file_name)}:"
            f"{frame.start_line} ({frame.function_name})")


def _sub_closed(eqn):
    """Inner ClosedJaxpr-like of a call-style equation whose invars map
    1:1 onto the outer invals (pjit/remat/custom_* calls), or None."""
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        j = eqn.params.get(key)
        if j is not None and hasattr(j, "jaxpr"):
            return j
    return None


def _leaf_nonfinite(v) -> bool:
    try:
        a = np.asarray(v)
    except TypeError:  # opaque extended dtypes (PRNG keys) carry no NaNs
        return False
    if not (np.issubdtype(a.dtype, np.floating)
            or a.dtype.name in ("bfloat16", "float16")):
        return False
    return not _finite_all(a)


def _interp(jaxpr, consts, args, *, path: str = "", max_eqns: int = 20000):
    """Evaluate a jaxpr equation by equation, returning a provenance
    record for the first equation whose outputs go non-finite (or None
    when everything stays finite)."""
    from jax.core import DropVar
    from jax.extend.core import Literal
    env: dict = {}

    def read(v):
        return v.val if isinstance(v, Literal) else env[v]

    for var, c in zip(jaxpr.constvars, consts):
        env[var] = c
    for var, a in zip(jaxpr.invars, args):
        env[var] = a
    for i, eqn in enumerate(jaxpr.eqns):
        if i >= max_eqns:
            return {"op": "interpreter_budget_exhausted", "eqn": i,
                    "origin": "unknown", "site": None, "path": path}
        invals = [read(v) for v in eqn.invars]
        outvals = eqn.primitive.bind(*invals, **eqn.params)
        if not eqn.primitive.multiple_results:
            outvals = [outvals]
        if any(_leaf_nonfinite(ov) for ov in outvals):
            inputs_finite = not any(_leaf_nonfinite(v) for v in invals)
            sub = _sub_closed(eqn)
            if sub is not None and len(sub.jaxpr.invars) == len(invals):
                inner = _interp(sub.jaxpr, sub.consts, invals,
                                path=f"{path}{eqn.primitive.name}/",
                                max_eqns=max_eqns)
                if inner is not None:
                    return inner
            return {"op": eqn.primitive.name, "eqn": i,
                    "origin": "op" if inputs_finite else "propagated",
                    "site": _eqn_site(eqn), "path": path,
                    "out_shapes": [tuple(getattr(np.asarray(ov), "shape",
                                                 ()))
                                   for ov in outvals
                                   if _leaf_nonfinite(ov)]}
        for var, ov in zip(eqn.outvars, outvals):
            if not isinstance(var, DropVar):
                env[var] = ov
    return None


def first_nonfinite(fn: Callable, *args,
                    arg_names: Optional[list] = None,
                    max_eqns: int = 20000) -> Optional[dict]:
    """Trace ``fn`` to a jaxpr and name the first non-finite producer.

    Checks the flattened inputs first: an already-poisoned argument is
    reported as ``origin="input"`` naming the leaf (provenance stops at
    the program boundary — the poison entered with the data).  Otherwise
    the jaxpr is interpreted equation by equation and the first
    non-finite OUTPUT is the culprit: ``origin="op"`` when its inputs
    were finite (the NaN was born there), ``"propagated"`` otherwise.
    A fully-finite evaluation returns None."""
    import jax
    closed = jax.make_jaxpr(fn)(*args)
    flat = jax.tree_util.tree_leaves(args)
    if arg_names is None:
        from hetu_tpu.core.module import named_parameters
        arg_names = [n for n, _v in named_parameters(tuple(args))]
    for idx, leaf in enumerate(flat):
        if _leaf_nonfinite(leaf):
            name = (arg_names[idx] if arg_names is not None
                    and idx < len(arg_names) else str(idx))
            return {"op": "input", "eqn": -1, "origin": "input",
                    "site": None, "path": "", "leaf": name}
    return _interp(closed.jaxpr, closed.consts, flat, max_eqns=max_eqns)


def loss_provenance(loss_fn: Callable, model, batch, key,
                    max_eqns: int = 20000) -> Optional[dict]:
    """Trainer-shaped provenance: interpret ``value_and_grad`` of the
    loss (forward AND backward equations) on the poisoned step's exact
    (model, batch, key).  A post-mortem harness — one interpreted pass,
    run once per anomaly streak, never on the hot path."""
    import jax

    def wrapped(m, b, k):
        out = loss_fn(m, b, k)
        loss = out[0] if isinstance(out, tuple) else out
        return loss

    from hetu_tpu.core.module import named_parameters
    names = (["model." + n for n, _v in named_parameters(model)]
             + ["batch." + n for n, _v in named_parameters(batch)]
             + ["key." + n for n, _v in named_parameters(key)])
    return first_nonfinite(jax.value_and_grad(wrapped), model, batch, key,
                           arg_names=names, max_eqns=max_eqns)
