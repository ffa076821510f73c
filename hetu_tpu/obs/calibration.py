"""Performance calibration plane: profile store, measured cost-model
constants, and the perf-regression sentinel.

The obs plane measures everything — goodput buckets and rolling MFU
(:mod:`~hetu_tpu.obs.goodput`), per-signature compile wall and
``memory_analysis`` bytes (:mod:`~hetu_tpu.obs.compile`), tuned kernel
timings (:mod:`hetu_tpu.ops.pallas.autotune`), serve-stage profiles
(:mod:`~hetu_tpu.obs.slo`), per-op device tables
(``exec.profiler.device_op_breakdown``) — but until now none of it fed
back into the searchers: Galvatron's ``TimeCostModel`` hardcoded
``mfu=0.4`` / ``dp_overlap=0.7``, and the memory estimator never
reconciled its predictions against the XLA bytes the profiler
records.  This module closes the measure→calibrate loop the same way
PR 11 closed measure→actuate:

1. **ProfileStore** — versioned, CRC'd + signed calibration records
   keyed ``(record_kind, model_sig, mesh_sig, policy, device_kind)``.
   Each ``put`` appends a new version of the key's history (identical
   repeat values are deduplicated, so re-ingesting an unchanged signal
   is idempotent); every record carries a CRC32 over its canonical
   content and the whole store serializes to a canonical, sha256-signed
   envelope — :meth:`ProfileStore.to_json` is **byte-identical across
   same-input runs** (the determinism bar the deployment planner will
   inherit).  Persistence goes through the same exclusive-lock
   merge-on-save as the autotune DB (``exec/checkpoint.
   _atomic_write_bytes`` under a sibling ``.lock``), so a fleet of gang
   workers calibrating concurrently never lose each other's records;
   the merge itself is a pure function of the union of inputs
   (dedupe by content, sort, renumber versions).

2. **Fit layer** — :func:`fit_calibration` turns a key's record
   histories into calibrated cost-model constants with recorded
   residuals: measured ``mfu`` per (model, mesh, policy) from the
   goodput records, measured ``dp_overlap`` from goodput's
   compute/communication partition (``useful / (useful +
   straggler_wait)``), measured ``temp_bytes`` / ``bytes_per_layer``
   from the compile records, and the estimator's measured
   ``mem_error_ratio`` from the reconciliation records.  Each constant
   is the median over the history (deterministic) and the per-version
   deviations ride along as ``residuals``.  The resulting
   :class:`Calibration` is consumed by ``dp_search(calibration=...)``
   / ``TimeCostModel(calibration=...)`` /
   ``MemoryCostModel(calibration=...)`` and
   ``plan_memory(calibration=...)`` / ``MemoryPlanner`` — the
   searchers rank plans by *measured*, not guessed, constants.

3. **Regression sentinel** — every ``put`` past a key's first version
   is graded against the stored baseline (version 1) with the
   deterministic per-metric thresholds in :data:`DEFAULT_THRESHOLDS`;
   a crossing journals ``perf_regression`` (naming the metric, the
   baseline, the observed value, and the ratio), ticks
   ``hetu_calib_regressions_total{metric=}``, and flips the
   ``hetu_calib_regressed`` gauge — which ``/healthz`` surfaces as a
   ``perf_regression`` red flag and ``/fleet/healthz`` maxes across
   workers.  ``/calibration`` renders the installed store;
   ``/fleet/calibration`` renders the rank-0 merge of the shared store
   under the gang dir plus the fleet's ``perf_regression`` journal
   tail.

A store is installed process-wide with :func:`install_store`; the
measurement seams (``autotune.record_entry`` →
:func:`note_tune`, ``profiler.device_op_breakdown`` →
:func:`note_op_breakdown`) emit through module
functions that are a single global load + branch when no store is
installed — the ``Trainer.step`` overhead contract.  The clock is
injectable, so deterministic tests produce bitwise-identical stores.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import pathlib
import statistics
import threading
import time
import zlib
from typing import Callable, Iterable, Mapping, Optional

from hetu_tpu.obs import journal as _journal
from hetu_tpu.obs import registry as _registry

__all__ = [
    "STORE_FORMAT", "DEFAULT_THRESHOLDS",
    "DEFAULT_CONSTANTS",
    "CalibrationKey", "CalibrationStoreError", "ProfileStore",
    "RegressionSentinel", "FittedConstant", "Calibration",
    "fit_calibration", "install_store", "get_store",
    "active_regressions", "note_tune", "note_op_breakdown", "note_mem",
    "store_path",
]

STORE_FORMAT = "hetu-calibration-v1"

# Content signature over the canonical store body (the gang-manifest
# idiom): not a secret against a deliberate attacker who can re-sign,
# but a torn write, a stray editor, or bit rot cannot produce a store
# whose signature still verifies.
_SIGN_KEY = b"hetu-tpu-calibration-v1:"

#: Deterministic sentinel thresholds: ``metric -> (direction, ratio)``.
#: ``"low"`` grades a regression when ``observed < baseline * ratio``
#: (throughput-like metrics — lower is worse); ``"high"`` when
#: ``observed > baseline * ratio`` (latency/byte-like metrics).  The
#: table is the single source of which record values are *graded*;
#: everything else in a record is context, stored but never alarmed on.
DEFAULT_THRESHOLDS = {
    # goodput (throughput-like: lower is a regression)
    "mfu": ("low", 0.90),
    "mfu_rolling": ("low", 0.90),
    "mfu_cumulative": ("low", 0.90),
    "useful_fraction": ("low", 0.90),
    # step / kernel / compile wall (latency-like: higher is a regression)
    "step_time_s": ("high", 1.15),
    "median_s": ("high", 1.15),
    "best_s": ("high", 1.15),
    "compile_s": ("high", 1.50),
    # memory (higher is a regression)
    "temp_bytes": ("high", 1.15),
    "device_peak_bytes": ("high", 1.15),
    # serving stage profile (latency-like)
    "queue_mean_s": ("high", 1.50),
    "prefill_mean_s": ("high", 1.25),
    "decode_mean_s": ("high", 1.25),
    "ttft_p99_s": ("high", 1.25),
    # tiered embedding (PR 15): cache efficiency dropping or pull traffic
    # growing past the baseline regresses the CTR path
    "hbm_hit_rate": ("low", 0.90),
    "host_hit_rate": ("low", 0.90),
    "pull_bytes_per_stage": ("high", 1.15),
    # memory ledger (PR 17): attributed footprint growing past the
    # stored baseline regresses capacity planning before it OOMs
    "kv_pool_bytes": ("high", 1.15),
    "embed_hbm_bytes": ("high", 1.15),
    "hwm_total_bytes": ("high", 1.15),
}

#: Named defaults for every constant the cost models consume — the
#: 0.4/0.7 idiom, centralized.  ``fit_calibration(defaults=True)``
#: fills these for any constant with no record history (journaling
#: ``calibration_fallback``), so the unified planner runs
#: uncalibrated-but-deterministic on a fresh checkout.
DEFAULT_CONSTANTS = {
    # training cost model (TimeCostModel's historical guesses)
    "mfu": 0.4,
    "dp_overlap": 0.7,
    "mem_error_ratio": 1.0,
    # serving-throughput model (SLO stage means, per request)
    "prefill_mean_s": 0.08,
    "decode_mean_s": 0.02,
    "queue_mean_s": 0.005,
    "spec_accept_rate": 0.6,
    # embedding-traffic model (tier hit-rate ceilings)
    "embed_hbm_hit_rate": 0.8,
    "embed_host_hit_rate": 0.95,
}


class CalibrationStoreError(Exception):
    """A store file could not be loaded (torn write, CRC mismatch,
    signature mismatch, alien format) — the diagnosis names which."""


@dataclasses.dataclass(frozen=True)
class CalibrationKey:
    """The five-part record key.  ``model_sig`` identifies the model
    (a config signature, a bench metric name, or a compile site);
    ``mesh_sig`` the device mesh (e.g. ``"dp4tp2"``); ``policy`` the
    remat policy; ``device_kind`` the chip.  Unused parts stay ``""``."""

    record_kind: str
    model_sig: str = ""
    mesh_sig: str = ""
    policy: str = ""
    device_kind: str = ""

    def __str__(self) -> str:
        return "|".join((self.record_kind, self.model_sig, self.mesh_sig,
                         self.policy, self.device_kind))

    @classmethod
    def parse(cls, s: str) -> "CalibrationKey":
        parts = s.split("|")
        # model_sig may itself contain "|" (autotune shape sigs): the
        # other four parts never do, so split off the outer fields
        if len(parts) < 5:
            raise ValueError(f"malformed calibration key {s!r}")
        kind = parts[0]
        mesh, policy, device = parts[-3], parts[-2], parts[-1]
        model = "|".join(parts[1:-3])
        return cls(kind, model, mesh, policy, device)


def _default_device_kind() -> str:
    import jax
    return str(getattr(jax.devices()[0], "device_kind", "cpu"))


def _clean_values(values: Mapping) -> dict:
    """Finite numbers only, sorted keys — the canonical ``values`` form
    (strict-JSON surfaces must never carry NaN/Infinity, and the
    sentinel ratios must never divide by a string)."""
    out = {}
    for k in sorted(values):
        v = values[k]
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            continue
        f = float(v)
        if f != f or f in (float("inf"), float("-inf")):
            continue
        out[str(k)] = f
    return out


def _kernel_values(entry: Mapping) -> dict:
    """The calibration values of one autotune-DB entry: its numeric
    fields (the winning block constants) plus ``best_s`` = the fastest
    measured candidate — the ONE extraction both the live
    ``record_entry`` seam (:func:`note_tune`) and the batch
    :meth:`ProfileStore.ingest_autotune` use, so the same kernel key
    never gets two differently-shaped records."""
    values = {k: float(v) for k, v in entry.items()
              if isinstance(v, (int, float)) and not isinstance(v, bool)}
    timed = [v for v in entry.get("table", {}).values()
             if isinstance(v, float)]
    if timed:
        values["best_s"] = min(timed)
    return values


def _record_ident(rec: dict) -> str:
    """Canonical content identity of a record — everything except its
    ``version`` and content CRC, which the merge renumbers/recomputes."""
    body = {k: v for k, v in rec.items() if k not in ("version", "crc32")}
    return json.dumps(body, sort_keys=True, separators=(",", ":"))


def _record_crc(rec: dict) -> int:
    return zlib.crc32(_record_ident(rec).encode()) & 0xFFFFFFFF


def _merge_histories(a: dict, b: dict) -> dict:
    """Pure, deterministic merge of two ``{key: [records]}`` maps:
    per key, the union of both sides' records deduplicated by content,
    sorted by (original version, timestamp, canonical content), and
    renumbered 1..n — so concurrent writers' records all survive and
    the merged result is a function of the input set only, not arrival
    order.  The ``ts`` tie-break keeps same-version collisions (two
    fresh-process writers both appending version k+1) in chronological
    order, so ``history[0]``/``history[-1]`` stay a meaningful
    baseline/latest pair after a merge."""
    out: dict = {}
    for key in sorted(set(a) | set(b)):
        seen, recs = set(), []
        for rec in list(a.get(key, ())) + list(b.get(key, ())):
            ident = _record_ident(rec)
            if ident in seen:
                continue
            seen.add(ident)
            recs.append((int(rec.get("version", 0)),
                         float(rec.get("ts", 0.0)), ident, rec))
        recs.sort(key=lambda t: (t[0], t[1], t[2]))
        merged = []
        for i, (_v, _ts, _ident, rec) in enumerate(recs, 1):
            r = dict(rec)
            r["version"] = i
            r["crc32"] = _record_crc(r)
            merged.append(r)
        out[key] = merged
    return out


# ------------------------------------------------------------- sentinel

class RegressionSentinel:
    """Grades a record's values against its key's baseline with the
    deterministic per-metric thresholds — pure arithmetic, no state, so
    the same (baseline, observed) pair always yields the same findings
    in the same (sorted-metric) order."""

    def __init__(self, thresholds: Optional[Mapping] = None):
        self.thresholds = dict(DEFAULT_THRESHOLDS if thresholds is None
                               else thresholds)

    def grade(self, baseline: Mapping, observed: Mapping) -> list:
        """Findings for every graded metric whose observed/baseline
        ratio crosses its threshold; ``[]`` for a clean record."""
        findings = []
        for metric in sorted(set(baseline) & set(observed)
                             & set(self.thresholds)):
            b, o = float(baseline[metric]), float(observed[metric])
            if b <= 0.0:
                continue  # no meaningful ratio against a zero baseline
            direction, threshold = self.thresholds[metric]
            ratio = round(o / b, 6)
            bad = ratio < threshold if direction == "low" \
                else ratio > threshold
            if bad:
                findings.append({"metric": metric, "baseline": b,
                                 "observed": o, "ratio": ratio,
                                 "direction": direction,
                                 "threshold": threshold})
        return findings


# ------------------------------------------------------------ the store

_calib_metrics = None


def _calib_m() -> dict:
    global _calib_metrics
    if _calib_metrics is None:
        reg = _registry.get_registry()
        _calib_metrics = {
            "records": reg.counter(
                "hetu_calib_records_total",
                "calibration records appended to the profile store, by "
                "record kind (goodput/compile/kernel/serve/ops/mem)",
                ("kind",)),
            "regressions": reg.counter(
                "hetu_calib_regressions_total",
                "perf-regression findings journaled by the calibration "
                "sentinel, by regressed metric", ("metric",)),
            "regressed": reg.gauge(
                "hetu_calib_regressed",
                "1 while any calibration key's latest record grades as "
                "a perf regression against its stored baseline, else 0 "
                "(the /healthz perf_regression red flag)"),
            "keys": reg.gauge(
                "hetu_calib_keys",
                "distinct calibration keys in the installed profile "
                "store"),
        }
    return _calib_metrics


class ProfileStore:
    """Versioned calibration-record store with sentinel grading.

    ``path=None`` keeps the store in memory (tests, fits over a loaded
    file); with a path every ``put`` merge-saves through the exclusive
    lock (``autosave=False`` defers to an explicit :meth:`save`).  The
    ``clock`` stamps records; inject a constant for byte-identical
    stores across runs."""

    def __init__(self, path: Optional[str] = None, *,
                 clock: Callable[[], float] = time.time,
                 sentinel: Optional[RegressionSentinel] = None,
                 registry: Optional[_registry.MetricsRegistry] = None,
                 autosave: bool = True):
        self.path = str(path) if path is not None else None
        self.clock = clock
        self.sentinel = sentinel if sentinel is not None \
            else RegressionSentinel()
        self.autosave = bool(autosave)
        self.records: dict = {}     # key_str -> [record dicts], v ascending
        self._reg = registry
        self._m = None
        self._lock = threading.RLock()

    def _metrics(self):
        if self._m is None:
            if self._reg is None:
                self._m = _calib_m()
            else:
                # private-registry form (tests): same family names and
                # label schemas, help omitted (a family lookup, not a
                # conflicting re-registration)
                reg = self._reg
                self._m = {
                    "records": reg.counter(
                        "hetu_calib_records_total",
                        labelnames=("kind",)),
                    "regressions": reg.counter(
                        "hetu_calib_regressions_total",
                        labelnames=("metric",)),
                    "regressed": reg.gauge("hetu_calib_regressed"),
                    "keys": reg.gauge("hetu_calib_keys"),
                }
        return self._m

    # -- write side ---------------------------------------------------------

    def put(self, record_kind: str, values: Mapping, *,
            model_sig: str = "", mesh_sig: str = "", policy: str = "",
            device_kind: Optional[str] = None, source: str = "",
            grade: bool = True) -> dict:
        """Append one calibration record; returns it (with ``version``).

        Version 1 of a key IS its baseline; later versions are graded
        against it (``grade=False`` skips — fits-only ingestion).  A
        record whose values and source exactly match the key's latest
        version is deduplicated (the latest is returned unchanged), so
        repeated ingestion of an unchanged signal is idempotent."""
        kind = device_kind if device_kind is not None \
            else _default_device_kind()
        key = CalibrationKey(str(record_kind), str(model_sig),
                             str(mesh_sig), str(policy), str(kind))
        vals = _clean_values(values)
        with self._lock:
            history = self.records.setdefault(str(key), [])
            if history and history[-1]["values"] == vals \
                    and history[-1]["source"] == source:
                return history[-1]
            rec = {"key": str(key), "record_kind": key.record_kind,
                   "version": len(history) + 1, "values": vals,
                   "source": str(source), "ts": float(self.clock())}
            rec["crc32"] = _record_crc(rec)
            history.append(rec)
            findings = []
            if grade and len(history) > 1:
                findings = self.sentinel.grade(history[0]["values"], vals)
            enabled = _registry.enabled()
            if enabled:
                m = self._metrics()
                m["records"].labels(kind=key.record_kind).inc()
                m["keys"].set(float(len(self.records)))
            _journal.record("calibration_update",
                            record_kind=key.record_kind, key=str(key),
                            version=rec["version"])
            for f in findings:
                _journal.record("perf_regression", metric=f["metric"],
                                baseline=f["baseline"],
                                observed=f["observed"], ratio=f["ratio"],
                                key=str(key),
                                record_kind=key.record_kind)
                if enabled:
                    self._metrics()["regressions"].labels(
                        metric=f["metric"]).inc()
            if enabled:
                self._metrics()["regressed"].set(
                    1.0 if self.regressions() else 0.0)
        if self.path is not None and self.autosave:
            self.save()
        return rec

    # -- read side ----------------------------------------------------------

    def _key(self, record_kind, model_sig, mesh_sig, policy,
             device_kind) -> str:
        kind = device_kind if device_kind is not None \
            else _default_device_kind()
        return str(CalibrationKey(str(record_kind), str(model_sig),
                                  str(mesh_sig), str(policy), str(kind)))

    def history(self, record_kind: str, *, model_sig: str = "",
                mesh_sig: str = "", policy: str = "",
                device_kind: Optional[str] = None) -> list:
        with self._lock:
            return list(self.records.get(
                self._key(record_kind, model_sig, mesh_sig, policy,
                          device_kind), ()))

    def get(self, record_kind: str, **kw) -> Optional[dict]:
        """The latest record for the key, or None."""
        h = self.history(record_kind, **kw)
        return h[-1] if h else None

    def regressions(self) -> list:
        """Active regressions: every key whose LATEST record grades as
        regressed against its baseline — recomputed from the records
        (deterministic), so a merged/loaded store reports the same
        findings the writing process journaled.  Sorted by key then
        metric."""
        out = []
        with self._lock:
            for key in sorted(self.records):
                history = self.records[key]
                if len(history) < 2:
                    continue
                for f in self.sentinel.grade(history[0]["values"],
                                             history[-1]["values"]):
                    out.append({"key": key,
                                "record_kind": history[-1]["record_kind"],
                                "version": history[-1]["version"], **f})
        return out

    def summary(self) -> dict:
        """The ``/calibration`` payload: per-kind key counts, each key's
        latest record, and the active regressions."""
        with self._lock:
            kinds: dict = {}
            latest = {}
            for key in sorted(self.records):
                history = self.records[key]
                k = history[-1]["record_kind"]
                kinds[k] = kinds.get(k, 0) + 1
                latest[key] = {"version": history[-1]["version"],
                               "values": dict(history[-1]["values"]),
                               "source": history[-1]["source"],
                               "ts": history[-1]["ts"]}
            return {"installed": True, "format": STORE_FORMAT,
                    "path": self.path, "keys": len(self.records),
                    "kinds": kinds, "latest": latest,
                    "regressions": self.regressions()}

    # -- serialization ------------------------------------------------------

    def _canonical_body(self) -> str:
        with self._lock:
            body = {"format": STORE_FORMAT, "records": self.records}
            return json.dumps(body, sort_keys=True, separators=(",", ":"))

    def to_json(self) -> bytes:
        """The exact on-disk bytes: canonical body + CRC32 + sha256
        signature over it.  Byte-identical across same-input runs (sorted
        keys, canonical separators, injectable clock)."""
        canon = self._canonical_body()
        envelope = {
            "body": json.loads(canon),
            "crc32": zlib.crc32(canon.encode()) & 0xFFFFFFFF,
            "sha256": hashlib.sha256(_SIGN_KEY + canon.encode()).hexdigest(),
        }
        return json.dumps(envelope, sort_keys=True,
                          separators=(",", ":")).encode()

    @staticmethod
    def _verify(raw: bytes, where: str) -> dict:
        """Parse + verify an envelope; returns the records map.  Raises
        :class:`CalibrationStoreError` naming the failure."""
        try:
            envelope = json.loads(raw.decode())
        except (ValueError, UnicodeDecodeError) as e:
            raise CalibrationStoreError(
                f"calibration store {where}: not valid JSON ({e}) — torn "
                f"write or alien file") from e
        body = envelope.get("body")
        if not isinstance(body, dict) or body.get("format") != STORE_FORMAT:
            raise CalibrationStoreError(
                f"calibration store {where}: format is not {STORE_FORMAT}")
        canon = json.dumps(body, sort_keys=True, separators=(",", ":"))
        if envelope.get("crc32") != (zlib.crc32(canon.encode())
                                     & 0xFFFFFFFF):
            raise CalibrationStoreError(
                f"calibration store {where}: CRC32 mismatch — the bytes "
                f"were damaged after writing")
        expect = hashlib.sha256(_SIGN_KEY + canon.encode()).hexdigest()
        if envelope.get("sha256") != expect:
            raise CalibrationStoreError(
                f"calibration store {where}: signature mismatch — the "
                f"file was modified after signing")
        records = body.get("records", {})
        for key, history in records.items():
            for rec in history:
                if rec.get("crc32") != _record_crc(rec):
                    raise CalibrationStoreError(
                        f"calibration store {where}: record CRC mismatch "
                        f"at key {key!r} version {rec.get('version')}")
        return records

    @classmethod
    def load(cls, path: str, **kw) -> "ProfileStore":
        """Load (and verify) a store file; a missing file yields an
        empty store bound to the path."""
        store = cls(path, **kw)
        try:
            raw = pathlib.Path(path).read_bytes()
        except OSError:
            return store
        store.records = cls._verify(raw, str(path))
        return store

    def save(self) -> str:
        """Exclusive-lock merge-on-save (the autotune-DB discipline):
        re-read the disk copy under the lock, merge this store's records
        in (pure content merge — no writer's records are ever lost),
        publish atomically, and adopt the merged view in memory."""
        if self.path is None:
            raise ValueError("ProfileStore has no path; pass one to save")
        from hetu_tpu.exec.checkpoint import _atomic_write_bytes
        path = pathlib.Path(self.path)
        path.parent.mkdir(parents=True, exist_ok=True)
        lock = path.with_name(path.name + ".lock")
        lf = open(lock, "a+b")
        try:
            try:
                import fcntl
                fcntl.flock(lf, fcntl.LOCK_EX)
                locked = True
            except ImportError:  # non-POSIX: no advisory lock exists
                locked = False
            try:
                disk = self._verify(path.read_bytes(), str(path))
            except OSError:
                disk = {}
            except CalibrationStoreError:
                # a damaged store must not poison new measurements: the
                # merge starts fresh (the damage is diagnosed on load)
                disk = {}
            with self._lock:
                self.records = _merge_histories(disk, self.records)
                payload = self.to_json()
            if locked:
                _atomic_write_bytes(str(path), payload)
            else:
                tmp = path.with_name(path.name + f".tmp.{os.getpid()}")
                tmp.write_bytes(payload)
                tmp.replace(path)
        finally:
            lf.close()
        return str(path)

    # -- ingestion ----------------------------------------------------------

    def ingest_goodput(self, meter, *, model_sig: str, mesh_sig: str = "",
                       policy: str = "",
                       device_kind: Optional[str] = None) -> dict:
        """One ``goodput`` record from a
        :class:`~hetu_tpu.obs.goodput.GoodputMeter` snapshot: bucket
        totals/fractions + rolling/cumulative MFU — the measured-MFU and
        compute/communication-partition inputs to the fit."""
        snap = meter.snapshot()
        values = {"mfu_rolling": snap["mfu_rolling"],
                  "mfu_cumulative": snap["mfu_cumulative"],
                  "total_s": snap["total"]}
        for bucket, v in snap["totals"].items():
            values[f"{bucket}_s"] = v
        for bucket, v in snap["fractions"].items():
            values[f"{bucket}_fraction"] = v
        return self.put("goodput", values, model_sig=model_sig,
                        mesh_sig=mesh_sig, policy=policy,
                        device_kind=device_kind, source="obs.goodput")

    def ingest_compile(self, *watchers, model_sig: str, mesh_sig: str = "",
                       policy: str = "",
                       device_kind: Optional[str] = None) -> dict:
        """One ``compile`` record over
        :class:`~hetu_tpu.obs.compile.InstrumentedJit` sites: total
        compile wall, program count, and the largest program's
        ``memory_analysis`` temp/argument bytes (the measured memory
        inputs to the fit; zeros on backends without memory analysis)."""
        compile_s, programs, temp, args_b = 0.0, 0, 0.0, 0.0
        for w in watchers:
            for prog in w.report().values():
                compile_s += float(prog["compile_s"])
                programs += 1
                mb = prog.get("memory_bytes", {})
                temp = max(temp, float(mb.get("temp", 0.0)))
                args_b = max(args_b, float(mb.get("argument", 0.0)))
        values = {"compile_s": compile_s, "programs": float(programs),
                  "temp_bytes": temp, "argument_bytes": args_b}
        return self.put("compile", values, model_sig=model_sig,
                        mesh_sig=mesh_sig, policy=policy,
                        device_kind=device_kind, source="obs.compile")

    def ingest_slo(self, engine, *, model_sig: str, mesh_sig: str = "",
                   policy: str = "",
                   device_kind: Optional[str] = None) -> dict:
        """One ``serve`` record from an
        :class:`~hetu_tpu.obs.slo.SLOEngine`: per-stage mean/fraction
        profile, request/violation counts, shed pressure."""
        values = {"requests": float(engine.requests),
                  "shed_pressure": float(engine.shed_pressure())}
        for stage, ent in engine.stage_summary().items():
            values[f"{stage}_mean_s"] = ent["mean_s"]
            values[f"{stage}_fraction"] = ent["fraction"]
        for target, n in engine.violations.items():
            values[f"{target}_violations"] = float(n)
        return self.put("serve", values, model_sig=model_sig,
                        mesh_sig=mesh_sig, policy=policy,
                        device_kind=device_kind, source="obs.slo")

    def ingest_autotune(self, *, device_kind: Optional[str] = None) -> list:
        """One ``kernel`` record per autotune-DB entry (best measured
        candidate seconds + the winning block constants) — a retune that
        lands >15% slower than the stored baseline trips the sentinel.
        Autosave is deferred to ONE merge-save after the loop: a
        per-put save would re-read, re-verify, and atomically rewrite
        the whole store once per DB entry."""
        from hetu_tpu.ops.pallas import autotune as _autotune
        out = []
        prev_autosave, self.autosave = self.autosave, False
        try:
            for full_key, entry in sorted(_autotune._load().items()):
                parts = full_key.split("|")
                if len(parts) < 3:
                    continue
                kernel, kind = parts[0], parts[1]
                if device_kind is not None and kind != device_kind:
                    continue
                sig = "|".join(parts[2:])
                values = _kernel_values(entry)
                if not values:
                    continue
                out.append(self.put("kernel", values,
                                    model_sig=f"{kernel}|{sig}",
                                    device_kind=kind,
                                    source="ops.pallas.autotune"))
        finally:
            self.autosave = prev_autosave
        if out and self.path is not None and self.autosave:
            self.save()
        return out

    def ingest_op_breakdown(self, per_op: Mapping, totals: Mapping, *,
                            model_sig: str, mesh_sig: str = "",
                            policy: str = "",
                            device_kind: Optional[str] = None,
                            top: int = 5) -> dict:
        """One ``ops`` record from a
        ``exec.profiler.device_op_breakdown`` table: device/copy totals
        plus the top ops by device seconds (deterministic order)."""
        values = {"device_s": float(totals.get("device_s", 0.0)),
                  "copy_s": float(totals.get("copy_s", 0.0))}
        ranked = sorted(per_op.items(), key=lambda kv: (-kv[1], kv[0]))
        for name, secs in ranked[:max(int(top), 0)]:
            values[f"op:{name}_s"] = float(secs)
        return self.put("ops", values, model_sig=model_sig,
                        mesh_sig=mesh_sig, policy=policy,
                        device_kind=device_kind, source="exec.profiler")

    def ingest_embed(self, embedding, *, model_sig: str, mesh_sig: str = "",
                     policy: str = "",
                     device_kind: Optional[str] = None) -> dict:
        """One ``embed`` record from a
        :class:`~hetu_tpu.embed.tier.TieredEmbedding` (or its
        ``tier_stats()`` dict): per-tier hit rates, pull bytes/step, and
        PS resident bytes — the CTR-path signals the regression sentinel
        grades (a hit-rate drop >10% or pull-traffic growth >15% against
        the stored baseline journals ``perf_regression``)."""
        stats = embedding if isinstance(embedding, Mapping) \
            else embedding.tier_stats()
        values = {
            "hbm_hit_rate": float(stats["hbm"]["hit_rate"]),
            "host_hit_rate": float(stats["host"]["hit_rate"]),
            "pull_bytes_per_stage": float(stats["pull_bytes_per_stage"]),
            "ps_resident_bytes": float(stats["ps"]["resident_bytes"]),
            "hbm_resident": float(stats["hbm"]["resident"]),
            "promotions": float(stats["hbm"]["promotions"]),
            "demotions": float(stats["hbm"]["demotions"]),
            "evictions": float(stats["hbm"]["evictions"]),
            "stages": float(stats["stages"]),
        }
        return self.put("embed", values, model_sig=model_sig,
                        mesh_sig=mesh_sig, policy=policy,
                        device_kind=device_kind, source="embed.tier")

    def ingest_memory(self, ledger, *, model_sig: str, mesh_sig: str = "",
                      policy: str = "",
                      device_kind: Optional[str] = None) -> dict:
        """One ``memory`` record from a
        :class:`~hetu_tpu.obs.memledger.MemoryLedger` (or a ``snapshot()``
        dict): per-component attributed bytes, the total high-water mark,
        and the pressure/fragmentation gauges.  The graded values are the
        byte footprints — a >15% growth against the stored baseline
        journals ``perf_regression`` while the fleet still fits."""
        snap = ledger if isinstance(ledger, Mapping) else ledger.snapshot()
        values = {"total_bytes": float(snap["total_bytes"]),
                  "hwm_total_bytes": float(snap["hwm_bytes"]["total"]),
                  "fragmentation": float(snap["fragmentation"]),
                  "pressure": float(snap["pressure"])}
        for comp, nbytes in sorted(snap["components"].items()):
            values[f"{comp}_bytes"] = float(nbytes)
        return self.put("memory", values, model_sig=model_sig,
                        mesh_sig=mesh_sig, policy=policy,
                        device_kind=device_kind, source="obs.memledger")


# ------------------------------------------------------------- fit layer

@dataclasses.dataclass(frozen=True)
class FittedConstant:
    """One calibrated constant: the median over its record series plus
    the per-version deviations from the fit (the residuals the planner's
    determinism bar covers)."""

    name: str
    value: float
    n: int
    residuals: tuple = ()


@dataclasses.dataclass(frozen=True)
class Calibration:
    """A bundle of fitted constants the cost models consume.  Duck-typed
    by ``TimeCostModel`` / ``MemoryCostModel`` / ``plan_memory`` through
    :meth:`get` and the named properties; construct directly for manual
    overrides (no store required)."""

    constants: tuple = ()           # FittedConstant, sorted by name
    source: str = ""
    # constants that are named defaults, not fits (no record history
    # when ``fit_calibration(defaults=...)`` ran) — the
    # ``calibration_fallback`` diagnosis, carried on the artifact
    fallbacks: tuple = ()

    def get(self, name: str, default=None):
        for c in self.constants:
            if c.name == name:
                return c.value
        return default

    def constant(self, name: str) -> Optional[FittedConstant]:
        for c in self.constants:
            if c.name == name:
                return c
        return None

    @property
    def mfu(self):
        return self.get("mfu")

    @property
    def dp_overlap(self):
        return self.get("dp_overlap")

    @property
    def bytes_per_layer(self):
        return self.get("bytes_per_layer")

    @property
    def mem_error_ratio(self):
        return self.get("mem_error_ratio")

    def to_json(self) -> str:
        """Canonical serialization — byte-identical for identical
        constants (sorted keys, canonical separators)."""
        return json.dumps(dataclasses.asdict(self), sort_keys=True,
                          separators=(",", ":"))

    @classmethod
    def of(cls, source: str = "manual", **constants) -> "Calibration":
        """Manual construction: ``Calibration.of(mfu=0.55,
        dp_overlap=0.9)``."""
        fitted = tuple(FittedConstant(name, float(v), 1)
                       for name, v in sorted(constants.items())
                       if v is not None)
        return cls(fitted, source)


def _fit_series(name: str, series: Iterable[float]
                ) -> Optional[FittedConstant]:
    vals = [float(v) for v in series]
    if not vals:
        return None
    fitted = float(statistics.median(vals))
    residuals = tuple(round(v - fitted, 12) for v in vals)
    return FittedConstant(name, fitted, len(vals), residuals)


def fit_calibration(store: ProfileStore, *, model_sig: str = "",
                    mesh_sig: str = "", policy: str = "",
                    device_kind: Optional[str] = None,
                    n_layers: Optional[int] = None,
                    defaults=None) -> Calibration:
    """Fit cost-model constants for one key from the store's record
    histories — a pure function of the records (median fit, residuals
    recorded), so identical stores yield bitwise-identical calibrations:

    - ``mfu`` from the goodput records (rolling MFU, falling back to
      cumulative when the rolling window was empty);
    - ``dp_overlap`` from goodput's compute/communication partition:
      ``useful / (useful + straggler_wait)`` per record, clamped to
      [0, 1] — time NOT spent waiting on the slowest contributor is
      time the gradient exchange overlapped compute;
    - ``temp_bytes`` (and, given ``n_layers``, ``bytes_per_layer``)
      from the compile records' ``memory_analysis`` bytes;
    - ``mem_error_ratio`` from the estimator-reconciliation records
      (predicted / XLA-reported bytes — the correction
      ``plan_memory(calibration=...)`` divides by);
    - ``step_time_s`` from explicit ``step`` records when a driver
      ingested them;
    - the serving stage means (``prefill_mean_s``/``decode_mean_s``/
      ``queue_mean_s``) from the SLO ``serve`` records, and the
      embedding-tier signals (``embed_hbm_hit_rate``/
      ``embed_host_hit_rate``/``embed_pull_bytes_per_stage``) from the
      ``embed`` records — the unified planner's serving-throughput and
      embedding-traffic constants.

    ``defaults`` hardens the empty/single-record path: ``True`` fills
    any constant in :data:`DEFAULT_CONSTANTS` that has no record
    history with its named default (``n=0`` marks it unfitted, the
    name lands in :attr:`Calibration.fallbacks`, and one
    ``calibration_fallback`` event is journaled); a mapping supplies a
    custom defaults table.  The planner passes ``defaults=True`` so a
    fresh checkout plans deterministically instead of raising.
    """
    key = dict(model_sig=model_sig, mesh_sig=mesh_sig, policy=policy,
               device_kind=device_kind)
    consts = []

    good = store.history("goodput", **key)
    mfu_series, overlap_series = [], []
    for rec in good:
        v = rec["values"]
        mfu = v.get("mfu_rolling", 0.0) or v.get("mfu_cumulative", 0.0)
        if mfu > 0:
            mfu_series.append(mfu)
        useful = v.get("useful_s", 0.0)
        wait = v.get("straggler_wait_s", 0.0)
        if useful + wait > 0:
            overlap_series.append(
                min(max(useful / (useful + wait), 0.0), 1.0))
    consts.append(_fit_series("mfu", mfu_series))
    consts.append(_fit_series("dp_overlap", overlap_series))

    comp = store.history("compile", **key)
    temp_series = [rec["values"].get("temp_bytes", 0.0) for rec in comp
                   if rec["values"].get("temp_bytes", 0.0) > 0]
    consts.append(_fit_series("temp_bytes", temp_series))
    if n_layers and temp_series:
        consts.append(_fit_series(
            "bytes_per_layer", [t / float(n_layers) for t in temp_series]))

    mem = store.history("mem", **key)
    consts.append(_fit_series(
        "mem_error_ratio",
        [rec["values"]["ratio"] for rec in mem
         if rec["values"].get("ratio", 0.0) > 0]))

    steps = store.history("step", **key)
    consts.append(_fit_series(
        "step_time_s",
        [rec["values"]["step_time_s"] for rec in steps
         if rec["values"].get("step_time_s", 0.0) > 0]))

    serve = store.history("serve", **key)
    for name in ("prefill_mean_s", "decode_mean_s", "queue_mean_s"):
        consts.append(_fit_series(
            name, [rec["values"][name] for rec in serve
                   if rec["values"].get(name, 0.0) > 0]))

    emb = store.history("embed", **key)
    for src_name, fit_name in (
            ("hbm_hit_rate", "embed_hbm_hit_rate"),
            ("host_hit_rate", "embed_host_hit_rate"),
            ("pull_bytes_per_stage", "embed_pull_bytes_per_stage")):
        consts.append(_fit_series(
            fit_name, [rec["values"][src_name] for rec in emb
                       if src_name in rec["values"]]))

    fitted = tuple(sorted((c for c in consts if c is not None),
                          key=lambda c: c.name))
    src = str(CalibrationKey("fit", model_sig, mesh_sig, policy,
                             device_kind if device_kind is not None
                             else _default_device_kind()))
    fallbacks: tuple = ()
    if defaults:
        table = DEFAULT_CONSTANTS if defaults is True else defaults
        have = {c.name for c in fitted}
        missing = [name for name in sorted(table) if name not in have]
        if missing:
            fitted = tuple(sorted(
                fitted + tuple(FittedConstant(name, float(table[name]), 0)
                               for name in missing),
                key=lambda c: c.name))
            fallbacks = tuple(missing)
            _journal.record("calibration_fallback", constants=missing,
                            key=src)
    return Calibration(fitted, src, fallbacks)


# ------------------------------------------------ process-wide installation

_store: Optional[ProfileStore] = None


def install_store(store: Optional[ProfileStore]) -> Optional[ProfileStore]:
    """Install ``store`` as the process-wide sink for the measurement
    seams (:func:`note_tune` / :func:`note_op_breakdown` /
    :func:`note_mem`) and the ``/calibration`` endpoint (None
    uninstalls).  Returns the store."""
    global _store
    _store = store
    return store


def get_store() -> Optional[ProfileStore]:
    return _store


def store_path(gang_dir: str) -> str:
    """The fleet-shared store under a gang dir — every worker
    merge-saves into it, rank 0 serves it at ``/fleet/calibration``."""
    return os.path.join(gang_dir, "obs", "calibration.json")


def active_regressions() -> list:
    """The installed store's active regressions (``[]`` when none is
    installed) — the ``/healthz`` red-flag read."""
    s = _store
    if s is None:
        return []
    return s.regressions()


def note_tune(kernel: str, sig: str, entry: Mapping, *,
              device_kind: Optional[str] = None) -> None:
    """Measurement seam for ``autotune.record_entry``: fold one tuned
    kernel entry into the installed store.  One global load + branch
    when no store is installed; never raises into the tuner."""
    s = _store
    if s is None or not _registry.enabled():
        return
    values = _kernel_values(entry)
    if not values:
        return
    try:
        s.put("kernel", values, model_sig=f"{kernel}|{sig}",
              device_kind=device_kind, source="ops.pallas.autotune")
    except Exception:
        pass  # a calibration hiccup must never fail the tune itself


def note_op_breakdown(per_op: Mapping, totals: Mapping, *,
                      model_sig: str = "device_op_breakdown") -> None:
    """Measurement seam for ``profiler.device_op_breakdown``: fold the
    parsed per-op device table into the installed store (no-op without
    one; never raises into the profiler)."""
    s = _store
    if s is None or not _registry.enabled():
        return
    try:
        s.ingest_op_breakdown(per_op, totals, model_sig=model_sig)
    except Exception:
        pass


def note_mem(predicted_bytes: float, xla_bytes: float, ratio: float, *,
             model_sig: str = "") -> None:
    """Measurement seam for the estimator reconciliation
    (``mem.estimator.reconcile``): fold one predicted-vs-XLA comparison
    into the installed store as a ``mem`` record — the
    ``mem_error_ratio`` fit input."""
    s = _store
    if s is None or not _registry.enabled():
        return
    try:
        s.put("mem", {"predicted_bytes": float(predicted_bytes),
                      "xla_bytes": float(xla_bytes),
                      "ratio": float(ratio)},
              model_sig=model_sig, source="mem.estimator")
    except Exception:
        pass
