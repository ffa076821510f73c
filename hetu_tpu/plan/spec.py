"""Frozen deployment inputs and the signed, versioned ``Plan``.

The unified planner (ROADMAP item 1's composition layer) turns one
:class:`DeploymentSpec` — model signature, fleet shape, HBM budget, SLO
targets, workload mix — into exactly one :class:`Plan` covering every
axis the last six PRs made tunable: the training mesh (dp × tp × pp,
pipeline schedule, remat policy, microbatch), the gang (size,
partial-reduce deadline), the serving tier (replica count,
prefill/decode role split, bucket ladder, KV pool pages, speculative
``spec_k``) and the embedding tier (HBM hot-row budget, promote/demote
thresholds, host cache capacity, int8 vs f32 storage).

Both dataclasses are frozen and serialize through the ProfileStore's
canonical-envelope idiom (``obs/calibration.py``): a canonical JSON body
(sorted keys, canonical separators) wrapped with a CRC32 and a sha256
signature over a format-versioned sign key, so identical inputs yield
byte-identical ``to_json`` output and a torn write, a stray editor, or
bit rot is diagnosed by name (:class:`PlanError`) rather than half-read.
Older-format plans (``hetu-plan-v0``) load with the missing axes filled
from the dataclass defaults — a plan file outlives the planner version
that wrote it.

Determinism bar: this package never touches wall clocks or entropy (the
plan-determinism lint in ``tests/test_obs.py`` rejects ``time``/
``random`` imports and unsorted dict iteration in ``hetu_tpu/plan/``),
so a Plan is a pure function of (spec, calibration).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import pathlib
import zlib

__all__ = [
    "PLAN_FORMAT", "PlanError", "DeploymentSpec", "Plan",
]

PLAN_FORMAT = "hetu-plan-v1"
# older envelope formats still accepted by Plan.from_json (missing
# fields fill from the dataclass defaults)
_COMPAT_FORMATS = ("hetu-plan-v0",)
# content signature over the canonical plan body (the gang-manifest /
# calibration-store discipline): not a secret — the key is in the repo —
# but a torn write or an edited file cannot produce a plan whose
# signature still verifies.
_SIGN_KEYS = {
    "hetu-plan-v1": b"hetu-tpu-plan-v1:",
    "hetu-plan-v0": b"hetu-tpu-plan-v0:",
}


class PlanError(Exception):
    """A plan could not be loaded or verified (torn write, CRC mismatch,
    signature mismatch, alien format) — the diagnosis names which."""


def _canon(body: dict) -> str:
    return json.dumps(body, sort_keys=True, separators=(",", ":"))


@dataclasses.dataclass(frozen=True)
class DeploymentSpec:
    """Everything the planner is allowed to know, frozen.

    One spec = one deployment question: this model, on this fleet,
    under this HBM budget, serving this workload mix against these SLO
    targets.  The planner is a pure function of (spec, calibration);
    anything not in the spec cannot influence the emitted plan.
    """

    # -- model -------------------------------------------------------------
    model_sig: str = "model"
    n_layers: int = 2
    hidden_size: int = 64
    seq_len: int = 128
    vocab_size: int = 32000
    mlp_ratio: int = 4
    global_batch: int = 8

    # -- fleet shape / HBM budget -----------------------------------------
    n_devices: int = 8
    serve_devices: int = 0          # devices carved out for the serving fleet
    hbm_bytes: float = 16e9         # per-device budget
    peak_flops: float = 197e12
    device_kind: str = ""

    # -- SLO targets -------------------------------------------------------
    ttft_p99_s: float = 0.5
    decode_tps: float = 0.0         # fleet decode-throughput floor (0 = none)

    # -- serving workload mix ----------------------------------------------
    requests_per_s: float = 0.0
    prompt_p50: int = 16
    prompt_p99: int = 64
    decode_len: int = 16            # mean generated tokens per request
    slots_per_replica: int = 8
    page_size: int = 16
    speculative: bool = False       # a draft model exists: search spec_k > 0

    # -- embedding workload ------------------------------------------------
    embed_rows: int = 0
    embed_dim: int = 0
    embed_hot_fraction: float = 0.05

    # -- training-side baseline -------------------------------------------
    partial_deadline_s: float = 0.0   # 0 = synchronous barrier

    def __post_init__(self):
        for name in ("n_layers", "hidden_size", "seq_len", "vocab_size",
                     "mlp_ratio", "global_batch", "n_devices",
                     "slots_per_replica", "page_size"):
            if int(getattr(self, name)) < 1:
                raise ValueError(f"{name} must be >= 1, "
                                 f"got {getattr(self, name)}")
        for name in ("serve_devices", "embed_rows", "embed_dim",
                     "prompt_p50", "prompt_p99", "decode_len"):
            if int(getattr(self, name)) < 0:
                raise ValueError(f"{name} must be >= 0, "
                                 f"got {getattr(self, name)}")
        if self.serve_devices > self.n_devices:
            raise ValueError(
                f"serve_devices ({self.serve_devices}) exceeds the fleet "
                f"({self.n_devices})")
        if not 0.0 <= self.embed_hot_fraction <= 1.0:
            raise ValueError("embed_hot_fraction must be in [0, 1], "
                             f"got {self.embed_hot_fraction}")
        if self.hbm_bytes <= 0 or self.peak_flops <= 0:
            raise ValueError("hbm_bytes and peak_flops must be positive")

    @property
    def train_devices(self) -> int:
        return self.n_devices - self.serve_devices

    def to_json(self) -> str:
        """Canonical serialization — byte-identical for identical specs."""
        return _canon(dataclasses.asdict(self))

    def signature(self) -> str:
        """sha256 over the canonical body: the spec identity the emitted
        plan's provenance (``spec_sha256``) and journal events carry."""
        return hashlib.sha256(self.to_json().encode()).hexdigest()


@dataclasses.dataclass(frozen=True)
class Plan:
    """One deployment decision, frozen and signed.

    Every axis the runtime consumes lives here; ``apply.py`` maps the
    serving axes onto ``ServingEngine`` kwargs and the training axes
    onto the gang's actuators.  Zero values mean "axis not deployed"
    (``gang_size=0`` = no training gang, ``replicas=0`` = no serving
    fleet, ``embed_hbm_rows=0`` = no tiered embedding), so one Plan
    type covers train-only, serve-only, and hybrid deployments.
    """

    # -- parallelism / training axes --------------------------------------
    dp: int = 1
    tp: int = 1
    pp: int = 1
    schedule: str = "none"          # "none" | "gpipe" | "1f1b" | "interleaved"
    virtual_stages: int = 1
    remat_policy: str = "none"
    microbatch: int = 1
    zero: bool = False
    gang_size: int = 0
    partial_deadline_s: float = 0.0

    # -- serving axes ------------------------------------------------------
    replicas: int = 0
    prefill_workers: int = 0        # 0/0 split = colocated replicas
    decode_workers: int = 0
    slots_per_replica: int = 8
    bucket_ladder: tuple = ()
    kv_pool_pages: int = 0          # 0 = engine default sizing
    page_size: int = 16
    spec_k: int = 0                 # 0 = no speculative decoding

    # -- embedding axes ----------------------------------------------------
    embed_hbm_rows: int = 0
    embed_host_rows: int = 0
    embed_storage: str = "f32"      # "f32" | "int8"
    promote_touches: int = 2
    demote_idle: int = 0

    # -- provenance / predictions -----------------------------------------
    spec_sha256: str = ""
    calibration_sha256: str = ""
    predicted: tuple = ()           # sorted ((name, value), ...) pairs
    feasible: bool = True

    def __post_init__(self):
        if self.embed_storage not in ("f32", "int8"):
            raise ValueError(f"embed_storage must be 'f32' or 'int8', "
                             f"got {self.embed_storage!r}")
        if self.schedule not in ("none", "gpipe", "1f1b", "interleaved"):
            raise ValueError(f"unknown pipeline schedule "
                             f"{self.schedule!r}")
        if self.prefill_workers + self.decode_workers not in (
                0, self.replicas):
            raise ValueError(
                f"role split {self.prefill_workers}+{self.decode_workers} "
                f"does not cover replicas={self.replicas} (0/0 = "
                f"colocated)")
        # normalize sequence fields so hand-built and deserialized plans
        # compare (and serialize) identically
        object.__setattr__(self, "bucket_ladder",
                           tuple(int(b) for b in self.bucket_ladder))
        object.__setattr__(
            self, "predicted",
            tuple(sorted((str(k), float(v)) for k, v in self.predicted)))

    # -- canonical serialization ------------------------------------------

    def _body(self) -> dict:
        plan = dataclasses.asdict(self)
        plan["bucket_ladder"] = list(self.bucket_ladder)
        plan["predicted"] = [[k, v] for k, v in self.predicted]
        return {"format": PLAN_FORMAT, "plan": plan}

    @property
    def sha256(self) -> str:
        """The plan identity: sha256 over the canonical body (what
        ``plan_emit`` / ``plan_apply`` journal)."""
        return hashlib.sha256(_canon(self._body()).encode()).hexdigest()

    def to_json(self) -> bytes:
        """The exact on-disk bytes: canonical body + CRC32 + sha256
        signature over it.  Byte-identical from identical inputs."""
        canon = _canon(self._body())
        key = _SIGN_KEYS[PLAN_FORMAT]
        envelope = {
            "body": json.loads(canon),
            "crc32": zlib.crc32(canon.encode()) & 0xFFFFFFFF,
            "sha256": hashlib.sha256(key + canon.encode()).hexdigest(),
        }
        return json.dumps(envelope, sort_keys=True,
                          separators=(",", ":")).encode()

    @classmethod
    def from_json(cls, raw: bytes, where: str = "<memory>") -> "Plan":
        """Parse + verify an envelope; raises :class:`PlanError` naming
        the failure (torn write, alien format, CRC, signature).  Bodies
        in an older accepted format load with missing axes defaulted."""
        try:
            envelope = json.loads(
                raw.decode() if isinstance(raw, bytes) else raw)
        except (ValueError, UnicodeDecodeError) as e:
            raise PlanError(
                f"plan {where}: not valid JSON ({e}) — torn write or "
                f"alien file") from e
        body = envelope.get("body") if isinstance(envelope, dict) else None
        if not isinstance(body, dict) or body.get("format") not in (
                (PLAN_FORMAT,) + _COMPAT_FORMATS):
            raise PlanError(
                f"plan {where}: format is not {PLAN_FORMAT} (or a "
                f"compatible older version)")
        fmt = body["format"]
        canon = _canon(body)
        if envelope.get("crc32") != (zlib.crc32(canon.encode())
                                     & 0xFFFFFFFF):
            raise PlanError(
                f"plan {where}: CRC32 mismatch — the bytes were damaged "
                f"after writing")
        expect = hashlib.sha256(
            _SIGN_KEYS[fmt] + canon.encode()).hexdigest()
        if envelope.get("sha256") != expect:
            raise PlanError(
                f"plan {where}: signature mismatch — the file was "
                f"modified after signing")
        plan = body.get("plan")
        if not isinstance(plan, dict):
            raise PlanError(f"plan {where}: body carries no plan")
        known = {f.name for f in dataclasses.fields(cls)}
        kwargs = {k: plan[k] for k in sorted(plan) if k in known}
        try:
            return cls(**kwargs)
        except (TypeError, ValueError) as e:
            raise PlanError(f"plan {where}: invalid field values "
                            f"({e})") from e

    def save(self, path: str) -> str:
        """Atomic write (tmp + replace) of the signed envelope."""
        p = pathlib.Path(path)
        tmp = p.with_name(p.name + ".tmp")
        tmp.write_bytes(self.to_json())
        tmp.replace(p)
        return str(p)

    @classmethod
    def load(cls, path: str) -> "Plan":
        try:
            raw = pathlib.Path(path).read_bytes()
        except OSError as e:
            raise PlanError(f"plan {path}: unreadable ({e})") from e
        return cls.from_json(raw, where=str(path))

    def describe(self) -> str:
        """One human line (the ``/plan`` payload headline)."""
        mesh = f"dp{self.dp}tp{self.tp}pp{self.pp}"
        serve = (f"{self.replicas}r"
                 + (f"({self.prefill_workers}p/{self.decode_workers}d)"
                    if self.prefill_workers or self.decode_workers
                    else "") if self.replicas else "-")
        embed = (f"{self.embed_hbm_rows}rows/{self.embed_storage}"
                 if self.embed_hbm_rows else "-")
        return (f"mesh={mesh} sched={self.schedule} "
                f"remat={self.remat_policy} micro={self.microbatch} "
                f"gang={self.gang_size} serve={serve} embed={embed} "
                f"feasible={self.feasible}")
