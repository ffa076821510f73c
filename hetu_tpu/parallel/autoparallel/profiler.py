"""Hardware cost profiler with a persistent cache.

Reference: ``HetuSimulator`` micro-benchmarks ops and caches execution times
in /tmp/hetu_cached_exetime.bin (profiler.py:609-877), and ``NCCLProfiler``
measures collectives over device subsets (profiler.py:390).  TPU-native:
measure MXU matmul throughput and per-axis collective bandwidth on the live
mesh, persist to a JSON cache keyed by device kind, and calibrate a
``ClusterSpec`` the cost models consume.
"""

from __future__ import annotations

import json
import pathlib
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from hetu_tpu.parallel.autoparallel.cost_model import ClusterSpec

__all__ = ["CostProfiler"]

_DEFAULT_CACHE = pathlib.Path.home() / ".cache" / "hetu_tpu_profile.json"


def _timed(fn, *args, iters: int = 5, chain: int = 8) -> float:
    """Per-call wall time of fn.

    Each sample times a CHAIN of data-dependent calls with ONE trailing
    scalar transfer and divides, so the per-dispatch host cost is amortized;
    the min over samples drops stall outliers.  fn must map its first arg's
    shape to an output reusable as that arg (all profiler probes do).
    """
    out = fn(*args)
    float(jnp.asarray(out).ravel()[0])  # compile + sync
    chained = out.shape == jnp.shape(args[0]) and out.dtype == args[0].dtype
    if not chained:
        chain = 1
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        a = args[0]
        for _i in range(chain):
            a = fn(a, *args[1:]) if chained else fn(*args)
        float(jnp.asarray(a).ravel()[0])
        times.append((time.perf_counter() - t0) / chain)
    return float(np.min(times))


class CostProfiler:
    def __init__(self, cache_path: str | pathlib.Path | None = None):
        self.cache_path = pathlib.Path(cache_path or _DEFAULT_CACHE)
        self._cache = {}
        if self.cache_path.exists():
            try:
                self._cache = json.loads(self.cache_path.read_text())
            except (json.JSONDecodeError, OSError):
                self._cache = {}

    # bump when probe methodology changes, else old caches silently serve
    # measurements taken with the previous (overhead-dominated) probes
    _PROBE_VERSION = "v2"

    def _key(self, what: str) -> str:
        dev = jax.devices()[0]
        return (f"{getattr(dev, 'device_kind', dev.platform)}/{what}/"
                f"{self._PROBE_VERSION}")

    def _memo(self, what: str, compute):
        key = self._key(what)
        if key not in self._cache:
            self._cache[key] = compute()
            self.cache_path.parent.mkdir(parents=True, exist_ok=True)
            self.cache_path.write_text(json.dumps(self._cache, indent=1))
        return self._cache[key]

    def matmul_flops(self, n: int = 2048) -> float:
        """Sustained bf16 matmul flop/s on one device."""

        def compute():
            a = jnp.ones((n, n), jnp.bfloat16)

            # enough matmuls per dispatch that launch overhead is noise
            # next to the compute; the CPU runs the same probe shape at
            # ~1000x less throughput, so scale down there
            loops = 4 if jax.devices()[0].platform == "cpu" else 512

            @jax.jit
            def mm(a):
                # returns a's shape/dtype so _timed can chain calls
                # data-dependently and amortize the host-sync cost
                return jax.lax.fori_loop(
                    0, loops, lambda i, x: (x @ a).astype(jnp.bfloat16) * 0.5,
                    a)

            dt = _timed(mm, a)
            return loops * 2 * n**3 / dt

        return self._memo(f"matmul{n}", compute)

    def collective_bandwidth(self, mesh, axis: str,
                             nbytes: int = 1 << 22) -> float:
        """Effective allreduce (psum) bytes/s over one mesh axis."""
        size = mesh.shape[axis]
        if size <= 1:
            return float("inf")

        def compute():
            from jax.sharding import PartitionSpec as P

            n = nbytes // 4
            x = jnp.ones((size, n), jnp.float32)

            @jax.jit
            @partial(jax.shard_map, mesh=mesh, in_specs=P(axis),
                     out_specs=P(axis))
            def ar(x):
                return jax.lax.psum(x, axis) * 0.5

            dt = _timed(ar, x)
            # ring allreduce volume per device: 2(n-1)/n * bytes
            return 2 * (size - 1) / size * nbytes / dt

        return self._memo(f"allreduce/{axis}{size}/{nbytes}", compute)

    def calibrate(self, mesh=None, *, hbm_bytes: float | None = None,
                  mfu_assumption: float = 0.4) -> ClusterSpec:
        """Build a ClusterSpec from measurements (reference: profilers feed
        the simulator feeding the searchers, §3.5).

        ``matmul_flops`` measures *sustained* throughput, but
        ``ClusterSpec.peak_flops`` is consumed by ``TimeCostModel`` which
        re-discounts it by its own ``mfu`` factor — so the measurement is
        divided by ``mfu_assumption`` (the utilization the benchmark matmul
        is assumed to have achieved; keep it equal to TimeCostModel's mfu
        so the discounts cancel back to the measured sustained rate)."""
        flops = self.matmul_flops()
        n_devices = len(jax.devices()) if mesh is None else mesh.size
        ici = 4.5e10
        if mesh is not None:
            for ax in mesh.axis_names:
                if mesh.shape[ax] > 1:
                    bw = self.collective_bandwidth(mesh, ax)
                    if np.isfinite(bw):
                        ici = bw
                        break
        if hbm_bytes is None:
            # what the backend reports for the device; the CPU backend
            # reports nothing, and there the value only feeds test plans
            stats = jax.devices()[0].memory_stats() or {}
            hbm_bytes = stats.get("bytes_limit", 4e9)
        return ClusterSpec(
            n_devices=n_devices,
            hbm_bytes=hbm_bytes,
            peak_flops=flops / mfu_assumption,
            ici_bandwidth=ici,
        )
