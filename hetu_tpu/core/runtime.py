"""The device and the compile cache, decided in one place.

Every entry point that measures or smokes on the chip (``chip_smoke.py``,
``benchmark/run.py``, ``tests/tpu_checks.py``) asks :func:`require_tpu` for the
device instead of guessing from strings, every process that compiles asks
:func:`compile_cache` for the persistent cache directory, and every Pallas
entry asks :func:`pallas_interpret` whether Mosaic or the interpreter runs
it — so a wrong backend is an error with a name, never a quiet fallback.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["NoTPUError", "device_info", "require_tpu", "compile_cache",
           "pallas_interpret"]

_CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


class NoTPUError(RuntimeError):
    """The default JAX backend is not a TPU."""


def device_info() -> dict:
    """``{"platform", "kind", "count"}`` exactly as JAX reports them."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu() -> dict:
    """:func:`device_info`, or :class:`NoTPUError` naming the platform JAX
    found when it is not ``tpu``."""
    info = device_info()
    if info["platform"] != "tpu":
        raise NoTPUError(
            f"this entry point needs a TPU; JAX found platform "
            f"{info['platform']!r} ({info['kind']}, {info['count']} "
            f"device(s)). Run it on the chip (one process per chip); the "
            f"CPU is for the test suite only.")
    return info


def compile_cache() -> str:
    """Point JAX's persistent compilation cache at one fixed place and
    return it.  Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it
    itself and nothing is touched; otherwise the cache is
    ``<checkout>/.jax_cache`` (git-ignored) — a fixed path, because a
    directory that moves between runs never hits."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def pallas_interpret() -> bool:
    """The default ``interpret=`` of every Pallas entry: ``False`` on a TPU
    (Mosaic compiles the kernel), ``True`` on the CPU (the test suite).
    Any other backend raises — it has neither Mosaic nor a reason to run
    the interpreter unasked."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise NoTPUError(
        f"Pallas TPU kernels run compiled on 'tpu' and interpreted on "
        f"'cpu'; the default backend is {backend!r}. Pass interpret= "
        f"explicitly to run them here.")
