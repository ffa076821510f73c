"""Published peaks of the chips the benchmark may run on, by ``device_kind``.

A device that is not in the table is an error, never a default."""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM2e at
# 819 GB/s.  JAX reports the chip as "TPU v5 lite".
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


class UnknownDevice(RuntimeError):
    """The device is not a TPU of the peak table."""


def peak(kind: str) -> dict:
    """The row of the table for ``device_kind``, or :class:`UnknownDevice`."""
    try:
        return PEAKS[kind]
    except KeyError:
        raise UnknownDevice(
            f"device kind {kind!r} is not in the peak table "
            f"({sorted(PEAKS)}); a measurement needs a chip whose peaks "
            f"are known") from None


def require_chips(devices, chips: int) -> dict:
    """The peaks of the devices a cell runs on.  Raises where the platform
    is not ``tpu``, the kind is unknown or fewer than ``chips`` are there."""
    if not devices or devices[0].platform != "tpu":
        found = devices[0].platform if devices else "nothing"
        raise UnknownDevice(
            f"the benchmark measures on a TPU; JAX found {found!r}")
    row = peak(devices[0].device_kind)
    if len(devices) < chips:
        raise UnknownDevice(
            f"the cell needs {chips} chip(s); JAX found {len(devices)}")
    return row
