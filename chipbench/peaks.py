"""Published peaks per accelerator, keyed by JAX's ``device_kind``.

Every device-kind lookup of the benchmark goes through :func:`peaks`. A kind
that is not in the table is an error, never a default.
"""
from __future__ import annotations

# Source: Google Cloud documentation, "TPU v5e" (system architecture page):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud, TPU v5e",
    },
}


class UnknownDevice(KeyError):
    """The device kind has no row in :data:`PEAKS`."""


def peaks(device_kind: str) -> dict:
    """The row of ``device_kind``; raises :class:`UnknownDevice` otherwise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
