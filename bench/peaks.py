"""Peaks of the chips the benchmark runs on, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.  The same
numbers as ``repro.launch.hlo_cost.PEAKS``, copied so that the benchmark owns
its yardstick.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peak(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a chip not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}; add them to bench/peaks.py") from None
