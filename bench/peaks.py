"""Published peaks of the chips the benchmark runs on, keyed by device_kind.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s, and
1,600 Gbit/s of chip-to-chip interconnect (ICI) per chip.

A device that is not in the table is an error, not a default: a
utilization over the wrong peak is a wrong number.
"""

from __future__ import annotations

_V5E = {
    "bf16_flops_per_s": 197e12,
    "int8_ops_per_s": 393e12,
    "hbm_bytes": 16e9,
    "hbm_bytes_per_s": 819e9,
    "ici_bits_per_s": 1600e9,
    "source": "Google Cloud documentation, TPU v5e",
}

PEAKS = {
    "TPU v5 lite": _V5E,  # what JAX reports as device_kind on a v5e chip
    "TPU v5e": _V5E,
}


def peak(device_kind: str) -> dict:
    """The peak table entry of ``device_kind``; KeyError if unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/peaks.py "
                       f"with their source") from None
