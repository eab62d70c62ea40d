"""Published peaks of one chip, keyed by the exact ``device_kind`` JAX
reports. A device that is not in the table is an error, never a default."""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
                  "16 GB HBM2e at 819 GB/s per chip",
    },
}


def peaks_of(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"benchmark/harness/peaks.py has no row for device_kind "
            f"{device_kind!r} (known: {sorted(PEAKS)}); a benchmark PR adds "
            f"it with its source")
    return PEAKS[device_kind]
