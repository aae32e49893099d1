"""Published peaks of one chip, by jax `device_kind`. A device that is not
here is an error, never a default."""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at
    # 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect per chip.
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
    },
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks on record for device_kind {device_kind!r}; add it to "
            "benchmark/harness/peaks.py with its source")
    return PEAKS[device_kind]
