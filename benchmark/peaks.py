"""Published peaks of one chip, keyed by the exact `device_kind` jax reports.

Source: Google Cloud TPU documentation, the system-architecture page of each
generation ("TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s per chip;
"TPU v4": 275 TFLOP/s, 1,200 GB/s; "TPU v5p": 459 TFLOP/s, 2,765 GB/s;
"TPU v6e": 918 TFLOP/s, 1,640 GB/s). A v5e chip reports "TPU v5 lite". A
device that is not in the table is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v4": {"flops_bf16": 275e12, "hbm_bytes_per_s": 1200e9},
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9},
    "TPU v5e": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9},
    "TPU v5": {"flops_bf16": 459e12, "hbm_bytes_per_s": 2765e9},
    "TPU v5p": {"flops_bf16": 459e12, "hbm_bytes_per_s": 2765e9},
    "TPU v6 lite": {"flops_bf16": 918e12, "hbm_bytes_per_s": 1640e9},
    "TPU v6e": {"flops_bf16": 918e12, "hbm_bytes_per_s": 1640e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise ValueError(
            f"no peaks on record for device_kind {device_kind!r}; add it to "
            f"benchmark/peaks.py with its source (known: {sorted(PEAKS)})"
        )
    return PEAKS[device_kind]


def roofline_seconds(flops: float, nbytes: float, device_kind: str) -> tuple[float, str]:
    """Least time the chip could take for `flops` and `nbytes`, and which of
    the two peaks bounds it."""
    p = peaks(device_kind)
    t_c, t_m = flops / p["flops_bf16"], nbytes / p["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
