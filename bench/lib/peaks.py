"""Published peaks of the chips the benchmark runs on, keyed by the
`device_kind` JAX reports.  A device that is not here is an error, not a
default.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
393 TOP/s in int8, 16 GB of HBM at 819 GB/s.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
}


def peak(device_kind: str, what: str) -> float:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks on record for device kind "
                       f"{device_kind!r}; add them to bench/lib/peaks.py "
                       f"with their source")
    return PEAKS[device_kind][what]
