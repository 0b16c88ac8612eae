"""Published peaks of each accelerator the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A device that is not listed is an error.

TPU v5e (JAX's "TPU v5 lite"): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of
HBM at 819 GB/s -- Google Cloud documentation, "TPU v5e".
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to chipbench/peaks.py "
                       f"with their source") from None
