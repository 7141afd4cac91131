"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.  A device that is not in the table is an error: a share
of a peak must never be taken against a guessed one.

Source: Google Cloud documentation, "TPU v5e" (system architecture): one
v5e chip has 197 TFLOP/s in bf16, 393 TOP/s in int8 and 16 GB of HBM at
819 GB/s.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(f"chipbench: no published peaks for device kind "
                         f"{device_kind!r}; add them to chipbench/peaks.py "
                         f"with their source") from None
