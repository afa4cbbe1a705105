"""The chip peaks every utilisation and roofline share divides by.

One table, keyed by `device_kind` as JAX reports it, with its source. A
device that is not in the table is an error, never a default.
"""
from __future__ import annotations

_SOURCE_V5E = ("Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip")

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "hbm_bytes": 16e9, "source": _SOURCE_V5E},
    "TPU v5e": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                "hbm_bytes": 16e9, "source": _SOURCE_V5E},
}


def peaks(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks recorded for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}. Add a row with its source to "
            f"benchmark/lib/peaks.py") from None
