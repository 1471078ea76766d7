"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` string JAX reports. A kind that is not here is an error:
no roofline or utilization is ever read against a guessed peak.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip.
"""
from __future__ import annotations

SOURCE = "Google Cloud documentation, TPU v5e system architecture"

_V5E = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_s": 819e9,
        "hbm_bytes": 16e9}

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
