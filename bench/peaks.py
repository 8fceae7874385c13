"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
393 TOP/s in int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of
chip-to-chip interconnect.  A device kind missing here is an error,
never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,     # FLOP/s
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 200e9,  # 1,600 Gbit/s
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
