"""Published peaks of each accelerator the benchmark may run on.

Keyed by ``jax.Device.device_kind``.  A device that is not listed is an
error: a share of a peak needs the peak, and no default stands in for it.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' (system architecture: "
                  "197 TFLOP/s bf16, 16 GB HBM2 at 819 GB/s per chip)",
    },
}


def peak_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(f"no peak for device kind {device_kind!r}; known: "
                         f"{sorted(PEAKS)}") from None
