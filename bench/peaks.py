"""Published peak rates of the chips the benchmark runs on, keyed by
``jax.Device.device_kind``.  A device that is not listed is an error."""
from __future__ import annotations

_V5E = {
    "bf16_flops_per_s": 197e12,
    "hbm_bytes_per_s": 819e9,
    "hbm_bytes": 16e9,
    "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
              "16 GB HBM2 at 819 GB/s per chip",
}

PEAKS = {
    "TPU v5 lite": _V5E,
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
