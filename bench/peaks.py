"""Published peaks of each accelerator the benchmark may run on, keyed by
``jax.Device.device_kind``.  A device that is not here is an error: a share
of a guessed peak is no measurement.

TPU v5e: 197 TFLOP/s bf16 and 819 GB/s HBM bandwidth per chip, from Google
Cloud's "TPU v5e" documentation (cloud.google.com/tpu/docs/v5e).  JAX names
the chip "TPU v5 lite".
"""
from __future__ import annotations

from typing import Dict

_V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, TPU v5e"}

PEAKS: Dict[str, Dict] = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks(device_kind: str) -> Dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
