"""Published peaks per chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
inter-chip interconnect per chip. JAX names the chip "TPU v5 lite".
A device that is not here is an error, never a default.
"""
from __future__ import annotations

from typing import Dict

_V5E = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9, "ici_bytes_per_s": 1600e9 / 8,
        "source": "Google Cloud documentation, TPU v5e"}

PEAKS: Dict[str, Dict] = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peak(device_kind: str) -> Dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
