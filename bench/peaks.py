"""Published peaks of the accelerators the benchmark runs on.

Keyed by ``jax.Device.device_kind``.  A device that is not in the table is
an error, never a default: a roofline or utilization against the wrong
peak is a wrong number.
"""
from __future__ import annotations

from typing import Dict

#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
#: int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
#: interconnect per chip.
_V5E = {
    "bf16_flops": 197e12,
    "int8_ops": 393e12,
    "hbm_bytes_per_s": 819e9,
    "hbm_bytes": 16e9,
    "ici_bytes_per_s": 1600e9 / 8,
    "source": "Google Cloud documentation, TPU v5e",
}

PEAKS: Dict[str, Dict] = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> Dict:
    """The peak table of ``device_kind``; raises for an unknown device."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
