"""Published peaks of one chip, keyed by ``jax.Device.device_kind``.

TPU v5e (device kind "TPU v5 lite"): Google Cloud documentation, "TPU v5e"
system architecture page: 197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GiB of
HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect.  A float32
matmul at JAX's default precision takes one bf16 pass on this chip, so the
bf16 peak bounds the float32 programs measured here too.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "int8_ops": 393e12, "hbm_bytes": 16 * 2**30,
                    "hbm_bw": 819e9, "ici_bw": 200e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table of one chip kind; an unknown kind is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peak table for device kind {device_kind!r}; "
                       f"known kinds: {sorted(PEAKS)}")
    return PEAKS[device_kind]
