"""Operations and bytes a step needs, from the configuration's shapes alone.

Each architecture counts its own (``bench/arch/<arch>.py``, where the
formulas and their sources are); this module dispatches on the
configuration's ``"arch"`` and holds what every architecture shares: the
bytes of a value's type and the roofline's least time.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable

from . import arch

DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def train_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    """Operations of a training step's forward and backward passes per token
    of a ``seq``-long row."""
    return arch.of(c).train_flops_per_token(c, seq)


def matmul_params(c: Dict[str, Any]) -> int:
    """Parameters of the matmuls that one token's decode runs."""
    return arch.of(c).matmul_params(c)


def kv_entry_bytes(c: Dict[str, Any]) -> int:
    """Bytes that one position adds to a slot's cache."""
    return arch.of(c).kv_entry_bytes(c)


def decode_step(c: Dict[str, Any], positions: Iterable[int]) -> Dict[str, float]:
    """FLOPs and bytes of one batcher step whose live slots stand at
    ``positions``."""
    return arch.of(c).decode_step(c, positions)


def least_seconds(work: Dict[str, float], peak: Dict[str, float]) -> Dict[str, Any]:
    """The roofline's least time for ``work`` and which bound sets it."""
    compute = work["flops"] / peak["flops"]
    memory = work["bytes"] / peak["hbm_bw"]
    return {"seconds": max(compute, memory),
            "bound": "compute" if compute >= memory else "memory"}
