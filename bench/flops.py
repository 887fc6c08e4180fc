"""Operations and bytes a step needs, from the configuration's shapes alone.

Training, per token (PaLM, arXiv:2204.02311, appendix B):

    6 N + 12 L d_attn S

N counts every parameter once; with tied embeddings the one table serves
as the output projection, whose matmul the 6 N covers.  d_attn is heads x
head size, S the sequence length; the second term is the attention scores
and their weighted sum, forward and backward, over the full S x S square
(PaLM's convention).  Recomputation (remat) is not counted.

One decode step of the continuous batcher, for the live slots only, a
slot at position p (p tokens already in its cache):

    FLOPs = sum over live slots of  2 N_mm + 4 L d_attn (p + 1)
    bytes = all weights once
          + sum over live slots of (p + 1) KV entries read
          + one new KV entry written per live slot

N_mm is the parameters of the matmuls (q/k/v/o, the MLP, and the output
projection over the vocabulary); a KV entry is L x 2 x kv_heads x head
size values of the cache's type.  Weights are counted once per step: the
batch shares them.  Slots that hold no request, and the cache beyond each
slot's position, are work the algorithm does not need and are not counted.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable

from .weights import dims, n_params

DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def train_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    d = dims(c)
    return 6.0 * n_params(c) + 12.0 * d["L"] * d["H"] * d["hd"] * seq


def matmul_params(c: Dict[str, Any]) -> int:
    d = dims(c)
    D, F, H, KV, L, V, hd = (d[k] for k in ("D", "F", "H", "KV", "L", "V", "hd"))
    per_layer = D * H * hd * 2 + D * KV * hd * 2 + 3 * D * F
    return L * per_layer + V * D


def kv_entry_bytes(c: Dict[str, Any]) -> int:
    d = dims(c)
    return d["L"] * 2 * d["KV"] * d["hd"] * DTYPE_BYTES[c["deployment"]["cache_dtype"]]


def weight_bytes(c: Dict[str, Any]) -> int:
    return n_params(c) * DTYPE_BYTES[c["deployment"]["compute_dtype"]]


def decode_step(c: Dict[str, Any], positions: Iterable[int]) -> Dict[str, float]:
    """FLOPs and bytes of one batcher step whose live slots stand at
    ``positions``."""
    d = dims(c)
    pos = list(positions)
    att = 4.0 * d["L"] * d["H"] * d["hd"]
    kvb = kv_entry_bytes(c)
    return {"flops": sum(2.0 * matmul_params(c) + att * (p + 1) for p in pos),
            "bytes": float(weight_bytes(c) + sum((p + 1) * kvb for p in pos)
                           + len(pos) * kvb)}


def least_seconds(work: Dict[str, float], peak: Dict[str, float]) -> Dict[str, Any]:
    """The roofline's least time for ``work`` and which bound sets it."""
    compute = work["flops"] / peak["flops"]
    memory = work["bytes"] / peak["hbm_bw"]
    return {"seconds": max(compute, memory),
            "bound": "compute" if compute >= memory else "memory"}
