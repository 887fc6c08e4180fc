"""A dense decoder with grouped-query attention (Llama, Qwen2, SmolLM).

Weights, in the program's layout, every layer's leaves stacked on a
leading axis of ``num_hidden_layers``:

  embed (V, D), final_norm (D,), [unembed (D, V) when not tied],
  g0: ln1 (L, D), wq (L, D, H, hd), wk/wv (L, D, KV, hd), wo (L, H, hd, D),
      [bq (L, H, hd), bk/bv (L, KV, hd)], ln2 (L, D),
      w1 (L, D, F) up, w3 (L, D, F) gate, w2 (L, F, D) down.

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

import math
from typing import Any, Dict, Iterable, Tuple

from ..flops import DTYPE_BYTES
from ..reference import decoder as reference  # noqa: F401
from ..weights import n_params


def program_config(c: Dict[str, Any]):
    from repro.models.config import ModelConfig

    H = c["num_attention_heads"]
    return ModelConfig(
        arch_id=c["name"], family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=H,
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"],
        head_dim=c.get("head_dim") or c["hidden_size"] // H,
        qkv_bias=bool(c.get("attention_bias")),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        act=c["hidden_act"], source=c["source"])


def dims(c: Dict[str, Any]) -> Dict[str, int]:
    H = c["num_attention_heads"]
    return {"D": c["hidden_size"], "F": c["intermediate_size"], "H": H,
            "KV": c["num_key_value_heads"], "L": c["num_hidden_layers"],
            "V": c["vocab_size"], "hd": c.get("head_dim") or c["hidden_size"] // H}


def shapes(c: Dict[str, Any]) -> Dict[str, Any]:
    d = dims(c)
    D, F, H, KV, L, V, hd = (d[k] for k in ("D", "F", "H", "KV", "L", "V", "hd"))
    layer = {"ln1": (L, D), "wq": (L, D, H, hd), "wk": (L, D, KV, hd),
             "wv": (L, D, KV, hd), "wo": (L, H, hd, D), "ln2": (L, D),
             "w1": (L, D, F), "w2": (L, F, D), "w3": (L, D, F)}
    if c.get("attention_bias"):
        layer.update(bq=(L, H, hd), bk=(L, KV, hd), bv=(L, KV, hd))
    tree = {"embed": (V, D), "final_norm": (D,), "g0": layer}
    if not c["tie_word_embeddings"]:
        tree["unembed"] = (D, V)
    return tree


def init(name: str, shape: Tuple[int, ...], key, c: Dict[str, Any]):
    """Norm weights 1 + N(0, 0.02); each layer's output projections
    N(0, 0.02 / sqrt(2 L)); every other leaf N(0, 0.02)."""
    import jax
    import jax.numpy as jnp

    z = jax.random.normal(key, shape, jnp.float32)
    leaf = name.rsplit("/", 1)[-1]
    if leaf in ("ln1", "ln2", "final_norm"):
        return 1.0 + 0.02 * z
    if leaf in ("wo", "w2"):
        return z * (0.02 / math.sqrt(2 * c["num_hidden_layers"]))
    return z * 0.02


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
