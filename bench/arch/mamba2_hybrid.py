"""A hybrid of Mamba-2 and grouped-query attention layers, each followed
by a SwiGLU MLP (GraniteMoeHybrid without experts: granite-4.0-h).

Weights, in the program's layout.  ``layer_types`` runs of equal kinds
are groups ``g0, g1, ...`` in order, every layer's leaves stacked on a
leading axis of the run's length L_g:

  embed (V, D), final_norm (D,)  (tied: the output projection is embed^T)
  a "mamba" group:
      ln (L_g, D), wz/wx (L_g, D, I), wB/wC (L_g, D, GN), wdt (L_g, D, H),
      conv_x (L_g, I, K), conv_B/conv_C (L_g, GN, K), conv_x_b (L_g, I),
      conv_B_b/conv_C_b (L_g, GN), dt_bias/A_log/D_skip (L_g, H),
      norm (L_g, I), wout (L_g, I, D),
      ln2 (L_g, D), w1 (L_g, D, F) up, w3 (L_g, D, F) gate, w2 (L_g, F, D);
  an "attention" group:
      ln1 (L_g, D), wq (L_g, D, Hq, hd), wk/wv (L_g, D, KV, hd),
      wo (L_g, Hq, hd, D), and the same MLP leaves.

I = H x P (the Mamba heads times their size), GN = groups x state size N.
z, x, B, C and dt together are the published ``in_proj``; ``w3`` and
``w1`` the two halves of ``input_linear``.

Training, per token (PaLM, arXiv:2204.02311, appendix B, with the
recurrence added):

    6 N + 12 L_attn d_attn S + 12 L_mamba H P N

N counts every parameter once; the second term is the attention layers'
scores and weighted sum over the full S x S square; the third the
recurrence's state update and readout (a multiply-add each per state
element), forward and backward, in its sequential form.

One decode step of the continuous batcher, for the live slots only, a
slot at position p (p tokens already in its cache):

    FLOPs = sum over live slots of  2 N_mm + 4 L_attn d_attn (p + 1)
                                    + L_mamba (4 H P N + 2 K (I + 2 GN))
    bytes = all weights once
          + sum over live slots of (p + 1) KV entries read
          + one new KV entry written per live slot
          + each live slot's SSM state and conv windows read and written

N_mm is the parameters of the matmuls (the mixers' projections, q/k/v/o,
the MLPs and the output projection over the vocabulary); a KV entry is
L_attn x 2 x kv_heads x head size values of the cache's type; a slot's SSM
state is L_mamba x H x P x N float32 values, its conv windows L_mamba x
(K - 1) x (I + 2 GN) values of the cache's type.  Slots that hold no
request, and the cache beyond each slot's position, are not counted.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Iterable, Tuple

import numpy as np

from ..flops import DTYPE_BYTES
from ..reference import mamba2_hybrid as reference
from ..weights import n_params

STATE_BYTES = 4   # the recurrent state stays float32 whatever the cache type


def program_config(c: Dict[str, Any]):
    from repro.models.config import ModelConfig

    d = dims(c)
    for key in ("attention_bias", "mamba_proj_bias"):
        if c.get(key):
            raise ValueError(f"{c['name']}: {key} is not supported")
    if c.get("num_local_experts"):
        raise ValueError(f"{c['name']}: experts are not supported")
    if d["H"] * d["P"] != c["mamba_expand"] * d["D"]:
        raise ValueError(f"{c['name']}: mamba heads x head size != expand x hidden")
    return ModelConfig(
        arch_id=c["name"], family="hybrid", n_layers=d["L"], d_model=d["D"],
        n_heads=d["Hq"], n_kv_heads=d["KV"], d_ff=d["F"], vocab_size=d["V"],
        head_dim=d["hd"], tie_embeddings=bool(c["tie_word_embeddings"]),
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        act=c["hidden_act"], ssm_state=d["N"], ssm_expand=c["mamba_expand"],
        ssm_head_dim=d["P"], ssm_conv=d["K"], ssm_chunk=c["mamba_chunk_size"],
        ssm_groups=d["G"], ssm_conv_bias=bool(c["mamba_conv_bias"]),
        layer_types=tuple(c["layer_types"]),
        embedding_multiplier=float(c["embedding_multiplier"]),
        residual_multiplier=float(c["residual_multiplier"]),
        attn_scale=float(c["attention_multiplier"]),
        logits_scaling=float(c["logits_scaling"]),
        rope=c["position_embedding_type"] != "nope", source=c["source"])


def dims(c: Dict[str, Any]) -> Dict[str, int]:
    Hq = c["num_attention_heads"]
    types = c["layer_types"]
    if len(types) != c["num_hidden_layers"]:
        raise ValueError(f"{c['name']}: {len(types)} layer_types for "
                         f"{c['num_hidden_layers']} layers")
    return {"D": c["hidden_size"], "F": c["shared_intermediate_size"], "Hq": Hq,
            "KV": c["num_key_value_heads"], "hd": c["hidden_size"] // Hq,
            "L": c["num_hidden_layers"], "V": c["vocab_size"],
            "La": types.count("attention"), "Lm": types.count("mamba"),
            "H": c["mamba_n_heads"], "P": c["mamba_d_head"],
            "N": c["mamba_d_state"], "G": c["mamba_n_groups"],
            "K": c["mamba_d_conv"]}


def _mlp(n: int, D: int, F: int) -> Dict[str, Tuple[int, ...]]:
    return {"ln2": (n, D), "w1": (n, D, F), "w2": (n, F, D), "w3": (n, D, F)}


def shapes(c: Dict[str, Any]) -> Dict[str, Any]:
    d = dims(c)
    D, F, V = d["D"], d["F"], d["V"]
    I, GN, K, H = d["H"] * d["P"], d["G"] * d["N"], d["K"], d["H"]
    tree: Dict[str, Any] = {"embed": (V, D), "final_norm": (D,)}
    for gi, (kind, n) in enumerate(reference.groups(c)):
        if kind == "mamba":
            layer = {"ln": (n, D), "wz": (n, D, I), "wx": (n, D, I),
                     "wB": (n, D, GN), "wC": (n, D, GN), "wdt": (n, D, H),
                     "conv_x": (n, I, K), "conv_B": (n, GN, K),
                     "conv_C": (n, GN, K), "conv_x_b": (n, I),
                     "conv_B_b": (n, GN), "conv_C_b": (n, GN),
                     "dt_bias": (n, H), "A_log": (n, H), "D_skip": (n, H),
                     "norm": (n, I), "wout": (n, I, D)}
        else:
            Hq, KV, hd = d["Hq"], d["KV"], d["hd"]
            layer = {"ln1": (n, D), "wq": (n, D, Hq, hd), "wk": (n, D, KV, hd),
                     "wv": (n, D, KV, hd), "wo": (n, Hq, hd, D)}
        tree[f"g{gi}"] = {**layer, **_mlp(n, D, F)}
    return tree


def init(name: str, shape: Tuple[int, ...], key, c: Dict[str, Any]):
    """As ``dense_gqa``: norm weights (``ln*``, ``norm``, ``final_norm``)
    1 + N(0, 0.02); each layer's output projections (``wo``, ``wout``,
    ``w2``) N(0, 0.02 / sqrt(2 L)); every other matrix N(0, 0.02), but the
    tied embedding N(0, 0.02 / embedding_multiplier), so that the residual
    stream starts at dense_gqa's N(0, 0.02): at N(0, 0.02) the scaled
    input outweighs every layer's branch, and through the tied output
    projection each greedy token is the one before it, at any precision,
    which no check of the served tokens can tell from a fault.
    Mamba-2's own defaults where the config gives none: the conv's
    weights and bias U[-1/sqrt(K), 1/sqrt(K)] (a depthwise conv's default,
    fan-in K; at N(0, 0.02) the recurrence's part of the logits is a
    millionth of them, too small for any check to see), ``A_log`` =
    log U[1, 16], and ``dt_bias`` the inverse softplus of a dt drawn
    log-uniform in [0.001, 0.1].  ``D_skip`` = 0.1, not Mamba-2's 1: at 1
    the skip outweighs the state's readout, and a slot that keeps the state
    of the request before it moves the logits about as much as the
    program's own rounding, so the check could pass a reset left out."""
    import jax
    import jax.numpy as jnp

    leaf = name.rsplit("/", 1)[-1]
    if leaf.startswith("conv_"):
        bound = 1.0 / math.sqrt(c["mamba_d_conv"])
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    if leaf == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if leaf == "D_skip":
        return jnp.full(shape, 0.1, jnp.float32)
    if leaf == "dt_bias":
        u = jax.random.uniform(key, shape, jnp.float32,
                               math.log(1e-3), math.log(1e-1))
        dt = jnp.exp(u)
        return dt + jnp.log(-jnp.expm1(-dt))
    z = jax.random.normal(key, shape, jnp.float32)
    if leaf == "embed":
        return z * (0.02 / c["embedding_multiplier"])
    if leaf in ("ln", "ln1", "ln2", "norm", "final_norm"):
        return 1.0 + 0.02 * z
    if leaf in ("wo", "wout", "w2"):
        return z * (0.02 / math.sqrt(2 * c["num_hidden_layers"]))
    return z * 0.02


def train_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    d = dims(c)
    return (6.0 * n_params(c) + 12.0 * d["La"] * d["Hq"] * d["hd"] * seq
            + 12.0 * d["Lm"] * d["H"] * d["P"] * d["N"])


def matmul_params(c: Dict[str, Any]) -> int:
    d = dims(c)
    D, F, V = d["D"], d["F"], d["V"]
    I, GN = d["H"] * d["P"], d["G"] * d["N"]
    mlp = 3 * D * F
    mamba = D * (2 * I + 2 * GN + d["H"]) + I * D + mlp
    attn = D * d["Hq"] * d["hd"] * 2 + D * d["KV"] * d["hd"] * 2 + mlp
    return d["Lm"] * mamba + d["La"] * attn + V * D


def kv_entry_bytes(c: Dict[str, Any]) -> int:
    d = dims(c)
    return d["La"] * 2 * d["KV"] * d["hd"] * DTYPE_BYTES[c["deployment"]["cache_dtype"]]


def ssm_slot_bytes(c: Dict[str, Any]) -> int:
    """One slot's SSM state and conv windows."""
    d = dims(c)
    I, GN = d["H"] * d["P"], d["G"] * d["N"]
    state = d["H"] * d["P"] * d["N"] * STATE_BYTES
    conv = (d["K"] - 1) * (I + 2 * GN) * DTYPE_BYTES[c["deployment"]["cache_dtype"]]
    return d["Lm"] * (state + conv)


def weight_bytes(c: Dict[str, Any]) -> int:
    return n_params(c) * DTYPE_BYTES[c["deployment"]["compute_dtype"]]


def decode_step(c: Dict[str, Any], positions: Iterable[int]) -> Dict[str, float]:
    """FLOPs and bytes of one batcher step whose live slots stand at
    ``positions``."""
    d = dims(c)
    pos = np.asarray(list(positions), np.float64)
    I, GN = d["H"] * d["P"], d["G"] * d["N"]
    att = 4.0 * d["La"] * d["Hq"] * d["hd"]
    ssm = d["Lm"] * (4.0 * d["H"] * d["P"] * d["N"] + 2.0 * d["K"] * (I + 2 * GN))
    kvb = kv_entry_bytes(c)
    n = len(pos)
    return {"flops": float(n * (2.0 * matmul_params(c) + ssm)
                           + att * np.sum(pos + 1)),
            "bytes": float(weight_bytes(c) + kvb * np.sum(pos + 1) + n * kvb
                           + n * 2 * ssm_slot_bytes(c))}
