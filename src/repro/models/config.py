"""Model configuration + TP padding planner.

``ModelConfig`` captures every assigned architecture (see repro.configs).
``plan_padding`` maps a config onto a tensor-parallel shard count: head
counts, vocab and expert counts are padded to shardable multiples.  Pad
slots are masked to exact zero contribution (head_mask / logit mask /
router mask), so the padded model computes the *same function* as the
unpadded one — the padding waste is visible, by design, in the roofline
MODEL_FLOPS/HLO_FLOPs ratio (DESIGN.md §4).

Head plan: original GQA group size g0 = q0/kv0 must be an integer.  We
duplicate each original KV head ``spo`` times (in compute, not in params)
so kv_pad = shard-aligned, and arrange padded Q slots so that q slot
``s`` attends kv slot ``s // group`` — locality-preserving, so GSPMD
never needs a cross-shard gather inside attention.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    arch_id: str
    family: str  # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None  # default d_model // n_heads
    qkv_bias: bool = False
    qk_norm: bool = False
    tie_embeddings: bool = False
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-5
    act: str = "silu"  # silu (gated) | gelu (plain MLP)
    # --- MoE
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0            # per-expert hidden width
    n_shared_experts: int = 0
    shared_d_ff: int = 0
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.001
    # --- SSM (Mamba-2 SSD)
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_conv: int = 4
    ssm_chunk: int = 256
    ssm_groups: int = 1
    ssm_conv_bias: bool = False
    # --- per-layer pattern ("mamba" | "attention" per layer; empty: the
    # family decides) and scalar multipliers (GraniteMoeHybrid); each
    # default adds no operation
    layer_types: Tuple[str, ...] = ()
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attn_scale: float = 0.0      # softmax scale; 0 = 1/sqrt(head_dim)
    logits_scaling: float = 1.0  # logits divided by it
    rope: bool = True            # False: no positional encoding (NoPE)
    # --- hybrid / attention variants
    swa_window: int = 0          # 0 = full attention everywhere
    global_layers: Tuple[int, ...] = ()  # layer indices using full attn when swa_window>0
    # --- long-context serving variant (dense archs at 500k)
    longctx_window: int = 4096
    # --- encoder-decoder (audio)
    n_enc_layers: int = 0
    enc_seq: int = 0             # frontend-stub sequence length (e.g. 1500 frames)
    # --- provenance
    source: str = ""

    @property
    def hd(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // self.n_heads if self.n_heads else 0

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def ssm_heads(self) -> int:
        return (self.ssm_expand * self.d_model) // self.ssm_head_dim

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model


def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclasses.dataclass(frozen=True)
class PadPlan:
    shard: int                   # model-axis size this plan targets
    q_pad: int
    kv_pad: int
    group: int                   # q_pad == kv_pad * group
    spo: int                     # kv duplication factor (slots per original)
    n_kv_orig: int
    q_slot_of_orig: Tuple[int, ...]   # len q0: padded slot index per orig q head
    vocab_pad: int
    experts_pad: int
    ssm_heads_pad: int

    def head_mask(self) -> np.ndarray:
        """(q_pad,) 1.0 for live q slots, 0.0 for pad slots."""
        m = np.zeros((self.q_pad,), dtype=np.float32)
        for s in self.q_slot_of_orig:
            m[s] = 1.0
        return m

    def kv_dup_index(self) -> np.ndarray:
        """(kv_pad,) original kv head index per padded kv slot (clipped)."""
        idx = np.minimum(np.arange(self.kv_pad) // max(self.spo, 1),
                         self.n_kv_orig - 1)
        return idx.astype(np.int32)


def plan_padding(cfg: ModelConfig, shard: int) -> PadPlan:
    vocab_pad = _ceil_to(cfg.vocab_size, max(shard, 1))
    experts_pad = _ceil_to(cfg.n_experts, shard) if cfg.n_experts else 0
    ssm_heads_pad = _ceil_to(cfg.ssm_heads, shard) if cfg.ssm_state else 0

    if cfg.family == "ssm" or cfg.n_heads == 0:
        return PadPlan(shard=shard, q_pad=0, kv_pad=0, group=1, spo=1,
                       n_kv_orig=0, q_slot_of_orig=(),
                       vocab_pad=vocab_pad, experts_pad=experts_pad,
                       ssm_heads_pad=ssm_heads_pad)

    q0, kv0 = cfg.n_heads, cfg.n_kv_heads
    if q0 % kv0 != 0:
        raise ValueError(f"{cfg.arch_id}: n_heads {q0} not divisible by kv {kv0}")
    g0 = q0 // kv0
    kv_pad = _ceil_to(kv0, shard) if kv0 >= shard else shard
    spo = kv_pad // kv0  # duplication factor (floor; leftover slots are dead)
    group = max(1, math.ceil(g0 / max(spo, 1)))
    q_pad = kv_pad * group
    # place orig q head i (parent p=i//g0, rank r=i%g0) at slot p*spo*group + r
    slots = tuple(int((i // g0) * spo * group + (i % g0)) for i in range(q0))
    assert len(set(slots)) == q0 and max(slots) < q_pad, (cfg.arch_id, slots, q_pad)
    # consistency: slot s uses kv slot s//group which duplicates orig kv
    for i in range(q0):
        assert min(slots[i] // group // max(spo, 1), kv0 - 1) == i // g0, (
            cfg.arch_id, i, slots[i])
    return PadPlan(shard=shard, q_pad=q_pad, kv_pad=kv_pad, group=group, spo=spo,
                   n_kv_orig=kv0, q_slot_of_orig=slots, vocab_pad=vocab_pad,
                   experts_pad=experts_pad, ssm_heads_pad=ssm_heads_pad)
