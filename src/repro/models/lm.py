"""Decoder-only LM assembly: dense / MoE / SSM / hybrid families.

A model is a sequence of *block groups* — contiguous runs of identical
layer kinds — so ``lax.scan`` over stacked per-group parameters keeps
compile time O(#groups), not O(#layers), with ``jax.checkpoint`` (remat)
around each layer.  Kinds:

  attn    — GQA attention + gated MLP            (dense, vlm)
  swa     — same, sliding-window attention       (hybrid/serving variant)
  moe     — GQA attention + routed-expert FFN (+ optional shared experts)
  ssm     — Mamba-2 SSD mixer                    (attention-free)
  hybrid  — parallel attention + SSD heads, then MLP (hymba)

Decode ("serve") uses per-group caches: KV ring buffers for attention,
(state, conv) tuples for SSD.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.sharding import logical_constraint as lc
from . import layers as L
from .config import ModelConfig, PadPlan
from .params import LeafSpec

NEG_INF = -1e9


@dataclasses.dataclass(frozen=True)
class BlockGroup:
    kind: str          # attn | swa | moe | ssm | hybrid
    count: int
    window: int = 0    # >0 for swa kind


def block_groups(cfg: ModelConfig, *, serve_longctx: bool = False) -> List[BlockGroup]:
    """Static layer grouping for a config (DESIGN.md §4)."""
    if cfg.family == "ssm":
        return [BlockGroup("ssm", cfg.n_layers)]
    if cfg.family == "hybrid":
        groups: List[BlockGroup] = []
        kinds = ["hybrid_swa"] * cfg.n_layers
        for gi in cfg.global_layers:
            kinds[gi] = "hybrid"
        # long-context serving keeps SWA for the global layers too
        if serve_longctx:
            kinds = ["hybrid_swa"] * cfg.n_layers
        i = 0
        while i < cfg.n_layers:
            j = i
            while j < cfg.n_layers and kinds[j] == kinds[i]:
                j += 1
            groups.append(BlockGroup(
                kinds[i].replace("hybrid_swa", "hybrid_swa"), j - i,
                window=cfg.swa_window if kinds[i] == "hybrid_swa" else 0))
            i = j
        return groups
    kind = "moe" if cfg.n_experts else "attn"
    if serve_longctx:
        # dense/moe archs at 500k run the sliding-window serving variant
        return [BlockGroup(kind, cfg.n_layers, window=cfg.longctx_window)]
    if cfg.swa_window:
        return [BlockGroup(kind, cfg.n_layers, window=cfg.swa_window)]
    return [BlockGroup(kind, cfg.n_layers)]


# ---------------------------------------------------------------------------
# parameter descriptions


def _attn_desc(cfg: ModelConfig, plan: PadPlan) -> Dict[str, Any]:
    D, hd = cfg.d_model, cfg.hd
    d = {
        "ln1": LeafSpec((D,), ("d_model",), "ones"),
        "wq": LeafSpec((D, plan.q_pad, hd), ("d_model", "heads", None),
                       f"normal:{0.02}"),
        "wk": LeafSpec((D, plan.n_kv_orig, hd), ("d_model", "kv_orig", None)),
        "wv": LeafSpec((D, plan.n_kv_orig, hd), ("d_model", "kv_orig", None)),
        "wo": LeafSpec((plan.q_pad, hd, D), ("heads", None, "d_model"),
                       f"normal:{0.02 / math.sqrt(2 * cfg.n_layers)}"),
    }
    if cfg.qkv_bias:
        d["bq"] = LeafSpec((plan.q_pad, hd), ("heads", None), "zeros")
        d["bk"] = LeafSpec((plan.n_kv_orig, hd), ("kv_orig", None), "zeros")
        d["bv"] = LeafSpec((plan.n_kv_orig, hd), ("kv_orig", None), "zeros")
    if cfg.qk_norm:
        d["q_norm"] = LeafSpec((hd,), (None,), "ones")
        d["k_norm"] = LeafSpec((hd,), (None,), "ones")
    return d


def _mlp_desc(cfg: ModelConfig, d_ff: Optional[int] = None) -> Dict[str, Any]:
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    d = {
        "ln2": LeafSpec((D,), ("d_model",), "ones"),
        "w1": LeafSpec((D, F), ("d_model", "ff")),
        "w2": LeafSpec((F, D), ("ff", "d_model"),
                       f"normal:{0.02 / math.sqrt(2 * cfg.n_layers)}"),
    }
    if cfg.act == "silu":
        d["w3"] = LeafSpec((D, F), ("d_model", "ff"))
    return d


def _moe_desc(cfg: ModelConfig, plan: PadPlan) -> Dict[str, Any]:
    D, F, E = cfg.d_model, cfg.moe_d_ff, plan.experts_pad
    d = {
        "ln2": LeafSpec((D,), ("d_model",), "ones"),
        "router": LeafSpec((D, E), ("d_model", None), "normal:0.02"),
        "w1": LeafSpec((E, D, F), ("experts", "d_model", None)),
        "w3": LeafSpec((E, D, F), ("experts", "d_model", None)),
        "w2": LeafSpec((E, F, D), ("experts", None, "d_model"),
                       f"normal:{0.02 / math.sqrt(2 * cfg.n_layers)}"),
    }
    if cfg.n_shared_experts:
        Fs = cfg.shared_d_ff
        d["shared"] = {
            "w1": LeafSpec((D, Fs), ("d_model", "ff")),
            "w3": LeafSpec((D, Fs), ("d_model", "ff")),
            "w2": LeafSpec((Fs, D), ("ff", "d_model"),
                           f"normal:{0.02 / math.sqrt(2 * cfg.n_layers)}"),
            "gate": LeafSpec((D,), ("d_model",), "zeros"),
        }
    return d


def _ssm_desc(cfg: ModelConfig, plan: PadPlan) -> Dict[str, Any]:
    D = cfg.d_model
    Hp = plan.ssm_heads_pad
    P = cfg.ssm_head_dim
    GN = cfg.ssm_groups * cfg.ssm_state
    K = cfg.ssm_conv
    inner = Hp * P
    return {
        "ln": LeafSpec((D,), ("d_model",), "ones"),
        "wz": LeafSpec((D, inner), ("d_model", "inner")),
        "wx": LeafSpec((D, inner), ("d_model", "inner")),
        "wB": LeafSpec((D, GN), ("d_model", None)),
        "wC": LeafSpec((D, GN), ("d_model", None)),
        "wdt": LeafSpec((D, Hp), ("d_model", "ssm_heads")),
        "dt_bias": LeafSpec((Hp,), ("ssm_heads",), "dt_bias"),
        "A_log": LeafSpec((Hp,), ("ssm_heads",), "a_log"),
        "D_skip": LeafSpec((Hp,), ("ssm_heads",), "ones"),
        "conv_x": LeafSpec((inner, K), ("inner", None), "normal:0.5"),
        "conv_B": LeafSpec((GN, K), (None, None), "normal:0.5"),
        "conv_C": LeafSpec((GN, K), (None, None), "normal:0.5"),
        "norm": LeafSpec((inner,), ("inner",), "ones"),
        "wout": LeafSpec((inner, D), ("inner", "d_model"),
                         f"normal:{0.02 / math.sqrt(2 * cfg.n_layers)}"),
    }


def _block_desc(cfg: ModelConfig, plan: PadPlan, kind: str) -> Dict[str, Any]:
    base_kind = kind.replace("_swa", "").replace("hybrid_swa", "hybrid")
    if kind.startswith("hybrid"):
        return {
            **_attn_desc(cfg, plan),
            "ssm": _ssm_desc(cfg, plan),
            "attn_fuse_norm": LeafSpec((cfg.d_model,), ("d_model",), "ones"),
            "ssm_fuse_norm": LeafSpec((cfg.d_model,), ("d_model",), "ones"),
            **_mlp_desc(cfg),
        }
    if kind == "ssm":
        return _ssm_desc(cfg, plan)
    if kind == "moe":
        return {**_attn_desc(cfg, plan), **_moe_desc(cfg, plan)}
    return {**_attn_desc(cfg, plan), **_mlp_desc(cfg)}  # attn / swa


def _stack(desc: Any, n: int) -> Any:
    return jax.tree.map(
        lambda s: LeafSpec((n,) + s.shape, ("layers",) + s.axes, s.init, s.dtype),
        desc, is_leaf=lambda x: isinstance(x, LeafSpec))


def describe_lm(cfg: ModelConfig, plan: PadPlan, *,
                serve_longctx: bool = False) -> Dict[str, Any]:
    groups = block_groups(cfg, serve_longctx=serve_longctx)
    desc: Dict[str, Any] = {
        "embed": LeafSpec((plan.vocab_pad, cfg.d_model), ("vocab", "d_model")),
        "final_norm": LeafSpec((cfg.d_model,), ("d_model",), "ones"),
    }
    if not cfg.tie_embeddings:
        desc["unembed"] = LeafSpec((cfg.d_model, plan.vocab_pad),
                                   ("d_model", "vocab"))
    for gi, g in enumerate(groups):
        desc[f"g{gi}"] = _stack(_block_desc(cfg, plan, g.kind), g.count)
    return desc


# ---------------------------------------------------------------------------
# forward blocks


def _project_qkv(cfg, plan, p, h, positions):
    B, S, D = h.shape
    hd = cfg.hd
    q = jnp.einsum("bsd,dqh->bsqh", h, p["wq"].astype(h.dtype))
    k = jnp.einsum("bsd,dkh->bskh", h, p["wk"].astype(h.dtype))
    v = jnp.einsum("bsd,dkh->bskh", h, p["wv"].astype(h.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(h.dtype)
        k = k + p["bk"].astype(h.dtype)
        v = v + p["bv"].astype(h.dtype)
    if cfg.qk_norm:
        q = L.rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = L.rmsnorm(k, p["k_norm"], cfg.norm_eps)
    q = L.rope(q, positions, cfg.rope_theta)
    k = L.rope(k, positions, cfg.rope_theta)
    q = lc(q, "batch", "seq", "heads", None)
    return q, k, v


def _attn_out(cfg, plan, p, attn, B, S):
    out = jnp.einsum("bskgh,kghd->bsd",
                     attn,
                     p["wo"].astype(attn.dtype).reshape(
                         plan.kv_pad, plan.group, cfg.hd, cfg.d_model))
    return lc(out, "batch", "seq_res", None)


def _maybe_gather_seq(h: jax.Array) -> jax.Array:
    """Megatron-SP schedule: when the residual stream is seq-sharded
    (rules seq_res->model), gather h ONCE before the qkv projections so
    GSPMD doesn't re-gather q/k/v per head shard (EXPERIMENTS §Perf)."""
    from ..parallel import sharding as shd

    rules = shd.current_rules()
    if rules and rules.get("seq_res") == "model" and rules.get("sp_gather_h", True):
        return lc(h, "batch", None, None)
    return h


def attn_block(cfg: ModelConfig, plan: PadPlan, p: Dict[str, Any],
               x: jax.Array, positions: jax.Array, *,
               window: int, q_chunk: int) -> jax.Array:
    B, S, D = x.shape
    h = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
    h = _maybe_gather_seq(h)
    q, k, v = _project_qkv(cfg, plan, p, h, positions)
    q = q.reshape(B, S, plan.kv_pad, plan.group, cfg.hd)
    k = L.duplicate_kv(k, plan)
    v = L.duplicate_kv(v, plan)
    hm = jnp.asarray(plan.head_mask(), x.dtype).reshape(plan.kv_pad, plan.group, 1)
    attn = L.attention(q, k, v, pos_q=positions, pos_kv=positions,
                       causal=True, window=window, q_chunk=q_chunk,
                       head_mask=hm)
    return x + _attn_out(cfg, plan, p, attn, B, S)


def mlp_block(cfg: ModelConfig, p: Dict[str, Any], x: jax.Array) -> jax.Array:
    h = L.rmsnorm(x, p["ln2"], cfg.norm_eps)
    return x + L.gated_mlp(h, p["w1"].astype(x.dtype),
                           p.get("w3") if p.get("w3") is None else p["w3"].astype(x.dtype),
                           p["w2"].astype(x.dtype), cfg.act)


def moe_block(cfg: ModelConfig, plan: PadPlan, p: Dict[str, Any],
              x: jax.Array, n_groups: int) -> Tuple[jax.Array, jax.Array]:
    B, S, D = x.shape
    h = L.rmsnorm(x, p["ln2"], cfg.norm_eps)
    hg = h.reshape(n_groups, (B * S) // n_groups, D)
    hg = lc(hg, "groups", None, None)
    out, stats = L.moe_ffn(
        hg, p["router"].astype(x.dtype),
        p["w1"].astype(x.dtype), p["w3"].astype(x.dtype), p["w2"].astype(x.dtype),
        n_experts=cfg.n_experts, top_k=cfg.top_k,
        capacity_factor=cfg.capacity_factor, act=cfg.act)
    out = out.reshape(B, S, D)
    if cfg.n_shared_experts:
        sp = p["shared"]
        shared = L.gated_mlp(h, sp["w1"].astype(x.dtype), sp["w3"].astype(x.dtype),
                             sp["w2"].astype(x.dtype), cfg.act)
        gate = jax.nn.sigmoid(
            jnp.einsum("bsd,d->bs", h.astype(jnp.float32), sp["gate"]))[..., None]
        out = out + shared * gate.astype(x.dtype)
    return x + out, stats.aux_loss


def ssm_block(cfg: ModelConfig, plan: PadPlan, p: Dict[str, Any],
              x: jax.Array) -> jax.Array:
    y, _ = ssm_mixer(cfg, plan, p, L.rmsnorm(x, p["ln"], cfg.norm_eps))
    return x + y


def ssm_mixer(cfg: ModelConfig, plan: PadPlan, p: Dict[str, Any],
              h: jax.Array, cache: Optional[Dict[str, jax.Array]] = None
              ) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Full mamba-2 mixer on normed input h (B,S,D).  With ``cache``
    (decode) S must be 1 and the conv/state caches are advanced."""
    B, S, D = h.shape
    Hp, P = plan.ssm_heads_pad, cfg.ssm_head_dim
    GN = cfg.ssm_groups * cfg.ssm_state
    z = h @ p["wz"].astype(h.dtype)
    xs = h @ p["wx"].astype(h.dtype)
    Bs = h @ p["wB"].astype(h.dtype)
    Cs = h @ p["wC"].astype(h.dtype)
    dt_raw = h @ p["wdt"].astype(h.dtype)
    z = lc(z, "batch", "seq", "inner")
    xs = lc(xs, "batch", "seq", "inner")

    new_cache: Optional[Dict[str, jax.Array]] = None
    if cache is None:
        xs, _ = L.causal_conv1d(xs, p["conv_x"].astype(h.dtype))
        Bs, _ = L.causal_conv1d(Bs, p["conv_B"].astype(h.dtype))
        Cs, _ = L.causal_conv1d(Cs, p["conv_C"].astype(h.dtype))
    else:
        xs, cx = L.causal_conv1d(xs, p["conv_x"].astype(h.dtype), cache["conv_x"])
        Bs, cb = L.causal_conv1d(Bs, p["conv_B"].astype(h.dtype), cache["conv_B"])
        Cs, cc = L.causal_conv1d(Cs, p["conv_C"].astype(h.dtype), cache["conv_C"])
        new_cache = {"conv_x": cx, "conv_B": cb, "conv_C": cc}
    xs, Bs, Cs = jax.nn.silu(xs), jax.nn.silu(Bs), jax.nn.silu(Cs)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])

    xh = xs.reshape(B, S, Hp, P)
    Bh = Bs.reshape(B, S, cfg.ssm_groups, cfg.ssm_state)
    Chh = Cs.reshape(B, S, cfg.ssm_groups, cfg.ssm_state)
    mask = jnp.asarray(_ssm_head_mask(cfg, plan), h.dtype)

    if cache is None:
        y, _ = L.ssd_chunked(xh, dt, p["A_log"], Bh, Chh, p["D_skip"],
                             chunk=min(cfg.ssm_chunk, S))
    else:
        y1, new_state = L.ssd_decode_step(
            xh[:, 0], dt[:, 0], p["A_log"], Bh[:, 0], Chh[:, 0],
            p["D_skip"], cache["state"])
        new_cache["state"] = new_state
        y = y1[:, None]
    y = y * mask[None, None, :, None]
    y = y.reshape(B, S, Hp * P)
    y = L.rmsnorm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    out = y @ p["wout"].astype(h.dtype)
    return lc(out, "batch", "seq", None), new_cache


def _ssm_head_mask(cfg: ModelConfig, plan: PadPlan) -> np.ndarray:
    m = np.zeros((plan.ssm_heads_pad,), np.float32)
    m[: cfg.ssm_heads] = 1.0
    return m


def hybrid_block(cfg: ModelConfig, plan: PadPlan, p: Dict[str, Any],
                 x: jax.Array, positions: jax.Array, *,
                 window: int, q_chunk: int) -> jax.Array:
    """Hymba: parallel attention + SSD heads, mean-fused, then MLP."""
    B, S, D = x.shape
    h = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
    q, k, v = _project_qkv(cfg, plan, p, h, positions)
    q = q.reshape(B, S, plan.kv_pad, plan.group, cfg.hd)
    k = L.duplicate_kv(k, plan)
    v = L.duplicate_kv(v, plan)
    hm = jnp.asarray(plan.head_mask(), x.dtype).reshape(plan.kv_pad, plan.group, 1)
    attn = L.attention(q, k, v, pos_q=positions, pos_kv=positions,
                       causal=True, window=window, q_chunk=q_chunk, head_mask=hm)
    a_out = _attn_out(cfg, plan, p, attn, B, S)
    s_out, _ = ssm_mixer(cfg, plan, p["ssm"], h)
    fused = 0.5 * (L.rmsnorm(a_out, p["attn_fuse_norm"], cfg.norm_eps)
                   + L.rmsnorm(s_out, p["ssm_fuse_norm"], cfg.norm_eps))
    x = x + fused
    return mlp_block(cfg, p, x)


# ---------------------------------------------------------------------------
# full forward / loss


def forward(cfg: ModelConfig, plan: PadPlan, params: Dict[str, Any],
            tokens: jax.Array, *, q_chunk: int = 0,
            compute_dtype: Any = jnp.float32,
            n_token_groups: int = 1,
            serve_longctx: bool = False,
            remat: bool = True, scan_unroll: int = 1) -> Tuple[jax.Array, jax.Array]:
    """tokens (B,S) -> (hidden (B,S,D), total_aux_loss)."""
    B, S = tokens.shape
    groups = block_groups(cfg, serve_longctx=serve_longctx)
    x = jnp.take(params["embed"].astype(compute_dtype), tokens, axis=0)
    x = lc(x, "batch", "seq_res", None)
    positions = jnp.arange(S, dtype=jnp.int32)
    aux_total = jnp.zeros((), jnp.float32)

    for gi, g in enumerate(groups):
        gp = params[f"g{gi}"]

        def layer_fn(x, pl, g=g):
            if g.kind == "ssm":
                return ssm_block(cfg, plan, pl, x), jnp.zeros((), jnp.float32)
            if g.kind in ("hybrid", "hybrid_swa"):
                return (hybrid_block(cfg, plan, pl, x, positions,
                                     window=g.window, q_chunk=q_chunk),
                        jnp.zeros((), jnp.float32))
            x2 = attn_block(cfg, plan, pl, x, positions,
                            window=g.window, q_chunk=q_chunk)
            if g.kind == "moe":
                x3, aux = moe_block(cfg, plan, pl, x2, n_token_groups)
                return x3, aux
            return mlp_block(cfg, pl, x2), jnp.zeros((), jnp.float32)

        if remat:
            layer_fn = jax.checkpoint(layer_fn)

        def scan_fn(x, pl):
            x2, aux = layer_fn(x, pl)
            return x2, aux

        x, auxes = jax.lax.scan(scan_fn, x, gp, unroll=scan_unroll)
        aux_total = aux_total + jnp.sum(auxes)

    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x, aux_total


def logits_from_hidden(cfg: ModelConfig, plan: PadPlan, params, x: jax.Array
                       ) -> jax.Array:
    unembed = params.get("unembed")
    if unembed is None:
        unembed = params["embed"].T
    logits = jnp.einsum("bsd,dv->bsv", x, unembed.astype(x.dtype))
    logits = lc(logits, "batch", "seq", "vocab")
    if plan.vocab_pad > cfg.vocab_size:
        pad_bias = jnp.where(jnp.arange(plan.vocab_pad) < cfg.vocab_size,
                             0.0, NEG_INF).astype(logits.dtype)
        logits = logits + pad_bias
    return logits


def loss_fn(cfg: ModelConfig, plan: PadPlan, params,
            batch: Dict[str, jax.Array], *, q_chunk: int = 0,
            compute_dtype: Any = jnp.float32, n_token_groups: int = 1,
            loss_chunk: int = 0, remat: bool = True,
            scan_unroll: int = 1) -> jax.Array:
    """Mean next-token cross-entropy + MoE aux, seq-chunked over the vocab
    projection so full (B,S,V) logits are never materialised."""
    tokens, labels = batch["tokens"], batch["labels"]
    x, aux = forward(cfg, plan, params, tokens, q_chunk=q_chunk,
                     compute_dtype=compute_dtype,
                     n_token_groups=n_token_groups, remat=remat,
                     scan_unroll=scan_unroll)
    B, S, D = x.shape
    unembed = params.get("unembed")
    if unembed is None:
        unembed = params["embed"].T
    unembed = unembed.astype(x.dtype)
    pad_bias = (jnp.where(jnp.arange(plan.vocab_pad) < cfg.vocab_size,
                          0.0, NEG_INF).astype(jnp.float32)
                if plan.vocab_pad > cfg.vocab_size else None)

    def chunk_nll(xc, yc):
        lg = jnp.einsum("btd,dv->btv", xc, unembed,
                        preferred_element_type=jnp.float32)
        lg = lc(lg, "batch", "seq", "vocab")
        if pad_bias is not None:
            lg = lg + pad_bias
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, yc[..., None].astype(jnp.int32),
                                   axis=-1)[..., 0]
        return jnp.sum(lse - gold)

    if loss_chunk and S > loss_chunk and S % loss_chunk == 0:
        nc = S // loss_chunk
        xr = x.reshape(B, nc, loss_chunk, D)
        yr = labels.reshape(B, nc, loss_chunk)
        chunk_nll_ckpt = jax.checkpoint(chunk_nll)  # logits recomputed in bwd

        def body(tot, i):
            return tot + chunk_nll_ckpt(xr[:, i], yr[:, i]), None

        total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                                jnp.arange(nc))
    else:
        total = chunk_nll(x, labels)
    nll = total / (B * S)
    return nll + cfg.router_aux_coef * aux


# ---------------------------------------------------------------------------
# serving (decode) path


def init_cache_desc(cfg: ModelConfig, plan: PadPlan, *, batch: int,
                    max_seq: int, serve_longctx: bool = False,
                    dtype: Any = jnp.float32) -> Dict[str, Any]:
    """LeafSpec tree for the decode cache (window-bounded for SWA groups).
    KV/conv caches use ``dtype`` (bf16 in production); the SSD state stays
    float32 — it is a long-lived accumulator."""
    groups = block_groups(cfg, serve_longctx=serve_longctx)
    hd = cfg.hd
    desc: Dict[str, Any] = {}
    for gi, g in enumerate(groups):
        n = g.count
        gdesc: Dict[str, Any] = {}
        if g.kind in ("attn", "swa", "moe", "hybrid", "hybrid_swa"):
            span = min(max_seq, g.window) if g.window else max_seq
            gdesc["k"] = LeafSpec((n, batch, span, plan.kv_pad, hd),
                                  ("layers", "batch", None, "kv_heads", None),
                                  "zeros", dtype)
            gdesc["v"] = LeafSpec((n, batch, span, plan.kv_pad, hd),
                                  ("layers", "batch", None, "kv_heads", None),
                                  "zeros", dtype)
        if g.kind in ("ssm", "hybrid", "hybrid_swa"):
            Hp, P = plan.ssm_heads_pad, cfg.ssm_head_dim
            GN = cfg.ssm_groups * cfg.ssm_state
            K = cfg.ssm_conv
            gdesc["ssm"] = {
                "state": LeafSpec((n, batch, Hp, P, cfg.ssm_state),
                                  ("layers", "batch", "ssm_heads", None, None),
                                  "zeros", jnp.float32),
                "conv_x": LeafSpec((n, batch, K - 1, Hp * P),
                                   ("layers", "batch", None, "inner"), "zeros", dtype),
                "conv_B": LeafSpec((n, batch, K - 1, GN),
                                   ("layers", "batch", None, None), "zeros", dtype),
                "conv_C": LeafSpec((n, batch, K - 1, GN),
                                   ("layers", "batch", None, None), "zeros", dtype),
            }
        desc[f"g{gi}"] = gdesc
    return desc


def _write_index(pos, span: int, window: int):
    """The cache entry a token at ``pos`` is written to: a ring buffer's
    ``pos % span`` for a window, else ``pos`` (the last entry once full)."""
    return jnp.mod(pos, span) if window else jnp.minimum(pos, span - 1)


def _decode_attn_read(cfg, plan, p, x, kcache, vcache, pos, window):
    """One-token attention against a read-only (possibly ring-buffer)
    cache.  kcache/vcache: (B, span, KVp, hd).  The token attends to itself
    through its own k/v; the entry they will overwrite (``_write_index``)
    is masked, so nothing reads back an entry not written yet.  Returns
    (out, k, v), k/v (B, 1, KVp, hd) for the caller to write."""
    B = x.shape[0]
    span = kcache.shape[1]
    positions = jnp.full((1,), pos, dtype=jnp.int32)
    h = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
    q, k, v = _project_qkv(cfg, plan, p, h, positions)
    q = q.reshape(B, 1, plan.kv_pad, plan.group, cfg.hd)
    k = L.duplicate_kv(k, plan)
    v = L.duplicate_kv(v, plan)
    idx = jnp.arange(span, dtype=jnp.int32)
    if window:
        # ring buffer: slot s holds absolute position p iff p % span == s
        base = (pos // span) * span
        pos_kv = jnp.where(idx <= jnp.mod(pos, span), base + idx,
                           base - span + idx)
    else:
        pos_kv = idx
    pos_kv = jnp.where(idx == _write_index(pos, span, window), -1, pos_kv)
    hm = jnp.asarray(plan.head_mask(), x.dtype).reshape(plan.kv_pad, plan.group, 1)
    attn = L.decode_attention(q, kcache, vcache, k, v, pos_q=positions,
                              pos_kv=pos_kv, window=window, head_mask=hm)
    out = _attn_out(cfg, plan, p, attn, B, 1)
    return out, k, v


def _decode_attn(cfg, plan, p, x, kcache, vcache, pos, window):
    """``_decode_attn_read`` with the token's k/v written into the caches:
    returns (out, new_kcache, new_vcache)."""
    out, k, v = _decode_attn_read(cfg, plan, p, x, kcache, vcache, pos, window)
    at = _write_index(pos, kcache.shape[1], window)
    return (out, jax.lax.dynamic_update_slice_in_dim(kcache, k, at, axis=1),
            jax.lax.dynamic_update_slice_in_dim(vcache, v, at, axis=1))


def serve_step(cfg: ModelConfig, plan: PadPlan, params,
               cache: Dict[str, Any], tokens: jax.Array, pos: jax.Array,
               *, compute_dtype: Any = jnp.float32,
               serve_longctx: bool = False, n_token_groups: int = 1,
               scan_unroll: int = 1) -> Tuple[jax.Array, Dict[str, Any]]:
    """One decode step: tokens (B,1) + cache @ pos -> (logits (B,1,V), cache).

    The attention cache is read-only inside the layer loop (layer ``i``
    indexed, not passed as scan xs/ys): each layer's new k/v leave the
    loop as small ys and are written once per leaf after it, so a donated
    cache is updated in place, not copied or relaid out.  SSM state and
    conv caches, small and rewritten whole each step, ride the scan."""
    groups = block_groups(cfg, serve_longctx=serve_longctx)
    x = jnp.take(params["embed"].astype(compute_dtype), tokens, axis=0)
    new_cache: Dict[str, Any] = {}

    for gi, g in enumerate(groups):
        gp = params[f"g{gi}"]
        gc = cache[f"g{gi}"]

        def layer_fn(x, packed, g=g, gc=gc):
            pl, i, ssm_c = packed
            if g.kind == "ssm":
                h = L.rmsnorm(x, pl["ln"], cfg.norm_eps)
                y, ssm_cache = ssm_mixer(cfg, plan, pl, h, cache=ssm_c)
                return x + y, (None, None, ssm_cache)
            h = L.rmsnorm(x, pl["ln1"], cfg.norm_eps)
            kc = jax.lax.dynamic_index_in_dim(gc["k"], i, keepdims=False)
            vc = jax.lax.dynamic_index_in_dim(gc["v"], i, keepdims=False)
            a_out, k, v = _decode_attn_read(cfg, plan, pl, x, kc, vc, pos,
                                            g.window)
            if g.kind in ("hybrid", "hybrid_swa"):
                s_out, ssm_cache = ssm_mixer(cfg, plan, pl["ssm"], h,
                                             cache=ssm_c)
                fused = 0.5 * (L.rmsnorm(a_out, pl["attn_fuse_norm"], cfg.norm_eps)
                               + L.rmsnorm(s_out, pl["ssm_fuse_norm"], cfg.norm_eps))
                x = x + fused
                return mlp_block(cfg, pl, x), (k, v, ssm_cache)
            x = x + a_out
            if g.kind == "moe":
                x, _ = moe_block(cfg, plan, pl, x, n_token_groups)
                return x, (k, v, None)
            return mlp_block(cfg, pl, x), (k, v, None)

        x, (ks, vs, ssm_cache) = jax.lax.scan(
            layer_fn, x, (gp, jnp.arange(g.count), gc.get("ssm")),
            unroll=scan_unroll)
        ngc: Dict[str, Any] = {}
        if "k" in gc:
            at = _write_index(pos, gc["k"].shape[2], g.window)
            ngc["k"] = jax.lax.dynamic_update_slice_in_dim(gc["k"], ks, at, axis=2)
            ngc["v"] = jax.lax.dynamic_update_slice_in_dim(gc["v"], vs, at, axis=2)
        if ssm_cache is not None:
            ngc["ssm"] = ssm_cache
        new_cache[f"g{gi}"] = ngc

    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = logits_from_hidden(cfg, plan, params, x)
    return logits, new_cache
