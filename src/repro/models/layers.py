"""Neural-net building blocks (the paper's Table-1 "NN building blocks"
row, grown to 2026): RMSNorm, RoPE, padded GQA attention (full / chunked
/ sliding-window / decode), gated MLP, sort-based dropped-token MoE, and
the Mamba-2 SSD mixer with chunked scan + O(1) decode.

All functions are pure jnp (the Pallas TPU kernels in repro.kernels are
drop-in replacements for the hot paths and are validated against these).
Softmax/normalization accumulate in float32 regardless of compute dtype.
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..parallel.sharding import logical_constraint as lc
from .config import ModelConfig, PadPlan

# ---------------------------------------------------------------------------
# norms / rope / mlp


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w.astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, n, Dh); positions: (S,) int32."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]  # (S, half)
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    # broadcast over any head-like dims between S and Dh
    while cos.ndim < x.ndim:
        cos = cos[..., None, :, :] if False else jnp.expand_dims(cos, -2)
        sin = jnp.expand_dims(sin, -2)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def gated_mlp(x: jax.Array, w1: jax.Array, w3: jax.Array, w2: jax.Array,
              act: str = "silu") -> jax.Array:
    """SwiGLU: (x@w1)*silu_or_gelu(x@w3) @ w2; if w3 is None, plain MLP."""
    a = jax.nn.silu if act == "silu" else jax.nn.gelu
    h = x @ w1
    h = lc(h, "batch", "seq", "ff")
    if w3 is not None:
        g = x @ w3
        g = lc(g, "batch", "seq", "ff")
        h = a(g) * h
    else:
        h = a(h)
    out = h @ w2
    return lc(out, "batch", "seq_res", None)


# ---------------------------------------------------------------------------
# attention (padded-GQA layout: q (B,S,KVp,G,Dh), kv (B,T,KVp,Dh))


def _mask_bias(pos_q: jax.Array, pos_kv: jax.Array, causal: bool,
               window: int) -> jax.Array:
    """(Sq, Skv) additive bias in f32: 0 allowed, -inf masked."""
    ok = pos_kv[None, :] >= 0  # ring-buffer slots not yet written sit at p<0
    ok = jnp.broadcast_to(ok, (pos_q.shape[0], pos_kv.shape[0]))
    if causal:
        ok &= pos_kv[None, :] <= pos_q[:, None]
    if window > 0:
        ok &= pos_kv[None, :] > (pos_q[:, None] - window)
    return jnp.where(ok, 0.0, -jnp.inf).astype(jnp.float32)


def _attn_block(q: jax.Array, k: jax.Array, v: jax.Array,
                bias: jax.Array, head_mask: Optional[jax.Array]) -> jax.Array:
    """q (B,Sq,KV,G,D), k/v (B,Skv,KV,D), bias (Sq,Skv) -> (B,Sq,KV,G,D)."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bskgd,btkd->bsktg", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = s + bias[None, :, None, :, None]
    p = jax.nn.softmax(s, axis=3)
    # rows that are fully masked (e.g. pre-fill positions in a decode cache)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    o = jnp.einsum("bsktg,btkd->bskgd", p.astype(v.dtype), v)
    if head_mask is not None:
        o = o * head_mask  # (KV, G) broadcast: zero out pad q slots
    return o


def attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *,
    pos_q: jax.Array, pos_kv: jax.Array,
    causal: bool = True,
    window: int = 0,
    q_chunk: int = 0,
    head_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Padded-GQA attention.

    q: (B, Sq, KVp, G, Dh); k, v: (B, Skv, KVp, Dh).
    pos_q (Sq,), pos_kv (Skv,) absolute positions (mask arithmetic).
    window > 0 = sliding-window attention.
    q_chunk > 0 = memory-efficient chunked path (scan over query blocks);
    with a window it also *slices* the kv stream so FLOPs are O(S*window).
    head_mask: (KVp, G) zeros out padded q slots exactly.
    """
    B, Sq, KV, G, Dh = q.shape
    if q_chunk <= 0 or Sq <= q_chunk or Sq % q_chunk != 0:
        # indivisible sequences (e.g. whisper's 1500 encoder frames) take
        # the one-shot path; chunking is a memory optimisation only
        bias = _mask_bias(pos_q, pos_kv, causal, window)
        return _attn_block(q, k, v, bias, head_mask)
    n_chunks = Sq // q_chunk

    if window > 0 and window % q_chunk == 0 and k.shape[1] == Sq:
        # sliding-window: slice only the kv band each chunk needs
        pad = window
        kp = jnp.pad(k, ((0, 0), (pad, 0), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (pad, 0), (0, 0), (0, 0)))
        pos_kv_p = jnp.concatenate(
            [jnp.full((pad,), -10**9, dtype=pos_kv.dtype), pos_kv])

        @jax.checkpoint  # flash-attention semantics: recompute scores in bwd
        def chunk_body(i):
            qs = jax.lax.dynamic_slice_in_dim(q, i * q_chunk, q_chunk, axis=1)
            ks = jax.lax.dynamic_slice_in_dim(kp, i * q_chunk, window + q_chunk, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(vp, i * q_chunk, window + q_chunk, axis=1)
            pq = jax.lax.dynamic_slice_in_dim(pos_q, i * q_chunk, q_chunk)
            pk = jax.lax.dynamic_slice_in_dim(pos_kv_p, i * q_chunk, window + q_chunk)
            bias = _mask_bias(pq, pk, causal, window)
            return _attn_block(qs, ks, vs, bias, head_mask)

        _, outs = jax.lax.scan(lambda c, i: (c, chunk_body(i)), None,
                               jnp.arange(n_chunks))
        out = jnp.moveaxis(outs, 0, 1).reshape(B, Sq, KV, G, Dh)
        return out

    @jax.checkpoint  # scores never live past the chunk, fwd or bwd
    def chunk_body(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * q_chunk, q_chunk, axis=1)
        pq = jax.lax.dynamic_slice_in_dim(pos_q, i * q_chunk, q_chunk)
        bias = _mask_bias(pq, pos_kv, causal, window)
        return _attn_block(qs, k, v, bias, head_mask)

    _, outs = jax.lax.scan(lambda c, i: (c, chunk_body(i)), None,
                           jnp.arange(n_chunks))
    return jnp.moveaxis(outs, 0, 1).reshape(B, Sq, KV, G, Dh)


def decode_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    k_new: jax.Array, v_new: jax.Array,
    *,
    pos_q: jax.Array, pos_kv: jax.Array,
    window: int = 0,
    head_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """One query token against a read-only cache and against itself.

    q: (B, 1, KVp, G, Dh); k, v: (B, T, KVp, Dh), the cache, masked by
    pos_kv as ``attention`` masks it (causal); k_new, v_new: (B, 1, KVp,
    Dh), the token's own, always attended.  The cache's scores and the
    token's own share one softmax, so the cache is never concatenated."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    bias = _mask_bias(pos_q, pos_kv, True, window)
    s = jnp.einsum("bskgd,btkd->bsktg", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = s + bias[None, :, None, :, None]
    s_self = jnp.einsum("bskgd,bskd->bskg", q, k_new,
                        preferred_element_type=jnp.float32) * scale
    m = jnp.maximum(jnp.max(s, axis=3), s_self)  # finite: the self-term
    p = jnp.exp(s - m[:, :, :, None, :])
    p_self = jnp.exp(s_self - m)
    denom = jnp.sum(p, axis=3) + p_self
    o = (jnp.einsum("bsktg,btkd->bskgd", p.astype(v.dtype), v)
         + p_self[..., None].astype(v.dtype) * v_new[:, :, :, None, :])
    o = o / denom[..., None].astype(o.dtype)
    if head_mask is not None:
        o = o * head_mask
    return o


def duplicate_kv(kv: jax.Array, plan: PadPlan) -> jax.Array:
    """(B,S,kv0,Dh) -> (B,S,kv_pad,Dh) by slot-duplication (compute-side,
    so the parameter count stays faithful to the original architecture)."""
    if plan.kv_pad == plan.n_kv_orig:
        return kv
    idx = jnp.asarray(plan.kv_dup_index())
    out = jnp.take(kv, idx, axis=2)
    return lc(out, "batch", "seq", "kv_heads", None)


# ---------------------------------------------------------------------------
# MoE: sort-based dropped-token dispatch (GShard-style capacity, grouped)


class MoEStats(NamedTuple):
    aux_loss: jax.Array
    frac_dropped: jax.Array


def moe_ffn(
    x: jax.Array,                   # (Gr, T, D) token groups (data-sharded)
    router_w: jax.Array,            # (D, Epad)
    w1: jax.Array, w3: jax.Array, w2: jax.Array,  # (Epad, D, F), (Epad, D, F), (Epad, F, D)
    *,
    n_experts: int,                 # real expert count (<= Epad)
    top_k: int,
    capacity_factor: float,
    act: str = "silu",
) -> Tuple[jax.Array, MoEStats]:
    Gr, T, D = x.shape
    Epad, _, F = w1.shape
    K = top_k
    C = max(1, int(math.ceil(T * K / n_experts * capacity_factor)))

    logits = jnp.einsum("gtd,de->gte", x, router_w,
                        preferred_element_type=jnp.float32)
    if Epad > n_experts:
        pad_bias = jnp.where(jnp.arange(Epad) < n_experts, 0.0, -jnp.inf)
        logits = logits + pad_bias
    probs = jax.nn.softmax(logits, axis=-1)                    # (Gr,T,Epad)
    gate_vals, e_idx = jax.lax.top_k(probs, K)                 # (Gr,T,K)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # --- aux load-balance loss (Switch): E * sum_e f_e * P_e
    pe = jnp.mean(probs, axis=(0, 1))                          # (Epad,)
    onehot_top1 = jax.nn.one_hot(e_idx[..., 0], Epad, dtype=jnp.float32)
    fe = jnp.mean(onehot_top1, axis=(0, 1))
    aux = n_experts * jnp.sum(fe * pe)

    # --- per-group sort by expert; rank within expert; capacity drop
    flat_e = e_idx.reshape(Gr, T * K)
    flat_t = jnp.broadcast_to(jnp.arange(T)[:, None], (T, K)).reshape(T * K)
    flat_w = gate_vals.reshape(Gr, T * K)

    order = jnp.argsort(flat_e, axis=1)                        # (Gr, T*K)
    se = jnp.take_along_axis(flat_e, order, axis=1)
    st = flat_t[order]
    sw = jnp.take_along_axis(flat_w, order, axis=1)
    first = jax.vmap(lambda row: jnp.searchsorted(row, jnp.arange(Epad)))(se)
    rank = jnp.arange(T * K)[None, :] - jnp.take_along_axis(first, se, axis=1)
    keep = rank < C
    slot = jnp.where(keep, se * C + rank, Epad * C)            # dropped -> overflow row

    # token index per (expert, capacity) slot; -1 = empty
    slot_to_tok = jnp.full((Gr, Epad * C + 1), -1, dtype=jnp.int32)
    slot_to_tok = jax.vmap(lambda s2t, sl, t: s2t.at[sl].set(t))(
        slot_to_tok, slot, jnp.broadcast_to(st, slot.shape).astype(jnp.int32))
    slot_to_tok = slot_to_tok[:, :-1]                          # (Gr, Epad*C)

    gathered = jnp.where(
        slot_to_tok[..., None] >= 0,
        jnp.take_along_axis(
            x, jnp.maximum(slot_to_tok, 0)[..., None], axis=1),
        0.0).reshape(Gr, Epad, C, D)
    gathered = lc(gathered, "groups", "experts", None, None)

    h = jnp.einsum("gecd,edf->gecf", gathered, w1)
    if w3 is not None:
        g = jnp.einsum("gecd,edf->gecf", gathered, w3)
        afn = jax.nn.silu if act == "silu" else jax.nn.gelu
        h = afn(g) * h
    else:
        h = (jax.nn.silu if act == "silu" else jax.nn.gelu)(h)
    y_e = jnp.einsum("gecf,efd->gecd", h, w2)                  # (Gr,Epad,C,D)
    y_e = lc(y_e, "groups", "experts", None, None)

    # --- combine: scatter-add weighted expert outputs back to tokens
    y_flat = y_e.reshape(Gr, Epad * C, D)
    w_slot = jnp.zeros((Gr, Epad * C + 1), dtype=jnp.float32)
    w_slot = jax.vmap(lambda ws, sl, w: ws.at[sl].set(w))(
        w_slot, slot, jnp.where(keep, sw, 0.0))
    w_slot = w_slot[:, :-1]
    contrib = y_flat * w_slot[..., None].astype(y_flat.dtype)
    out = jax.vmap(
        lambda o, t, c: o.at[jnp.maximum(t, 0)].add(
            jnp.where(t[:, None] >= 0, c, 0.0)))(
        jnp.zeros((Gr, T, D), dtype=x.dtype), slot_to_tok, contrib)
    out = lc(out, "groups", None, None)

    dropped = 1.0 - jnp.sum(jnp.where(keep, 1.0, 0.0)) / (Gr * T * K)
    return out, MoEStats(aux_loss=aux, frac_dropped=dropped)


# ---------------------------------------------------------------------------
# Mamba-2 SSD (state-space duality): chunked train scan + O(1) decode


def causal_conv1d(x: jax.Array, w: jax.Array,
                  cache: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Depthwise causal conv.  x (B,S,Cch), w (Cch,K).
    cache (B,K-1,Cch) for decode; returns (y, new_cache)."""
    B, S, Cch = x.shape
    K = w.shape[1]
    if cache is not None:
        win = jnp.concatenate([cache, x], axis=1)      # (B, K-1+S, C)
        new_cache = win[:, -(K - 1):, :]
        xp = win
    else:
        new_cache = None
        xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    y = jax.lax.conv_general_dilated(
        xp, w.T[:, None, :],                            # (K,1,C) WIO
        window_strides=(1,), padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=Cch)
    return y, new_cache


def ssd_chunked(
    x: jax.Array,        # (B,S,H,P)
    dt: jax.Array,       # (B,S,H) post-softplus
    A_log: jax.Array,    # (H,)
    B_: jax.Array,       # (B,S,G,N)
    C_: jax.Array,       # (B,S,G,N)
    D: jax.Array,        # (H,)
    *,
    chunk: int,
    initial_state: Optional[jax.Array] = None,  # (B,H,P,N)
) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan (Dao & Gu 2024): intra-chunk quadratic attention-
    like term + inter-chunk recurrent state pass.  Returns (y, final_state).
    """
    Bb, S, H, Pp = x.shape
    G, N = B_.shape[2], B_.shape[3]
    if S % chunk != 0:  # shrink to the largest divisor (correctness first)
        chunk = next(d for d in range(min(chunk, S), 0, -1) if S % d == 0)
    NC, Q = S // chunk, chunk
    rep = H // G

    a = -jnp.exp(A_log.astype(jnp.float32))              # (H,)
    dA = dt.astype(jnp.float32) * a                       # (B,S,H)
    dAc = dA.reshape(Bb, NC, Q, H)
    xc = x.reshape(Bb, NC, Q, H, Pp)
    dtc = dt.reshape(Bb, NC, Q, H).astype(jnp.float32)
    Bh = jnp.repeat(B_, rep, axis=2).reshape(Bb, NC, Q, H, N).astype(jnp.float32)
    Ch = jnp.repeat(C_, rep, axis=2).reshape(Bb, NC, Q, H, N).astype(jnp.float32)

    cs = jnp.cumsum(dAc, axis=2)                          # (B,NC,Q,H)
    seg = cs[:, :, :, None, :] - cs[:, :, None, :, :]     # (B,NC,Q,T,H)
    tri = jnp.tril(jnp.ones((Q, Q), dtype=bool))
    L = jnp.where(tri[None, None, :, :, None], jnp.exp(seg), 0.0)

    att = jnp.einsum("bcqhn,bcthn->bcqth", Ch, Bh) * L * dtc[:, :, None, :, :]
    xf = xc.astype(jnp.float32)
    y_intra = jnp.einsum("bcqth,bcthp->bcqhp", att, xf)

    # chunk state contributions: S_c = sum_t exp(cs_end - cs_t) dt_t B_t x_t
    decay_to_end = jnp.exp(cs[:, :, -1:, :] - cs)         # (B,NC,Q,H)
    Sc = jnp.einsum("bcthn,bcth,bcthp->bchpn",
                    Bh, dtc * decay_to_end, xf)           # (B,NC,H,P,N)
    chunk_decay = jnp.exp(cs[:, :, -1, :])                # (B,NC,H)

    h0 = (initial_state.astype(jnp.float32) if initial_state is not None
          else jnp.zeros((Bb, H, Pp, N), jnp.float32))

    def scan_fn(h, inputs):
        sc, cd = inputs                                   # (B,H,P,N), (B,H)
        h_new = h * cd[:, :, None, None] + sc
        return h_new, h                                   # emit state BEFORE chunk

    (h_final, h_prevs) = jax.lax.scan(
        scan_fn, h0, (jnp.moveaxis(Sc, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    h_prev = jnp.moveaxis(h_prevs, 0, 1)                  # (B,NC,H,P,N)

    y_inter = jnp.einsum("bcqhn,bchpn->bcqhp",
                         Ch * jnp.exp(cs)[..., None], h_prev)
    y = (y_intra + y_inter).reshape(Bb, S, H, Pp)
    y = y + D.astype(jnp.float32)[None, None, :, None] * x.astype(jnp.float32)
    return y.astype(x.dtype), h_final.astype(x.dtype)


def ssd_decode_step(
    x: jax.Array,       # (B,H,P)
    dt: jax.Array,      # (B,H)
    A_log: jax.Array,   # (H,)
    B_: jax.Array,      # (B,G,N)
    C_: jax.Array,      # (B,G,N)
    D: jax.Array,       # (H,)
    state: jax.Array,   # (B,H,P,N)
) -> Tuple[jax.Array, jax.Array]:
    H = x.shape[1]
    rep = H // B_.shape[1]
    Bh = jnp.repeat(B_, rep, axis=1).astype(jnp.float32)   # (B,H,N)
    Ch = jnp.repeat(C_, rep, axis=1).astype(jnp.float32)
    a = -jnp.exp(A_log.astype(jnp.float32))
    dA = jnp.exp(dt.astype(jnp.float32) * a)               # (B,H)
    xf = x.astype(jnp.float32)
    new_state = (state.astype(jnp.float32) * dA[:, :, None, None]
                 + jnp.einsum("bh,bhp,bhn->bhpn", dt.astype(jnp.float32), xf, Bh))
    y = jnp.einsum("bhn,bhpn->bhp", Ch, new_state) + D[None, :, None] * xf
    return y.astype(x.dtype), new_state.astype(state.dtype)
