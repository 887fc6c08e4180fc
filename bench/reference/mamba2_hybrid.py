"""A plain hybrid of Mamba-2 and attention layers in jax.numpy: the
reference of ``bench/arch/mamba2_hybrid.py`` (GraniteMoeHybrid without
experts).

It follows the published description, layer by layer in the order of
``layer_types``:

- ``x0 = embed(t) * embedding_multiplier``;
- each layer: ``x = x + r * mixer(rmsnorm(x))``, then
  ``x = x + r * mlp(rmsnorm(x))``, r the ``residual_multiplier``;
- the MLP: ``silu(h @ w3) * (h @ w1) @ w2`` (``w3`` is the first half of
  the published ``input_linear``, the gate; ``w1`` the second);
- a Mamba-2 mixer: projections z, x, B, C and dt of the normed input (no
  bias); a depthwise causal conv of width K with bias over x, B and C,
  then silu; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the
  recurrence in its plain sequential form, one position after another,

      h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,   y_t = h_t C_t + D x_t

  per head (state P x N, one B/C group shared by all heads); then the
  gated norm ``rmsnorm(y * silu(z))`` and the output projection;
- an attention layer: grouped-query attention, query head i reading kv
  head i // (heads / kv_heads), no bias, no positional encoding, causal
  softmax at the scale ``attention_multiplier``;
- logits ``rmsnorm(x) @ embed^T / logits_scaling`` (tied).

It imports nothing of the system under test.  Callers run it under
``jax.default_matmul_precision("highest")`` for float32; ``dtype`` lowers
the precision for the controls, and ``int8`` runs every linear layer (the
mixer's projections, q, k, v, o, the MLP and the output projection) on
int8 operands, as a W8A8 path would: each operand rounded to 255 levels of
one scale per tensor, the gradient passed straight through.

One sequence at a time (no batch axis); each layer under
``jax.checkpoint``.
"""
from __future__ import annotations

import itertools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp


def groups(c: Dict[str, Any]) -> List[Tuple[str, int]]:
    """The runs of equal layer kinds, in order: ``[("mamba", 5), ...]``;
    the weights hold run ``i`` under ``g{i}``."""
    return [(t, len(list(run)))
            for t, run in itertools.groupby(c["layer_types"])]


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y.astype(x.dtype) * w.astype(x.dtype)


def _int8(x):
    """``x`` rounded to int8 levels of one per-tensor scale, in its own
    dtype; the gradient passes straight through."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(x / s), -127, 127) * s
    return x + jax.lax.stop_gradient(q - x)


def _mlp(c, p, x, lin):
    h = lin(_rms(x, p["ln2"], c["rms_norm_eps"]))
    dt = x.dtype
    gate = jax.nn.silu(h @ lin(p["w3"].astype(dt)))
    up = h @ lin(p["w1"].astype(dt))
    return lin(gate * up) @ lin(p["w2"].astype(dt))


def _conv(u, w, b):
    """Depthwise causal conv: u (S, C), w (C, K), b (C,); position t reads
    u[t - K + 1 .. t], tap K - 1 on u[t]."""
    S = u.shape[0]
    K = w.shape[1]
    up = jnp.pad(u, ((K - 1, 0), (0, 0)))
    return sum(up[k:k + S] * w[:, k] for k in range(K)) + b


def _mamba(c, p, x, lin):
    S = x.shape[0]
    H, P, N = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"]
    G = c["mamba_n_groups"]
    eps, dt_ = c["rms_norm_eps"], x.dtype
    h = lin(_rms(x, p["ln"], eps))
    z = h @ lin(p["wz"].astype(dt_))
    xs = jax.nn.silu(_conv(h @ lin(p["wx"].astype(dt_)), p["conv_x"].astype(dt_),
                           p["conv_x_b"].astype(dt_)))
    B = jax.nn.silu(_conv(h @ lin(p["wB"].astype(dt_)), p["conv_B"].astype(dt_),
                          p["conv_B_b"].astype(dt_)))
    C = jax.nn.silu(_conv(h @ lin(p["wC"].astype(dt_)), p["conv_C"].astype(dt_),
                          p["conv_C_b"].astype(dt_)))
    dt = jax.nn.softplus((h @ lin(p["wdt"].astype(dt_))).astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))          # (S, H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))                     # (H,)
    xh = xs.reshape(S, H, P).astype(jnp.float32)
    Bg = B.reshape(S, G, N).astype(jnp.float32)
    Cg = C.reshape(S, G, N).astype(jnp.float32)
    rep = H // G

    def step(state, inp):
        x_t, B_t, C_t, dt_t = inp                  # (H,P), (G,N), (G,N), (H,)
        B_h, C_h = jnp.repeat(B_t, rep, axis=0), jnp.repeat(C_t, rep, axis=0)
        state = (jnp.exp(dt_t * A)[:, None, None] * state
                 + dt_t[:, None, None] * x_t[:, :, None] * B_h[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, C_h)

    state0 = jnp.zeros((H, P, N), jnp.float32)
    _, y = jax.lax.scan(step, state0, (xh, Bg, Cg, dt))
    y = y + p["D_skip"].astype(jnp.float32)[None, :, None] * xh
    y = y.reshape(S, H * P).astype(dt_)
    y = _rms(y * jax.nn.silu(z), p["norm"], eps)
    return lin(y) @ lin(p["wout"].astype(dt_))


def _attention(c, p, x, lin):
    S, _ = x.shape
    H, KV = c["num_attention_heads"], c["num_key_value_heads"]
    dt = x.dtype
    h = lin(_rms(x, p["ln1"], c["rms_norm_eps"]))
    q = jnp.einsum("sd,dnh->snh", h, lin(p["wq"].astype(dt)))
    k = jnp.einsum("sd,dnh->snh", h, lin(p["wk"].astype(dt)))
    v = jnp.einsum("sd,dnh->snh", h, lin(p["wv"].astype(dt)))
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    s = jnp.einsum("qnh,knh->nqk", q, k).astype(jnp.float32)
    s = s * c["attention_multiplier"]
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1).astype(dt)
    o = jnp.einsum("nqk,knh->qnh", a, v)
    return jnp.einsum("qnh,nhd->qd", lin(o), lin(p["wo"].astype(dt)))


def hidden(c: Dict[str, Any], w: Dict[str, Any], tokens, dtype=jnp.float32,
           int8: bool = False):
    """(S,) token ids -> (S, D) final-normed hidden states."""
    lin = _int8 if int8 else (lambda a: a)
    r = c["residual_multiplier"]
    x = w["embed"].astype(dtype)[tokens] * c["embedding_multiplier"]
    for gi, (kind, _) in enumerate(groups(c)):
        mixer = _mamba if kind == "mamba" else _attention

        @jax.checkpoint
        def body(x, p, mixer=mixer):
            x = x + r * mixer(c, p, x, lin)
            return x + r * _mlp(c, p, x, lin), None

        x, _ = jax.lax.scan(body, x, w[f"g{gi}"])
    return _rms(x, w["final_norm"], c["rms_norm_eps"])


def logits(c: Dict[str, Any], w: Dict[str, Any], tokens, dtype=jnp.float32,
           int8: bool = False):
    """(S,) token ids -> (S, V) float32 logits."""
    x = hidden(c, w, tokens, dtype, int8)
    out = w["embed"].T.astype(dtype)
    if int8:
        x, out = _int8(x), _int8(out)
    return (x @ out).astype(jnp.float32) / c["logits_scaling"]


def row_loss(c: Dict[str, Any], w: Dict[str, Any], tokens, labels,
             int8: bool = False):
    """Mean next-token cross-entropy of one row."""
    lg = logits(c, w, tokens, int8=int8)
    gold = jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(lg, axis=-1) - gold)
