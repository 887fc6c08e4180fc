"""A plain dense decoder in jax.numpy: the benchmark's reference.

It follows the published description of a Llama/Qwen2-style decoder with
grouped-query attention (RMSNorm, rotary position embedding on half-split
head dimensions, causal softmax attention with query head i reading kv head
i // (heads / kv_heads), optional q/k/v biases, SwiGLU MLP with ``w3`` as
the gate and ``w1`` as the up projection, tied or separate output
projection) over the weight layout of ``bench/arch/dense_gqa.py``.  It imports
nothing of the system under test.  Callers run it under
``jax.default_matmul_precision("highest")`` for float32, and in float32
throughout; ``dtype`` lowers the precision for the controls, and ``int8``
runs every linear layer (q, k, v, o, the MLP and the output projection)
on int8 operands, as a W8A8 path would: each operand rounded to 255 levels
of one scale per tensor (its largest magnitude over 127), the gradient
passed straight through.

One sequence at a time (no batch axis), each layer under ``jax.checkpoint``
so that gradients at 2048 positions fit beside the optimizer state.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .optim import adamw  # noqa: F401  (the training reference's optimizer)


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y.astype(x.dtype) * w.astype(x.dtype)


def _rope(x, theta):
    """x: (S, n, hd); rotate the two halves of each head by position."""
    S, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _int8(x):
    """``x`` rounded to int8 levels of one per-tensor scale, in its own
    dtype; the gradient passes straight through."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(x / s), -127, 127) * s
    return x + jax.lax.stop_gradient(q - x)


def _layer(c: Dict[str, Any], p: Dict[str, Any], x, int8: bool = False):
    S, D = x.shape
    H, KV = c["num_attention_heads"], c["num_key_value_heads"]
    eps, dt = c["rms_norm_eps"], x.dtype
    lin = _int8 if int8 else (lambda a: a)
    h = lin(_rms(x, p["ln1"], eps))
    q = jnp.einsum("sd,dnh->snh", h, lin(p["wq"].astype(dt)))
    k = jnp.einsum("sd,dnh->snh", h, lin(p["wk"].astype(dt)))
    v = jnp.einsum("sd,dnh->snh", h, lin(p["wv"].astype(dt)))
    if "bq" in p:
        q, k, v = (q + p["bq"].astype(dt), k + p["bk"].astype(dt),
                   v + p["bv"].astype(dt))
    q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    s = jnp.einsum("qnh,knh->nqk", q, k).astype(jnp.float32)
    s = s / math.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1).astype(dt)
    o = jnp.einsum("nqk,knh->qnh", a, v)
    x = x + jnp.einsum("qnh,nhd->qd", lin(o), lin(p["wo"].astype(dt)))
    h = lin(_rms(x, p["ln2"], eps))
    gate = jax.nn.silu(h @ lin(p["w3"].astype(dt)))
    up = h @ lin(p["w1"].astype(dt))
    return x + lin(gate * up) @ lin(p["w2"].astype(dt))


def hidden(c: Dict[str, Any], w: Dict[str, Any], tokens, dtype=jnp.float32,
           int8: bool = False):
    """(S,) token ids -> (S, D) final-normed hidden states."""
    x = w["embed"].astype(dtype)[tokens]

    @jax.checkpoint
    def body(x, p):
        return _layer(c, p, x, int8), None

    x, _ = jax.lax.scan(body, x, w["g0"])
    return _rms(x, w["final_norm"], c["rms_norm_eps"])


def logits(c: Dict[str, Any], w: Dict[str, Any], tokens, dtype=jnp.float32,
           int8: bool = False):
    """(S,) token ids -> (S, V) float32 logits."""
    x = hidden(c, w, tokens, dtype, int8)
    out = (w["unembed"] if "unembed" in w else w["embed"].T).astype(dtype)
    if int8:
        x, out = _int8(x), _int8(out)
    return (x @ out).astype(jnp.float32)


def row_loss(c: Dict[str, Any], w: Dict[str, Any], tokens, labels,
             int8: bool = False):
    """Mean next-token cross-entropy of one row."""
    lg = logits(c, w, tokens, int8=int8)
    gold = jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(lg, axis=-1) - gold)
