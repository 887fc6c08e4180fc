"""Flash-decode: one query token against a long KV cache.

The decode-path hot spot (decode_32k / long_500k): for each (batch, head)
a single query attends T cached keys.  K/V stream HBM->VMEM in bkv
blocks; the online-softmax state (m, l, acc) lives in VMEM scratch across
the KV sweep, and a per-row valid length masks unwritten cache slots —
matching the serve-path semantics of models.lm._decode_attn.

Layouts (heads folded): q (BH, D), k/v (BH, T, D), kv_valid (BH,) int32.
Inside the call q and the output carry a unit middle axis, (BH, 1, D), so
that every block's last two dims equal the array's, and kv_valid rides
scalar prefetch into SMEM: the TPU compiler refuses rank-1 blocks that
are not a multiple of 128 and (1, D) blocks of a (BH, D) array.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(valid_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, bkv: int, n_kv: int,
                   scale: float):
    i_kv = pl.program_id(1)

    @pl.when(i_kv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)            # (1, D)
    k = k_ref[0].astype(jnp.float32)            # (bkv, D)
    v = v_ref[0].astype(jnp.float32)
    valid = valid_ref[pl.program_id(0)]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    k_pos = i_kv * bkv + jax.lax.broadcasted_iota(jnp.int32, (1, bkv), 1)
    live = k_pos < valid                         # (1, bkv)
    s = jnp.where(live, s, NEG_INF)

    m_prev = m_scr[...]                          # (1, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)                       # (1, bkv)
    # fully-masked blocks: exp(NEG_INF - NEG_INF) = 1 must not count
    p = jnp.where(live, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(i_kv == n_kv - 1)
    def _done():
        l = l_scr[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / safe).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bkv", "interpret"))
def flash_decode_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                        kv_valid: jax.Array, *, bkv: int = 512,
                        interpret: bool = False) -> jax.Array:
    """q (BH, D); k, v (BH, T, D); kv_valid (BH,) -> (BH, D)."""
    BH, D = q.shape
    T = k.shape[1]
    bkv = min(bkv, T)
    assert T % bkv == 0, (T, bkv)
    n_kv = T // bkv
    scale = 1.0 / math.sqrt(D)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, bkv=bkv, n_kv=n_kv, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BH, n_kv),
            in_specs=[
                pl.BlockSpec((1, 1, D), lambda bh, ik, valid: (bh, 0, 0)),
                pl.BlockSpec((1, bkv, D), lambda bh, ik, valid: (bh, ik, 0)),
                pl.BlockSpec((1, bkv, D), lambda bh, ik, valid: (bh, ik, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, D), lambda bh, ik, valid: (bh, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((BH, 1, D), q.dtype),
        interpret=interpret,
    )(kv_valid.astype(jnp.int32), q.reshape(BH, 1, D), k, v)
    return out.reshape(BH, D)
