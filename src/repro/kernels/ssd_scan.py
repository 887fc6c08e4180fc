"""Mamba-2 SSD chunked scan as a Pallas kernel.

Grid (B*H, n_chunks) with the chunk dimension innermost/sequential; the
(N, P) recurrent state lives in f32 VMEM scratch across chunks.  Each
step computes the intra-chunk quadratic term (Q×Q attention-like matmul
on the MXU), the inter-chunk contribution from the carried state, and
the state update — one HBM pass over x/dt/B/C per layer, which is the
TPU-native shape of the SSD algorithm (DESIGN.md: recurrent-scan
blocking for VMEM instead of the paper's CUDA warp layout).

Layouts (heads folded): x (BH, S, P), dt (BH, S), Bc/Cc (BH, S, N),
A (BH,) negative decay rate per head.  Inside the call dt is a column,
(BH, S, 1), so its (chunk, 1) blocks meet the TPU's (8, 128) tiling rule
(the last dim equals the array's), and A rides scalar prefetch into SMEM.
Prefix sums are matmuls with a triangular ones matrix, which the MXU
runs and the TPU compiler accepts; the intra-chunk weights dt_t scale
the rows of x rather than the columns of the score matrix, so no row
copy of dt is needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, state_scr, *,
                chunk: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0].astype(jnp.float32)        # (Q, P)
    dt = dt_ref[0].astype(jnp.float32)      # (Q, 1)
    a = a_ref[pl.program_id(0)]             # scalar (negative)
    B = b_ref[0].astype(jnp.float32)        # (Q, N)
    C = c_ref[0].astype(jnp.float32)        # (Q, N)

    dA = dt * a                             # (Q, 1)
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = rows >= cols                      # tri[q, t] = t <= q
    # inclusive prefix sums of dA, as a column and as a row, at full f32
    # precision: they are exponentiated, so a bf16 pass would put errors
    # of ~2**-9 * |cs| into the decay exponents
    exact = jax.lax.Precision.HIGHEST
    cs = jnp.dot(tri.astype(jnp.float32), dA, precision=exact,
                 preferred_element_type=jnp.float32)          # (Q, 1)
    cs_row = jax.lax.dot_general(
        dA, (rows <= cols).astype(jnp.float32), (((0,), (0,)), ((), ())),
        precision=exact, preferred_element_type=jnp.float32)  # (1, Q)
    # intra-chunk: y_q = sum_{t<=q} C_q·B_t * exp(cs_q - cs_t) * dt_t x_t
    L = jnp.where(tri, jnp.exp(cs - cs_row), 0.0)
    att = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    y = jnp.dot(att * L, dt * x, preferred_element_type=jnp.float32)

    # inter-chunk: y += (C * exp(cs)) @ state   (state: (N, P))
    y += jnp.dot(C * jnp.exp(cs), state_scr[...],
                 preferred_element_type=jnp.float32)

    # state update: state = exp(cs_end) * state + sum_t w_t B_t^T x_t
    cs_end = jnp.sum(dA, axis=0, keepdims=True)               # (1, 1)
    w = dt * jnp.exp(cs_end - cs)           # (Q, 1)
    state_scr[...] = (jnp.exp(cs_end) * state_scr[...]
                      + jax.lax.dot_general(
                          B * w, x, (((0,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32))
    y_ref[0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(x: jax.Array, dt: jax.Array, a: jax.Array,
                    Bc: jax.Array, Cc: jax.Array, *, chunk: int = 128,
                    interpret: bool = False) -> jax.Array:
    """x (BH,S,P), dt (BH,S), a (BH,), Bc/Cc (BH,S,N) -> y (BH,S,P)."""
    BH, S, P = x.shape
    N = Bc.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    n_chunks = S // chunk
    return pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BH, n_chunks),
            in_specs=[
                pl.BlockSpec((1, chunk, P), lambda bh, c, a: (bh, c, 0)),
                pl.BlockSpec((1, chunk, 1), lambda bh, c, a: (bh, c, 0)),
                pl.BlockSpec((1, chunk, N), lambda bh, c, a: (bh, c, 0)),
                pl.BlockSpec((1, chunk, N), lambda bh, c, a: (bh, c, 0)),
            ],
            out_specs=pl.BlockSpec((1, chunk, P), lambda bh, c, a: (bh, c, 0)),
            scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((BH, S, P), x.dtype),
        interpret=interpret,
    )(a.astype(jnp.float32), x, dt.reshape(BH, S, 1), Bc, Cc)
