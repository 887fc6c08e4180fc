"""AdamW as the configuration states it: the optimizer of every
architecture's training reference.  It imports nothing of the program."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp


def adamw(params, grads, m, v, step: int, o: Dict[str, float]):
    """One AdamW step as the configuration states it: global-norm clip,
    bias-corrected moments, decoupled weight decay.  Returns the clipped
    gradient too."""
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves) + 1e-12)
    scale = jnp.minimum(1.0, o["grad_clip"] / gnorm)
    g = jax.tree.map(lambda x: x * scale, grads)
    m = jax.tree.map(lambda a, b: o["b1"] * a + (1 - o["b1"]) * b, m, g)
    v = jax.tree.map(lambda a, b: o["b2"] * a + (1 - o["b2"]) * b * b, v, g)
    bc1, bc2 = 1 - o["b1"] ** step, 1 - o["b2"] ** step
    params = jax.tree.map(
        lambda p, a, b: p - o["lr"] * ((a / bc1) / (jnp.sqrt(b / bc2) + o["eps"])
                                       + o["weight_decay"] * p),
        params, m, v)
    return params, m, v, g
