"""A toy architecture for the plug-in test: two leaves, its own init rule
and its own counts, so that each can be told from the dense decoder's."""
from types import SimpleNamespace


def program_config(c):
    return SimpleNamespace(arch_id=c["name"], family="toy", d=c["hidden_size"])


def shapes(c):
    D, V = c["hidden_size"], c["vocab_size"]
    return {"embed": (V, D), "mix": {"w": (D, D)}}


def init(name, shape, key, c):
    import jax.numpy as jnp

    return jnp.full(shape, 3.0 if name == "mix/w" else 2.0, jnp.float32)


def train_flops_per_token(c, seq):
    return 1000.0 * seq


def matmul_params(c):
    return c["hidden_size"] ** 2


def kv_entry_bytes(c):
    return 7


def decode_step(c, positions):
    pos = list(positions)
    return {"flops": 5.0 * len(pos), "bytes": 11.0 + sum(pos)}


reference = None
