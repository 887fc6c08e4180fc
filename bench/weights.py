"""Weights from the seed, made on the device in one jitted call.

The layout is that of a dense decoder with grouped-query attention, every
layer's leaves stacked on a leading axis of ``num_hidden_layers``:

  embed (V, D), final_norm (D,), [unembed (D, V) when not tied],
  g0: ln1 (L, D), wq (L, D, H, hd), wk/wv (L, D, KV, hd), wo (L, H, hd, D),
      [bq (L, H, hd), bk/bv (L, KV, hd)], ln2 (L, D),
      w1 (L, D, F) up, w3 (L, D, F) gate, w2 (L, F, D) down.

The benchmark hands the same tree to the system under test and to the
plain reference (``bench/reference``); neither makes its own.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import numpy as np


def dims(c: Dict[str, Any]) -> Dict[str, int]:
    H = c["num_attention_heads"]
    return {"D": c["hidden_size"], "F": c["intermediate_size"], "H": H,
            "KV": c["num_key_value_heads"], "L": c["num_hidden_layers"],
            "V": c["vocab_size"], "hd": c.get("head_dim") or c["hidden_size"] // H}


def shapes(c: Dict[str, Any]) -> Dict[str, Any]:
    d = dims(c)
    D, F, H, KV, L, V, hd = (d[k] for k in ("D", "F", "H", "KV", "L", "V", "hd"))
    layer = {"ln1": (L, D), "wq": (L, D, H, hd), "wk": (L, D, KV, hd),
             "wv": (L, D, KV, hd), "wo": (L, H, hd, D), "ln2": (L, D),
             "w1": (L, D, F), "w2": (L, F, D), "w3": (L, D, F)}
    if c.get("attention_bias"):
        layer.update(bq=(L, H, hd), bk=(L, KV, hd), bv=(L, KV, hd))
    tree = {"embed": (V, D), "final_norm": (D,), "g0": layer}
    if not c["tie_word_embeddings"]:
        tree["unembed"] = (D, V)
    return tree


def _init(path: str, shape: Tuple[int, ...], key, n_layers: int):
    import jax
    import jax.numpy as jnp

    z = jax.random.normal(key, shape, jnp.float32)
    leaf = path.rsplit("/", 1)[-1]
    if leaf in ("ln1", "ln2", "final_norm"):
        return 1.0 + 0.02 * z
    if leaf in ("wo", "w2"):
        return z * (0.02 / math.sqrt(2 * n_layers))
    return z * 0.02


def make(c: Dict[str, Any], key) -> Dict[str, Any]:
    """The float32 weight tree for config ``c`` from a PRNG key, built by
    one jitted call on the default device."""
    import jax

    tree = shapes(c)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    names = ["/".join(str(getattr(k, "key", k)) for k in p) for p, _ in flat]
    n_layers = c["num_hidden_layers"]

    def build(key):
        keys = jax.random.split(key, len(flat))
        return jax.tree_util.tree_unflatten(
            treedef, [_init(n, s, k, n_layers)
                      for n, (_, s), k in zip(names, flat, keys)])

    return jax.jit(build)(key)


def key_for(seed: int, stream: int):
    """A 32-bit PRNG key for ``stream`` of a run seed of any size."""
    import jax

    word = np.random.SeedSequence([int(seed), int(stream)]).generate_state(1)[0]
    return jax.random.PRNGKey(int(word) & 0x7FFFFFFF)


def n_params(c: Dict[str, Any]) -> int:
    import jax

    return sum(int(np.prod(s)) for s in jax.tree.leaves(
        shapes(c), is_leaf=lambda x: isinstance(x, tuple)))
