"""Weights from the seed, made on the device in one jitted call.

The tree, in the program's own layout, and the rule that draws each leaf
are the architecture's (``bench/arch/<arch>.py``: ``shapes``, ``init``).
The benchmark hands the same tree to the system under test and to the
plain reference; neither makes its own.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

from . import arch


def make(c: Dict[str, Any], key) -> Dict[str, Any]:
    """The float32 weight tree for config ``c`` from a PRNG key, built by
    one jitted call on the default device."""
    import jax

    a = arch.of(c)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        a.shapes(c), is_leaf=lambda x: isinstance(x, tuple))
    names = ["/".join(str(getattr(k, "key", k)) for k in p) for p, _ in flat]

    def build(key):
        keys = jax.random.split(key, len(flat))
        return jax.tree_util.tree_unflatten(
            treedef, [a.init(n, s, k, c) for n, (_, s), k in zip(names, flat, keys)])

    return jax.jit(build)(key)


def key_for(seed: int, stream: int):
    """A 32-bit PRNG key for ``stream`` of a run seed of any size."""
    import jax

    word = np.random.SeedSequence([int(seed), int(stream)]).generate_state(1)[0]
    return jax.random.PRNGKey(int(word) & 0x7FFFFFFF)


def n_params(c: Dict[str, Any]) -> int:
    """Every parameter of the architecture's tree, once."""
    import jax

    return sum(int(np.prod(s)) for s in jax.tree.leaves(
        arch.of(c).shapes(c), is_leaf=lambda x: isinstance(x, tuple)))
