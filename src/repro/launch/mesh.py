"""Production mesh definitions (TPU v5e pods).

Single pod: (data=16, model=16) = 256 chips.
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; ``pod`` is an outer
pure-data-parallel axis whose gradient all-reduce crosses DCI.

Functions, not module constants: importing this module must never touch
jax device state (the dry-run pins XLA_FLAGS before first jax init).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """A mesh over ``jax.devices()``, e.g. (data=2, model=2) on a v5e 2x2."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1x1 mesh for CPU tests of the sharded code path."""
    return make_mesh((1, 1), ("data", "model"))


def mesh_rules(mesh, *, overrides: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Logical-axis rules appropriate for a mesh (see parallel.sharding)."""
    from ..parallel.sharding import DEFAULT_RULES

    rules = dict(DEFAULT_RULES)
    if "pod" in mesh.axis_names:
        rules["batch"] = ("pod", "data")
    for k, v in (overrides or {}).items():
        rules[k] = v
    return rules


def batch_shard_size(mesh) -> int:
    n = mesh.shape["data"]
    if "pod" in mesh.axis_names:
        n *= mesh.shape["pod"]
    return n
