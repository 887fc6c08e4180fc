"""Optimizers, functional + as graph nodes.

The paper's design point: parameter updates are *just more nodes in the
graph* operating on Variables — no separate parameter-server subsystem
(§11, "a significant simplification").  ``attach_train_op`` realises that:
given a Session graph with a loss node and parameter Variables, it extends
the graph with §4.1 gradients, optimizer-state Variables, and Assign
update nodes, returning the train_op group node.

The functional forms (``*_init`` / ``*_update``) are pure pytree->pytree
and are what the compiled/pjit path fuses into the step function.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..core.graph import Node, TensorRef
from ..core.ops import GraphBuilder
from ..core import autodiff, numerics


class OptState(NamedTuple):
    step: jax.Array
    m: Any  # first moment / momentum (pytree like params, or ())
    v: Any  # second moment (pytree like params, or ())


# --- SGD ---------------------------------------------------------------------

def sgd_init(params) -> OptState:
    return OptState(step=jnp.zeros((), jnp.int32), m=(), v=())


def sgd_update(params, grads, state: OptState, *, lr: float = 1e-2,
               **_) -> Tuple[Any, OptState]:
    new_params = jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype), params, grads)
    return new_params, OptState(step=state.step + 1, m=(), v=())


# --- SGD + momentum -----------------------------------------------------------

def momentum_init(params) -> OptState:
    return OptState(step=jnp.zeros((), jnp.int32),
                    m=jax.tree.map(jnp.zeros_like, params), v=())


def momentum_update(params, grads, state: OptState, *, lr: float = 1e-2,
                    momentum: float = 0.9, **_) -> Tuple[Any, OptState]:
    new_m = jax.tree.map(lambda m, g: momentum * m + g.astype(m.dtype), state.m, grads)
    new_params = jax.tree.map(lambda p, m: p - lr * m, params, new_m)
    return new_params, OptState(step=state.step + 1, m=new_m, v=())


# --- AdamW --------------------------------------------------------------------

def adamw_init(params) -> OptState:
    zeros = lambda p: jnp.zeros_like(p, dtype=jnp.float32)
    return OptState(step=jnp.zeros((), jnp.int32),
                    m=jax.tree.map(zeros, params),
                    v=jax.tree.map(zeros, params))


def adamw_update(params, grads, state: OptState, *, lr: float = 3e-4,
                 b1: float = 0.9, b2: float = 0.95, eps: float = 1e-8,
                 weight_decay: float = 0.1,
                 grad_clip: Optional[float] = 1.0) -> Tuple[Any, OptState]:
    step = state.step + 1
    if grad_clip is not None:
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                             for g in jax.tree.leaves(grads)) + 1e-12)
        scale = jnp.minimum(1.0, grad_clip / gnorm)
        grads = jax.tree.map(lambda g: g * scale, grads)
    t = step.astype(jnp.float32)
    bc1 = 1.0 - b1 ** t
    bc2 = 1.0 - b2 ** t

    def upd(p, g, m, v):
        g = g.astype(jnp.float32)
        m2 = b1 * m + (1 - b1) * g
        v2 = b2 * v + (1 - b2) * g * g
        update = (m2 / bc1) / (jnp.sqrt(v2 / bc2) + eps)
        p2 = p.astype(jnp.float32) - lr * (update + weight_decay * p.astype(jnp.float32))
        return p2.astype(p.dtype), m2, v2

    flat_p, tdef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(state.m)
    flat_v = jax.tree.leaves(state.v)
    outs = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = jax.tree.unflatten(tdef, [o[0] for o in outs])
    new_m = jax.tree.unflatten(tdef, [o[1] for o in outs])
    new_v = jax.tree.unflatten(tdef, [o[2] for o in outs])
    return new_p, OptState(step=step, m=new_m, v=new_v)


_OPTIMIZERS: Dict[str, Tuple[Callable, Callable]] = {
    "sgd": (sgd_init, sgd_update),
    "momentum": (momentum_init, momentum_update),
    "adamw": (adamw_init, adamw_update),
}


def make_optimizer(name: str, **hparams):
    init, update = _OPTIMIZERS[name]

    def bound_update(params, grads, state):
        return update(params, grads, state, **hparams)

    return init, bound_update


# ---------------------------------------------------------------------------
# Graph integration: "updates are just nodes" (§2 Variables / §11).


def attach_train_op(
    b: GraphBuilder,
    loss: "Node | TensorRef",
    param_vars: Sequence[Node],
    optimizer: str = "sgd",
    name: str = "train",
    **hparams,
) -> Node:
    """Extend the graph with gradients + optimizer update nodes.

    Returns a NoOp group node; fetching it runs one optimization step.
    Optimizer state lives in per-parameter Variables in the same graph.
    """
    g = b.graph
    grad_refs = autodiff.gradients(g, [loss], list(param_vars))
    init_fn, update_fn = make_optimizer(optimizer, **hparams)

    step_var = b.variable(f"{name}/step", init_value=lambda: jnp.zeros((), jnp.int32))
    new_step = b.assign_add(step_var, b.constant(jnp.ones((), jnp.int32), name=f"{name}/one"))
    updates = [new_step]

    for pv, gref in zip(param_vars, grad_refs):
        if gref is None:
            raise ValueError(f"loss does not depend on variable {pv.name}")
        slots: Dict[str, Node] = {}

        def zeros_like_param(pv=pv):
            init = pv.attrs.get("init")
            if init is None:
                raise ValueError(f"variable {pv.name} needs an init for optimizer slots")
            val = init() if callable(init) else init
            return jax.tree.map(lambda x: jnp.zeros_like(x, dtype=jnp.float32), val)

        for slot in {"momentum": ("m",), "adamw": ("m", "v")}.get(optimizer, ()):
            svar = b.variable(f"{name}/{pv.name}/{slot}", init_value=zeros_like_param)
            svar.attrs["colocate_with"] = pv.name  # §4.3: state lives with its param
            slots[slot] = svar

        if optimizer == "sgd":
            def sgd_node(p, g, s, lr=hparams.get("lr", 1e-2)):
                return p - lr * g
            newp = b.call(sgd_node, [pv, gref, step_var], name=f"{name}/{pv.name}/newp")
            updates.append(b.assign(pv, newp))
        elif optimizer == "momentum":
            mu = hparams.get("momentum", 0.9)
            lr = hparams.get("lr", 1e-2)
            mvar = slots["m"]

            def mom_node(p, g, m, mu=mu, lr=lr):
                m2 = mu * m + g
                return p - lr * m2, m2
            res = b.call(mom_node, [pv, gref, mvar], name=f"{name}/{pv.name}/mom", n_out=2)
            updates.append(b.assign(pv, res.output(0)))
            updates.append(b.assign(mvar, res.output(1)))
        elif optimizer == "adamw":
            lr = hparams.get("lr", 3e-4)
            b1 = hparams.get("b1", 0.9)
            b2 = hparams.get("b2", 0.95)
            eps = hparams.get("eps", 1e-8)
            wd = hparams.get("weight_decay", 0.0)
            mvar, vvar = slots["m"], slots["v"]

            def adamw_node(p, g, m, v, t, lr=lr, b1=b1, b2=b2, eps=eps, wd=wd):
                t = t.astype(jnp.float32)
                g = g.astype(jnp.float32)
                m2 = b1 * m + (1 - b1) * g
                v2 = b2 * v + (1 - b2) * g * g
                upd = (m2 / (1 - b1 ** t)) / (jnp.sqrt(v2 / (1 - b2 ** t)) + eps)
                p2 = p.astype(jnp.float32) - lr * (upd + wd * p.astype(jnp.float32))
                return p2.astype(p.dtype), m2, v2
            res = b.call(adamw_node, [pv, gref, mvar, vvar, new_step],
                         name=f"{name}/{pv.name}/adamw", n_out=3,
                         attrs={"numerics_class": (numerics.OPTIMIZER,
                                                   "call", "call")})
            updates.append(b.assign(pv, res.output(0)))
            updates.append(b.assign(mvar, res.output(1)))
            updates.append(b.assign(vvar, res.output(2)))
        else:
            raise ValueError(f"unknown optimizer {optimizer!r}")
    return b.group(updates, name=f"{name}/op")
