"""What decides ``correct``: the system's results against the plain
reference of the configuration's architecture (``bench/arch``; the
references live in ``bench/reference``), each number beside its cell's
limit (``bench/limits/<cell>.json``).

Training.  The window's own step, on the run's first three batches,
against the reference's three AdamW steps from the same weights:
``first_loss_gap``, the relative gap of the first step's loss (the later
steps' losses are reported as ``loss_gaps`` and not compared: AdamW's first
steps move each weight by about the learning rate whatever its gradient's
size, so the gradient signs that rounding flips swing them from seed to
seed); ``grad_norm_gap``, the gradient as the optimizer got it on step 1 (its
first moment over 1 - b1), and ``update1_gap``, the parameters' change
made by step 1 (the change after step 3, ``update_norm_gap``, swings for
the same reason as the later losses and is reported, not compared).  Both
are taken by the worst leaf: |norm(system) - norm(reference)| over the
larger of the reference's norm of that leaf and of the median leaf.
``grad_diff`` is step 1's gradient again, by the norm of its difference
from the reference's, over the same denominator: a gap of norms is blind
to an error that is orthogonal to the gradient, as rounding mostly is, and
this one separates a lower precision.  Leaves whose reference gradient is
under a thousandth of the median leaf's are left out of all three: their
change is round-off turned into full steps by AdamW.

Serving.  Requests the window finished, each prompt with its served
tokens: ``logit_gap`` is the widest gap by which a served token's logit
lies below the reference's best logit at its position.
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from . import arch, gen
from . import weights as W
from .reference.optim import adamw

GRAD_FLOOR = 1e-3   # of the median leaf's reference gradient norm


def _names(tree) -> List[str]:
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", getattr(k, "name", k))) for k in p)
            for p, _ in flat]


def leaf_norms(tree) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp

    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                               for x in jax.tree.leaves(t)])(tree)
    return dict(zip(_names(tree), map(float, jax.device_get(norms))))


def change_norms(new, old) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp

    norms = jax.jit(lambda a, b: [jnp.sqrt(jnp.sum(jnp.square(x - y)))
                                  for x, y in zip(jax.tree.leaves(a),
                                                  jax.tree.leaves(b))])(new, old)
    return dict(zip(_names(new), map(float, jax.device_get(norms))))


def norm_gap(system: Dict[str, float], reference: Dict[str, float],
             leaves: List[str]) -> Dict[str, Any]:
    med = float(np.median(list(reference.values())))
    gaps = {k: abs(system[k] - reference[k]) / max(reference[k], med)
            for k in leaves}
    worst = max(gaps, key=gaps.get)
    return {"value": gaps[worst], "leaf": worst, "gaps": gaps}


def diff_gap(diff: Dict[str, float], reference: Dict[str, float],
             leaves: List[str]) -> Dict[str, Any]:
    """The worst leaf's norm of the difference over the larger of its own
    and the median leaf's reference norm."""
    med = float(np.median(list(reference.values())))
    gaps = {k: diff[k] / max(reference[k], med) for k in leaves}
    worst = max(gaps, key=gaps.get)
    return {"value": gaps[worst], "leaf": worst, "gaps": gaps}


def counted_leaves(ref_grad: Dict[str, float]) -> List[str]:
    med = float(np.median(list(ref_grad.values())))
    return [k for k, g in ref_grad.items() if g >= GRAD_FLOOR * med]


def train_reference(c: Dict[str, Any], t: Dict[str, Any], seed: int,
                    rows: int = 0, int8: bool = False, against=None,
                    keep: bool = False) -> Dict[str, Any]:
    """The reference's first three steps on the run's first three batches.
    ``rows`` > 0 keeps only the first ``rows`` rows of each batch (a
    planted fault: half of the batch left out); ``int8`` runs its linear
    layers on int8 operands (the precision control).  ``against``, a host
    tree of the system's first gradient, gives each leaf's norm of the
    difference (``grad1_diff``); ``keep`` returns this run's first
    gradient as such a tree (``grad1_tree``)."""
    import jax
    import jax.numpy as jnp

    o = c["deployment"]["optimizer"]
    ref = arch.of(c).reference
    batches = gen.train_batches(t, c["vocab_size"], c["eos_token_id"], seed)
    with jax.default_matmul_precision("highest"):
        grad_row = jax.jit(jax.value_and_grad(
            lambda w, x, y: ref.row_loss(c, w, x, y, int8)))
        add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
        update = jax.jit(lambda p, g, m, v, s: adamw(p, g, m, v, s, o))
        p0 = W.make(c, W.key_for(seed, 0))
        params = p0
        m = jax.tree.map(jnp.zeros_like, p0)
        v = jax.tree.map(jnp.zeros_like, p0)
        losses, out = [], {}
        for step in (1, 2, 3):
            b = next(batches)
            n = rows or b["tokens"].shape[0]
            total, grads = 0.0, None
            for r in range(n):
                loss, g = grad_row(params, jnp.asarray(b["tokens"][r]),
                                   jnp.asarray(b["labels"][r]))
                total += float(loss)
                grads = g if grads is None else add(grads, g)
            grads = jax.tree.map(lambda x: x / n, grads)
            params, m, v, clipped = update(params, grads, m, v,
                                           jnp.float32(step))
            losses.append(total / n)
            if step == 1:
                out["grad1"] = leaf_norms(clipped)
                out["change1"] = change_norms(params, p0)
                if against is not None:
                    out["grad1_diff"] = change_norms(clipped, against)
                if keep:
                    out["grad1_tree"] = jax.device_get(clipped)
            del grads, clipped
        out["change3"] = change_norms(params, p0)
    return dict(out, losses=losses)


def train_readings(system: Dict[str, Any], reference: Dict[str, Any]
                   ) -> Dict[str, Any]:
    leaves = counted_leaves(reference["grad1"])
    loss_gaps = [abs(a - b) / abs(b) for a, b in
                 zip(system["losses"], reference["losses"])]
    g = norm_gap(system["grad1"], reference["grad1"], leaves)
    u = norm_gap(system["change3"], reference["change3"], leaves)
    u1 = norm_gap(system["change1"], reference["change1"], leaves)
    d = diff_gap(reference["grad1_diff"], reference["grad1"], leaves)
    return {"first_loss_gap": loss_gaps[0], "loss_gap": max(loss_gaps),
            "loss_gaps": loss_gaps,
            "grad_norm_gap": g["value"], "grad_diff": d["value"],
            "diff_leaf": d["leaf"], "diff_gaps": d["gaps"],
            "update1_gap": u1["value"], "update1_gaps": u1["gaps"],
            "update_norm_gap": u["value"], "grad_leaf": g["leaf"],
            "update_leaf": u["leaf"],
            "left_out": sorted(set(reference["grad1"]) - set(leaves)),
            "grad_gaps": g["gaps"], "update_gaps": u["gaps"],
            "losses": list(system["losses"]),
            "reference_losses": reference["losses"]}


def train(c, t, seed: int, system: Dict[str, Any]) -> Dict[str, Any]:
    return train_readings(system, train_reference(
        c, t, seed, against=system["grad1_tree"]))


def _served_gaps(c: Dict[str, Any], control: str):
    """A jitted function of (weights, tokens, positions, served, mask) ->
    the widest gap, over the masked positions, between the reference's
    best logit and its logit of the served token (``control`` "bf16" or
    "int8": of the token that a reference in that precision puts first)."""
    import jax
    import jax.numpy as jnp

    ref = arch.of(c).reference

    def fn(w, tokens, pos, served, mask):
        lg = ref.logits(c, w, tokens)
        best = jnp.max(lg, axis=-1)[pos]
        if control == "bf16":
            low = ref.logits(c, jax.tree.map(lambda x: x.astype(jnp.bfloat16), w),
                             tokens, dtype=jnp.bfloat16)
        elif control == "int8":
            low = ref.logits(c, w, tokens, int8=True)
        if control:
            served = jnp.argmax(low[pos], axis=-1)
        got = jnp.take_along_axis(lg[pos], served[:, None], axis=-1)[:, 0]
        return jnp.max(jnp.where(mask, best - got, -jnp.inf))

    return jax.jit(fn)


def serve(c: Dict[str, Any], seed: int, samples: List[Dict[str, Any]],
          control: str = "") -> Dict[str, Any]:
    """``logit_gap`` over the sampled requests (each a prompt and its
    served tokens), the reference run at the server's ``max_seq``."""
    import jax

    L = int(c["deployment"]["max_seq"])
    gaps: List[float] = []
    tokens_compared = 0
    with jax.default_matmul_precision("highest"):
        fn = _served_gaps(c, control)
        w = W.make(c, W.key_for(seed, 0))
        for s in samples:
            prompt, served = s["prompt"], s["served"]
            P, T = len(prompt), len(served)
            seq = np.zeros(L, np.int32)
            seq[:P + T - 1] = np.concatenate([prompt, served[:-1]])
            pos = np.zeros(L, np.int32)
            pos[:T] = np.arange(P - 1, P - 1 + T)
            tok = np.zeros(L, np.int32)
            tok[:T] = served
            mask = np.arange(L) < T
            gaps.append(float(fn(w, seq, pos, tok, mask)))
            tokens_compared += T
    return {"logit_gap": max(gaps) if gaps else float("inf"),
            "tokens_compared": tokens_compared,
            "requests_compared": len(samples)}


def judge(readings: Dict[str, Any], limits: Dict[str, float]
          ) -> Dict[str, Dict[str, float]]:
    """Each compared number beside its limit."""
    return {k: {"value": float(readings[k]), "limit": float(v)}
            for k, v in limits.items()}


def correct(checks: Dict[str, Dict[str, float]]) -> bool:
    return all(np.isfinite(x["value"]) and x["value"] <= x["limit"]
               for x in checks.values())
