"""Pluggable kernel-backend registry for fused-region lowering (§5.4).

The paper attributes much of the single-device performance story to
"optimized libraries for kernel implementations" selected per device.
This module is that mechanism for *fused regions*: a registry mapping
(subgraph pattern, device kind) -> backend kernel, consulted once per
region by :func:`repro.core.lowering.lower_region`.  Each registered
:class:`KernelRule` pattern-matches a recognized idiom inside the region
(a MatMul, the rmsnorm chain emitted by ``GraphBuilder.rmsnorm``, the
softmax-attention chain, the SSDScan op) and rewrites its anchor node
onto one of the hand-written Pallas entry points in
:mod:`repro.kernels.ops` — ``interpret=True`` on CPU pools, compiled on
TPU.  Anything that does not match, or whose shapes the kernel cannot
take (checked at trace time), falls back to the generic jnp path.

Backends are named ("generic", "pallas") and join the RunSignature via
``Session(backend=...)`` / ``REPRO_KERNEL_BACKEND`` so flipping backends
never reuses a stale Executable.  Dispatch/fallback counters are bumped
at trace time — once per compiled region signature — so benchmarks and
the parity gate can assert the Pallas path actually ran (DESIGN.md §12).
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import ops as ops_mod
from .graph import Graph, Node, TensorRef
from ..obs.metrics import StatsDict


class BackendError(ValueError):
    """Unknown backend name (subclasses ValueError for Session plumbing)."""


def _interpret() -> bool:
    # On a TPU the kernels compile to Mosaic; on the CPU (tests, host
    # worker pools) they run in interpret mode.  No other backend can run
    # them, and quietly interpreting there would hide that.
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise BackendError(f"the Pallas kernels run on tpu (compiled) or "
                           f"cpu (interpreted), not on {backend!r}")
    return backend == "cpu"


def _feasible(*dims: int, block: int = 128) -> bool:
    # Every Pallas kernel clamps its block to min(block, dim) and then
    # requires dim % block == 0 — so any dim <= block is automatically
    # fine and larger dims must tile evenly.
    return all(d > 0 and (d <= block or d % block == 0) for d in dims)


def _is_float(x: Any) -> bool:
    return jnp.issubdtype(jnp.asarray(x).dtype if not hasattr(x, "dtype")
                          else x.dtype, jnp.floating)


# ---------------------------------------------------------------------------
# Registry types


@dataclasses.dataclass
class Match:
    """A recognized idiom: ``anchor`` is the member whose compute is
    replaced; ``leaves`` are the dataflow inputs the kernel consumes;
    ``interior`` is every member subsumed by the rewrite (claimed so it
    cannot anchor another match)."""

    rule: "KernelRule"
    anchor: str
    leaves: Dict[str, TensorRef]
    params: Dict[str, Any]
    interior: Set[str]


@dataclasses.dataclass(frozen=True)
class KernelRule:
    """One (pattern -> kernel) rewrite.

    ``matcher(g, anchor_name, members)`` inspects graph structure only
    (no shapes — those are unknown until trace time) and returns a Match
    or None.  ``emit(match, vals, device_kind)`` runs at trace time with
    the leaf values (tracers), re-checks shape/dtype feasibility, and
    returns the kernel output array — or None to fall back to the
    generic path for this anchor.
    """

    name: str
    anchor_op: str
    matcher: Callable[[Graph, str, Set[str]], Optional[Match]]
    emit: Callable[[Match, Dict[str, Any], str], Optional[Any]]


@dataclasses.dataclass(frozen=True)
class KernelBackend:
    name: str
    rules: Tuple[KernelRule, ...]
    device_kinds: Tuple[str, ...] = ("cpu", "gpu", "tpu")


BACKENDS: Dict[str, KernelBackend] = {}


def register_backend(backend: KernelBackend) -> KernelBackend:
    BACKENDS[backend.name] = backend
    return backend


def get_backend(name: str) -> KernelBackend:
    try:
        return BACKENDS[name]
    except KeyError:
        raise BackendError(
            f"unknown kernel backend {name!r}; available: "
            f"{sorted(BACKENDS)}") from None


def available_backends() -> List[str]:
    return sorted(BACKENDS)


# ---------------------------------------------------------------------------
# Dispatch accounting (trace-time: once per compiled region signature)

_LOCK = threading.Lock()
DISPATCH: Dict[Tuple[str, str], int] = {}
# registry-backed (§16.4): same dict surface as before, but every count
# is also a ``kernel_registry.*`` counter in repro.obs.metrics.REGISTRY
STATS = StatsDict("kernel_registry",
                  keys=("planned", "matched", "dispatched", "fallbacks"))


def _bump_dispatch(backend: str, kernel: str) -> None:
    with _LOCK:
        DISPATCH[(backend, kernel)] = DISPATCH.get((backend, kernel), 0) + 1
        STATS["dispatched"] += 1


def _bump(key: str, n: int = 1) -> None:
    with _LOCK:
        STATS[key] += n


def dispatch_counts(backend: str) -> Dict[str, int]:
    with _LOCK:
        return {k: v for (b, k), v in DISPATCH.items() if b == backend}


def dispatch_total(backend: str) -> int:
    return sum(dispatch_counts(backend).values())


def reset_stats() -> None:
    with _LOCK:
        DISPATCH.clear()
        for k in STATS:
            STATS[k] = 0


# ---------------------------------------------------------------------------
# Pattern matchers.  All shape checks live in emit() — at match time we
# only see graph structure.


def _producer(g: Graph, members: Set[str], ref: TensorRef) -> Optional[Node]:
    """The in-region node producing ``ref``, or None (leaves stay refs)."""
    if ref.port != 0 or ref.node not in members:
        return None
    return g.nodes.get(ref.node)


def _const_scalar(node: Node) -> Optional[float]:
    val = np.asarray(node.attrs.get("value"))
    if val.ndim != 0:
        return None
    return float(val)


def _match_matmul(g: Graph, anchor: str, members: Set[str]) -> Optional[Match]:
    node = g.nodes[anchor]
    return Match(rule=MATMUL_RULE, anchor=anchor,
                 leaves={"a": node.inputs[0], "b": node.inputs[1]},
                 params={}, interior={anchor})


def _emit_matmul(match: Match, vals: Dict[str, Any],
                 device_kind: str) -> Optional[Any]:
    from .. import kernels
    a, b = vals["a"], vals["b"]
    if getattr(a, "ndim", None) != 2 or getattr(b, "ndim", None) != 2:
        return None
    if a.dtype != b.dtype or not _is_float(a):
        return None
    (M, K), (K2, N) = a.shape, b.shape
    if K != K2 or not _feasible(M, K, N):
        return None
    return kernels.ops.matmul(a, b, interpret=_interpret())


def _match_rmsnorm(g: Graph, anchor: str, members: Set[str]) -> Optional[Match]:
    # Mul(Mul(x, Rsqrt(Add(ReduceMean(Square(x), -1, keepdims), eps))), w)
    node = g.nodes[anchor]
    for norm_ref, w_ref in ((node.inputs[0], node.inputs[1]),
                            (node.inputs[1], node.inputs[0])):
        norm = _producer(g, members, norm_ref)
        if norm is None or norm.op != "Mul":
            continue
        for x_ref, rs_ref in ((norm.inputs[0], norm.inputs[1]),
                              (norm.inputs[1], norm.inputs[0])):
            rs = _producer(g, members, rs_ref)
            if rs is None or rs.op != "Rsqrt":
                continue
            veps = _producer(g, members, rs.inputs[0])
            if veps is None or veps.op != "Add":
                continue
            for ms_ref, eps_ref in ((veps.inputs[0], veps.inputs[1]),
                                    (veps.inputs[1], veps.inputs[0])):
                ms = _producer(g, members, ms_ref)
                epsn = _producer(g, members, eps_ref)
                if ms is None or ms.op != "ReduceMean":
                    continue
                if epsn is None or epsn.op != "Const":
                    continue
                if ms.attrs.get("axis") != -1 or not ms.attrs.get("keepdims"):
                    continue
                sq = _producer(g, members, ms.inputs[0])
                if sq is None or sq.op != "Square" or sq.inputs[0] != x_ref:
                    continue
                eps = _const_scalar(epsn)
                if eps is None:
                    continue
                return Match(
                    rule=RMSNORM_RULE, anchor=anchor,
                    leaves={"x": x_ref, "w": w_ref}, params={"eps": eps},
                    interior={anchor, norm.name, rs.name, veps.name,
                              ms.name, sq.name})
    return None


def _emit_rmsnorm(match: Match, vals: Dict[str, Any],
                  device_kind: str) -> Optional[Any]:
    from .. import kernels
    x, w = vals["x"], vals["w"]
    if getattr(w, "ndim", None) != 1 or getattr(x, "ndim", 0) < 2:
        return None
    if x.shape[-1] != w.shape[0] or not _is_float(x) or not _is_float(w):
        return None
    rows = int(np.prod(x.shape[:-1]))
    if not _feasible(rows, block=256):
        return None
    return kernels.ops.rmsnorm(x, w, eps=match.params["eps"],
                               interpret=_interpret())


def _match_attention(g: Graph, anchor: str,
                     members: Set[str]) -> Optional[Match]:
    # MatMul(SoftMax(opt-Mul(MatMul(q, kT), scale)), v)
    node = g.nodes[anchor]
    probs = _producer(g, members, node.inputs[0])
    if probs is None or probs.op != "SoftMax":
        return None
    s = _producer(g, members, probs.inputs[0])
    interior = {anchor, probs.name}
    scale = None
    if s is not None and s.op == "Mul":
        for mm_ref, sc_ref in ((s.inputs[0], s.inputs[1]),
                               (s.inputs[1], s.inputs[0])):
            mm = _producer(g, members, mm_ref)
            sc = _producer(g, members, sc_ref)
            if (mm is not None and mm.op == "MatMul"
                    and sc is not None and sc.op == "Const"):
                scale = _const_scalar(sc)
                if scale is None:
                    return None
                interior.add(s.name)
                s = mm
                break
        else:
            return None
    if s is None or s.op != "MatMul":
        return None
    interior.add(s.name)
    return Match(rule=ATTENTION_RULE, anchor=anchor,
                 leaves={"q": s.inputs[0], "kT": s.inputs[1],
                         "v": node.inputs[1]},
                 params={"scale": scale}, interior=interior)


def _emit_attention(match: Match, vals: Dict[str, Any],
                    device_kind: str) -> Optional[Any]:
    from .. import kernels
    q, kT, v = vals["q"], vals["kT"], vals["v"]
    if any(getattr(t, "ndim", None) != 2 for t in (q, kT, v)):
        return None
    if not all(_is_float(t) for t in (q, kT, v)):
        return None
    (S, D), (Dk, T), (Tv, Dv) = q.shape, kT.shape, v.shape
    if D != Dk or T != Tv or Dv != D:
        return None  # flash kernel needs v rows in the q/k feature dim
    if not _feasible(S, T):
        return None
    return kernels.ops.attention(q, kT, v, scale=match.params["scale"],
                                 interpret=_interpret())


def _match_ssd(g: Graph, anchor: str, members: Set[str]) -> Optional[Match]:
    node = g.nodes[anchor]
    names = ("x", "dt", "A_log", "Bc", "Cc", "D_skip")
    return Match(rule=SSD_RULE, anchor=anchor,
                 leaves=dict(zip(names, node.inputs)),
                 params={"chunk": int(node.attrs.get("chunk", 128))},
                 interior={anchor})


def _emit_ssd(match: Match, vals: Dict[str, Any],
              device_kind: str) -> Optional[Any]:
    from .. import kernels
    x, dt, A_log = vals["x"], vals["dt"], vals["A_log"]
    Bc, Cc, D_skip = vals["Bc"], vals["Cc"], vals["D_skip"]
    if getattr(x, "ndim", None) != 4 or getattr(Bc, "ndim", None) != 4:
        return None
    B, S, H, P = x.shape
    G = Bc.shape[2]
    if (dt.shape != (B, S, H) or A_log.shape != (H,)
            or Bc.shape[:2] != (B, S) or Cc.shape != Bc.shape
            or D_skip.shape != (H,) or G == 0 or H % G != 0):
        return None
    if not _is_float(x):
        return None
    chunk = match.params["chunk"]
    if not _feasible(S, block=min(chunk, S)):
        return None
    return kernels.ops.ssd_scan(x, dt, A_log, Bc, Cc, D_skip,
                                chunk=chunk, interpret=_interpret())


MATMUL_RULE = KernelRule("matmul", "MatMul", _match_matmul, _emit_matmul)
RMSNORM_RULE = KernelRule("rmsnorm", "Mul", _match_rmsnorm, _emit_rmsnorm)
ATTENTION_RULE = KernelRule("flash_attention", "MatMul", _match_attention,
                            _emit_attention)
SSD_RULE = KernelRule("ssd_scan", "SSDScan", _match_ssd, _emit_ssd)


# ---------------------------------------------------------------------------
# Region planning


def plan_region_overrides(
        g: Graph, members: Set[str], backend_name: str,
        device_kind: str) -> Dict[str, Callable]:
    """Match the backend's rules over a fused region's members.

    Returns {anchor_name: override(ev, node) -> outputs-tuple} for
    :class:`repro.core.lowering._Evaluator`.  Members are visited
    consumers-first (reverse insertion order ~ reverse topo within a
    region) so a composite idiom claims its interior before an interior
    node can anchor a smaller match; rules are tried in backend order
    (flash_attention before matmul — both anchor MatMul).
    """
    backend = get_backend(backend_name)
    if not backend.rules or device_kind not in backend.device_kinds:
        return {}
    _bump("planned")

    claimed: Set[str] = set()
    overrides: Dict[str, Callable] = {}
    for name in reversed(list(members)):
        if name in claimed or name in overrides:
            continue
        node = g.nodes.get(name)
        if node is None:
            continue
        for rule in backend.rules:
            if node.op != rule.anchor_op:
                continue
            match = rule.matcher(g, name, members)
            if match is None:
                continue
            _bump("matched")
            claimed |= match.interior - {name}
            overrides[name] = _make_override(backend.name, rule, match,
                                             device_kind)
            break
    return overrides


def _make_override(backend_name: str, rule: KernelRule, match: Match,
                   device_kind: str) -> Callable:
    def override(ev: Any, node: Node) -> Tuple[Any, ...]:
        vals = {k: ev.value(r) for k, r in match.leaves.items()}
        out = rule.emit(match, vals, device_kind)
        if out is None:
            # shapes/dtypes the kernel cannot take: generic fallback
            _bump("fallbacks")
            ins = [ev.value(r) for r in node.inputs]
            return ops_mod.opdef(node.op).compute(ev.state, node, *ins)
        _bump_dispatch(backend_name, rule.name)
        return (out,)

    return override


# ---------------------------------------------------------------------------
# Built-in backends.  "generic" is the identity backend (no rewrites);
# "pallas" dispatches onto the hand-written kernels.  Rule order matters:
# flash_attention must precede matmul (both anchor MatMul).

register_backend(KernelBackend("generic", rules=()))
register_backend(KernelBackend(
    "pallas",
    rules=(ATTENTION_RULE, MATMUL_RULE, RMSNORM_RULE, SSD_RULE)))
