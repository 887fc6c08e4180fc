"""Tolerance-gated numerics parity (DESIGN.md §9).

PR 2's region fusion shipped with a bit-parity contract: fused regions
compile at XLA backend-opt-level 0 and accumulation-order-sensitive ops
(MatMul, reductions, ``Call``) stay eagerly dispatched, so fused ==
unfused bit-for-bit.  That leaves most of the paper's "compile subgraphs
into efficient kernels" win (§3.3/§4; TF-OSDI'16 accepts reassociation
drift for fused kernels) on the table.  ``numerics="fast"`` fuses
everything at full XLA optimization — and *this module is the contract
that makes fast mode safe*:

* a per-op-class tolerance table (ULP + relative, either satisfies);
* a suite of representative parity cases — matmul chains, residual
  towers, softmax/layernorm reductions, a multi-device partitioned
  step, a while-loop body, a ``Call`` train step — each executed
  fused-fast and unfused-strict on identical feeds/state;
* a structured :class:`ParityReport` of the max observed drift per op
  class, breaching if any element of any fetch/variable exceeds *both*
  bounds of its class tolerance;
* a CLI gate (``python -m repro.core.numerics --gate``) that CI runs on
  every PR, so the tolerance table is re-proven continuously (the
  pytest marker ``paritygate`` wraps the same suite).

The Session-level counterpart lives in ``executable.Executable``: a
fast-mode Executable verifies its first run against the unfused-strict
reference with :func:`compare` and falls back to strict execution (with
a warning) on a breach.

Comparison semantics: an element passes if its ULP distance is within
``Tolerance.ulp`` **or** its *scale-relative* error — ``|a-b|`` divided
by the larger array's max magnitude, the ``np.allclose`` convention with
``atol = rtol * amax`` — is within ``Tolerance.rel``.  ULP is the
natural unit for well-scaled floats; the scale-relative bound absorbs
near-zero elements (tiny gradients, optimizer second moments) where one
reassociated rounding step is enormous relative to *that element* but
meaningless relative to the tensor.  Non-float values (ints, bools,
shapes) must match exactly.
"""
from __future__ import annotations

import dataclasses
import json
import sys
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

# ---------------------------------------------------------------------------
# op classes and the tolerance table


#: op -> op class; anything unlisted is "elementwise" (order-insensitive
#: elementwise / data-movement ops, whose only fast-mode drift source is
#: cross-op FMA contraction).
OP_CLASSES: Dict[str, str] = {
    "MatMul": "matmul",
    "ReduceSum": "reduction",
    "ReduceMean": "reduction",
    "SoftMax": "softmax",
    "SoftmaxXent": "softmax",
    "SSDScan": "scan",
    "Call": "call",
}

#: op classes with no float output to drift: compared exactly, and they
#: contribute no tolerance of their own.
_EXACT_OPS = {
    "Const", "Placeholder", "Variable", "Shape", "Rank", "NoOp",
    "Identity", "Switch", "Merge", "Enter", "Exit", "NextIteration",
    "LoopCond", "Send", "Recv", "FusedRegion",
}


@dataclasses.dataclass(frozen=True)
class Tolerance:
    """Max allowed drift for one op class: ULP distance OR relative error
    (an element within either bound passes)."""

    ulp: float
    rel: float

    def __or__(self, other: "Tolerance") -> "Tolerance":
        return Tolerance(ulp=max(self.ulp, other.ulp),
                         rel=max(self.rel, other.rel))

    def __str__(self) -> str:  # for warnings/reports
        return f"(ulp<={self.ulp:g} | rel<={self.rel:g})"


#: The §9 base tolerance table (fp32-calibrated; see DESIGN.md §9 for the
#: derivation).  Bounds are the observed fast-vs-strict drift of the
#: parity suite with ~8-32x headroom, not theoretical worst cases — the
#: CI gate exists precisely to catch the day an XLA upgrade blows past
#: them, at which point the table is re-negotiated consciously.
_BASE: Dict[str, Tolerance] = {
    # FMA contraction on mul->add chains: each fused pair is <= 1 ulp off,
    # chains compound a handful of ulps
    "elementwise": Tolerance(ulp=32, rel=1e-6),
    # vectorized partial sums vs linear accumulation: O(log n) reassociation
    "reduction": Tolerance(ulp=256, rel=1e-5),
    # dot reassociation + FMA over the contraction dim, compounding
    # through chained layers
    "matmul": Tolerance(ulp=512, rel=1e-5),
    # exp/log rewrites + a reduction in the denominator; xent adds a log
    "softmax": Tolerance(ulp=1024, rel=1e-4),
    # order-sensitive recurrent scans (SSDScan): sequential f32 reference
    # vs XLA's fused scan body
    "scan": Tolerance(ulp=1024, rel=1e-4),
    # user closures: arbitrary compositions of the above
    "call": Tolerance(ulp=2048, rel=1e-4),
}

#: Per-device-kind tolerance tables (DESIGN.md §12).  CPU/GPU XLA share
#: the fp32 calibration; TPU loosens the accumulation-sensitive classes
#: (MXU partial-sum shapes and bf16-internal rewrites differ from the
#: host backends — provisional until calibrated on real hardware).  On
#: v5e an f32 matmul at the default precision takes one bf16 pass
#: (relative error 2.3e-3 at depth 1024), and fused and unfused
#: execution round at different points: a full-width smollm-360m train
#: step (a ``call``) drifted 8.4e-3 of its scale.  Readings in PERF.md.
TOLERANCES: Dict[str, Dict[str, Tolerance]] = {
    "cpu": dict(_BASE),
    "gpu": dict(_BASE),
    "tpu": {**_BASE,
            "reduction": Tolerance(ulp=512, rel=2e-5),
            "matmul": Tolerance(ulp=1024, rel=2e-5),
            "softmax": Tolerance(ulp=2048, rel=2e-4),
            "scan": Tolerance(ulp=2048, rel=2e-4),
            "call": Tolerance(ulp=2048, rel=5e-2)},
}

#: Per-backend calibration overlays, merged (loosest-wins) onto the
#: device-kind table.  The Pallas kernels legitimately reassociate more
#: than generic XLA: the matmul K-loop accumulates in f32 VMEM scratch
#: blockwise, flash attention's online softmax rescales the accumulator
#: once per KV block, and the SSD scan replaces the sequential recurrence
#: with a chunked cumsum/segment-matmul algorithm.  Bounds are observed
#: pallas-vs-strict drift of the parity suite with the same ~8-32x
#: headroom policy as the base table (calibration procedure: DESIGN.md
#: §12).
BACKEND_CALIBRATION: Dict[str, Dict[str, Tolerance]] = {
    "generic": {},
    "pallas": {
        "reduction": Tolerance(ulp=1024, rel=1e-4),
        "matmul": Tolerance(ulp=1024, rel=2e-5),
        "softmax": Tolerance(ulp=4096, rel=5e-4),
        "scan": Tolerance(ulp=4096, rel=5e-4),
        "call": Tolerance(ulp=4096, rel=5e-4),
    },
}


def tolerance_table(device_kind: str = "cpu",
                    backend: str = "generic") -> Dict[str, Tolerance]:
    """The effective per-class table for one (device kind, backend).
    A device kind with no calibrated table is an error, not the CPU's."""
    if device_kind not in TOLERANCES:
        raise KeyError(f"no tolerance table for device kind {device_kind!r}; "
                       f"calibrated kinds: {sorted(TOLERANCES)}")
    table = dict(TOLERANCES[device_kind])
    for cls, tol in BACKEND_CALIBRATION.get(backend, {}).items():
        table[cls] = table.get(cls, tol) | tol
    return table


#: The output class of an optimizer's updated parameters.  A node declares
#: it per output port: ``attrs["numerics_class"] = ("optimizer", ...)``.
OPTIMIZER = "optimizer"

#: Bound on the update drift (:func:`update_drift`) of a variable written
#: by an optimizer output, per device kind.  Elementwise bounds cannot
#: judge such a variable: AdamW's step m̂/(√v̂+ε) has slope up to 1/ε in
#: a near-zero gradient, so reassociation noise of 1e-9 in one element
#: moves its parameter by up to 2·lr, and a bound that admits that admits
#: a wrong update of the whole leaf.  Judged by norm, k flipped elements
#: of n give 2·√(k/n), while a missing, doubled or negated update gives
#: 1, 1 or 2.  Observed fast-vs-strict update drift of a smollm-360m
#: train step: up to 3.9e-5 on the CPU, 0.084 on v5e, where each f32
#: matmul takes one bf16 pass (readings in PERF.md).
UPDATE_TOLERANCE: Dict[str, float] = {"cpu": 0.05, "gpu": 0.05, "tpu": 0.25}


def update_tolerance(device_kinds: Iterable[str]) -> float:
    """The loosest update-drift bound over ``device_kinds``; an unknown
    kind is an error, as in :func:`tolerance_table`."""
    bound = 0.0
    for kind in device_kinds:
        if kind not in UPDATE_TOLERANCE:
            raise KeyError(f"no update tolerance for device kind {kind!r}; "
                           f"calibrated kinds: {sorted(UPDATE_TOLERANCE)}")
        bound = max(bound, UPDATE_TOLERANCE[kind])
    return bound


def optimizer_written_variables(g: Any, names: Iterable[str]) -> set:
    """Variables that an Assign in ``names`` sets from an output declared
    ``"optimizer"`` (see :data:`OPTIMIZER`)."""
    out = set()
    for n in names:
        node = g.nodes[n]
        if node.op != "Assign" or len(node.inputs) < 2:
            continue
        src = node.inputs[1]
        classes = g.nodes[src.node].attrs.get("numerics_class", ())
        if src.port < len(classes) and classes[src.port] == OPTIMIZER:
            out.add(node.inputs[0].node)
    return out


def update_drift(before: Any, ref: Any, got: Any,
                 got_before: Any = None) -> float:
    """max over leaves of ||Δgot − Δref|| / ||Δref||, where Δ is the value
    after a step minus the value before it (``got_before`` defaults to
    ``before``).  Infinite on a non-finite value or on a change where the
    reference has none."""
    import jax

    got_before = before if got_before is None else got_before
    worst = 0.0
    for b, r, g, gb in zip(*(jax.tree.leaves(t)
                             for t in (before, ref, got, got_before))):
        r64, g64 = np.asarray(r, np.float64), np.asarray(g, np.float64)
        d_ref = r64 - np.asarray(b, np.float64)
        d_got = g64 - np.asarray(gb, np.float64)
        if not (np.isfinite(r64).all() and np.isfinite(g64).all()):
            return float("inf")
        num, den = np.linalg.norm(d_got - d_ref), np.linalg.norm(d_ref)
        if num:
            worst = max(worst, float(num / den) if den else float("inf"))
    return worst


def op_class(op: str) -> Optional[str]:
    """The tolerance class of ``op`` (None for exact/structural ops)."""
    if op in OP_CLASSES:
        return OP_CLASSES[op]
    if op in _EXACT_OPS:
        return None
    return "elementwise"


def tolerance_for_classes(classes: Iterable[str], device_kind: str = "cpu",
                          backend: str = "generic") -> Tolerance:
    table = tolerance_table(device_kind, backend)
    tol = table["elementwise"]
    for c in classes:
        tol = tol | table[c]
    return tol


def tolerance_for_ops(ops: Iterable[str],
                      device_kinds: Iterable[str] = ("cpu",),
                      backend: str = "generic") -> Tolerance:
    """The merged tolerance for a graph containing ``ops`` — the loosest
    bound among the op classes present, across every device kind the
    graph runs on (used by the Session-level guard, which sees whole
    executables, not per-class fetches)."""
    classes = [c for c in (op_class(op) for op in set(ops)) if c is not None]
    tol: Optional[Tolerance] = None
    for kind in device_kinds:
        t = tolerance_for_classes(classes, kind, backend)
        tol = t if tol is None else (tol | t)
    return tol if tol is not None else tolerance_for_classes(classes)


# ---------------------------------------------------------------------------
# drift measurement


@dataclasses.dataclass(frozen=True)
class Drift:
    """Max observed divergence: ULP distance and relative error (each the
    max over all compared elements — possibly different elements)."""

    ulp: float = 0.0
    rel: float = 0.0

    def __or__(self, other: "Drift") -> "Drift":
        return Drift(ulp=max(self.ulp, other.ulp), rel=max(self.rel, other.rel))

    def __str__(self) -> str:
        return f"(ulp={self.ulp:g}, rel={self.rel:g})"


_EXACT_MISMATCH = Drift(ulp=float("inf"), rel=float("inf"))


def _is_float_dtype(dt: np.dtype) -> bool:
    """True for numpy floats AND the ml_dtypes extended floats (bfloat16,
    fp8) jax uses — ``np.issubdtype`` alone misclassifies those as
    non-float, which would exact-compare them (1 ULP => infinite drift)."""
    if np.issubdtype(dt, np.floating):
        return True
    try:
        import jax.numpy as jnp

        return bool(jnp.issubdtype(dt, jnp.floating))
    except Exception:  # noqa: BLE001 — unknown custom dtype: exact-compare
        return False


def _effective_ulp(ulp: float, dt: np.dtype) -> float:
    """Scale an fp32-calibrated ULP bound to ``dt``'s resolution.

    The TOLERANCES table is calibrated in fp32 ULPs (23-bit mantissa).
    In a narrower format the same *value* drift spans proportionally
    fewer ULPs — carrying 2048 fp32-ULPs over to bfloat16 (7-bit
    mantissa) would span ~16 binades and make the bound vacuous.  Floor
    of 8: reassociation legitimately moves a few ULPs in any format.
    """
    try:
        nmant = int(np.finfo(dt).nmant)
    except ValueError:
        try:  # ml_dtypes extended floats need their own finfo
            import ml_dtypes

            nmant = int(ml_dtypes.finfo(dt).nmant)
        except (ImportError, ValueError):
            return ulp
    if nmant >= 23:
        return ulp  # f32/f64: the calibrated unit
    return max(8.0, ulp / float(2 ** (23 - nmant)))


def _canonical_bits(a: np.ndarray) -> np.ndarray:
    """Map float bit patterns to a monotone integer line: adjacent floats
    differ by exactly 1, ``-0.0`` and ``+0.0`` coincide."""
    int_t = {1: np.int8, 2: np.int16, 4: np.int32, 8: np.int64}[a.dtype.itemsize]
    i = a.view(int_t).astype(np.int64)
    min_i = np.int64(-(2 ** (8 * a.dtype.itemsize - 1)))
    return np.where(i >= 0, i, min_i - i)


def ulp_distance(a: Any, b: Any) -> np.ndarray:
    """Elementwise ULP distance between two same-dtype float arrays
    (float64-valued: distances beyond 2**53 saturate approximately, which
    is far past any tolerance anyway)."""
    a = np.asarray(a)
    b = np.asarray(b)
    d = np.abs(_canonical_bits(a).astype(np.float64)
               - _canonical_bits(b).astype(np.float64))
    both_nan = np.isnan(a) & np.isnan(b)
    either_nan = np.isnan(a) | np.isnan(b)
    d = np.where(both_nan, 0.0, np.where(either_nan, np.inf, d))
    return d


def _leaves(x: Any) -> List[Any]:
    import jax

    return jax.tree.leaves(x)


def leaf_drift(ref: Any, got: Any) -> Tuple[Drift, np.ndarray]:
    """Drift of one array-ish leaf pair; returns (max drift, elementwise
    pass-relevant ulp array) — non-float or mismatched leaves are
    exact-compared and report infinite drift on mismatch."""
    if ref is None or got is None:
        ok = ref is None and got is None
        return (Drift() if ok else _EXACT_MISMATCH), np.zeros(())
    r = np.asarray(ref)
    g = np.asarray(got)
    if r.shape != g.shape or r.dtype != g.dtype:
        return _EXACT_MISMATCH, np.full((), np.inf)
    if not _is_float_dtype(r.dtype):
        ok = bool(np.array_equal(r, g))
        return (Drift() if ok else _EXACT_MISMATCH), np.zeros(())
    ulp = ulp_distance(r, g)
    rel = _scaled_rel(r, g)
    return Drift(ulp=float(np.max(ulp, initial=0.0)),
                 rel=float(np.max(rel, initial=0.0))), ulp


def _scaled_rel(r: np.ndarray, g: np.ndarray) -> np.ndarray:
    """Elementwise scale-relative error: |r-g| over the pair's max
    magnitude (the allclose atol=rtol*amax convention — near-zero
    elements are judged against the tensor's scale, not their own)."""
    rf = r.astype(np.float64)
    gf = g.astype(np.float64)
    finite_max = 0.0
    for a in (rf, gf):
        fin = a[np.isfinite(a)]
        if fin.size:
            finite_max = max(finite_max, float(np.max(np.abs(fin))))
    denom = max(finite_max, float(np.finfo(np.float64).tiny))
    with np.errstate(invalid="ignore"):
        rel = np.abs(rf - gf) / denom
    both_nan = np.isnan(rf) & np.isnan(gf)
    either_nan = np.isnan(rf) | np.isnan(gf)
    return np.where(both_nan, 0.0, np.where(either_nan, np.inf, rel))


def compare(ref: Any, got: Any, tol: Tolerance) -> Tuple[bool, Drift]:
    """Pytree-aware comparison.  Returns (ok, max drift); ``ok`` is the
    *elementwise* either-criterion — every element must be within
    ``tol.ulp`` ULPs or within ``tol.rel`` scale-relative error."""
    import jax

    # structure check (not just leaf count): jax drops None subtrees from
    # the leaf list, so [None, x] vs [x] would otherwise look identical
    if jax.tree.structure(ref) != jax.tree.structure(got):
        return False, _EXACT_MISMATCH
    ref_leaves = _leaves(ref)
    got_leaves = _leaves(got)
    if len(ref_leaves) != len(got_leaves):
        return False, _EXACT_MISMATCH
    ok = True
    drift = Drift()
    for r, g in zip(ref_leaves, got_leaves):
        d, ulp = leaf_drift(r, g)
        drift = drift | d
        ra = np.asarray(r) if r is not None else None
        is_float = (ra is not None and g is not None
                    and np.asarray(g).shape == ra.shape
                    and np.asarray(g).dtype == ra.dtype
                    and _is_float_dtype(ra.dtype))
        # the ULP bound is fp32-calibrated; judge each leaf in its own
        # dtype's resolution (bf16 ULPs are ~65536x coarser)
        eff_ulp = _effective_ulp(tol.ulp, ra.dtype) if is_float else tol.ulp
        if d.ulp <= eff_ulp or d.rel <= tol.rel:
            continue  # whole leaf within one of the bounds
        if not is_float:
            ok = False  # exact-compare leaf mismatched: no elementwise rescue
            continue
        # mixed leaf: some elements ulp-close, the rest scale-close —
        # re-check the either-criterion per element
        rel = _scaled_rel(ra, np.asarray(g))
        if not bool(np.all((ulp <= eff_ulp) | (rel <= tol.rel))):
            ok = False
    return ok, drift


# NOTE: there is deliberately no aggregate `within(drift, tol)` helper —
# a pytree's max ULP and max rel can come from different tensors that
# each pass on their own bound, so any comparator must go through
# :func:`compare`'s elementwise either-criterion.


# ---------------------------------------------------------------------------
# the parity-case suite


@dataclasses.dataclass
class ParityCase:
    """One representative graph executed fused-fast vs unfused-strict.

    ``build(b)`` constructs the graph on a fresh ``GraphBuilder`` and
    returns a dict of named handles; ``fetches(extras)`` the fetch list;
    ``feeds(extras, step)`` per-run feed dict (or None); ``fetch_classes``
    the op class gating each fetch positionally; ``must_fuse_ops`` ops
    that MUST end up inside a fused region in fast mode — the gate fails
    if they stay eager, so it can never pass vacuously.
    """

    name: str
    build: Callable[[Any], Dict[str, Any]]
    fetches: Callable[[Dict[str, Any]], List[Any]]
    fetch_classes: Tuple[str, ...]
    feeds: Optional[Callable[[Dict[str, Any], int], Dict[Any, Any]]] = None
    devices: Optional[Callable[[], Any]] = None
    var_class: str = "elementwise"
    n_runs: int = 3
    must_fuse_ops: Tuple[str, ...] = ()


def _rng(case_seed: int, step: int) -> np.random.RandomState:
    return np.random.RandomState(1_000_003 * case_seed + step)


def _case_matmul_chain() -> ParityCase:
    """Deep residual matmul chain — dot reassociation + FMA compounding
    through layers (the §3.3 'compile subgraphs' headline shape)."""
    import jax.numpy as jnp

    n_layers = 8

    def build(b):
        rs = _rng(1, 0)
        W = b.constant(jnp.asarray(rs.randn(96, 96).astype("f") * 0.1),
                       name="W")
        x = b.placeholder("x")
        cur = x
        for i in range(n_layers):
            h = b.matmul(cur, W, name=f"mm{i}")
            cur = b.relu(b.add(h, cur, name=f"res{i}"), name=f"r{i}")
        total = b.reduce_sum(cur, name="total")
        return {"x": x, "out": cur, "total": total}

    def feeds(ex, step):
        import jax.numpy as jnp

        rs = _rng(1, step + 1)
        return {ex["x"].ref: jnp.asarray(rs.randn(32, 96).astype("f"))}

    return ParityCase(
        name="matmul_chain", build=build,
        fetches=lambda ex: [ex["out"].ref, ex["total"].ref],
        fetch_classes=("matmul", "reduction"),
        feeds=feeds, must_fuse_ops=("MatMul", "ReduceSum"))


def _case_residual_tower() -> ParityCase:
    """Elementwise mul->add tower: pure FMA-contraction bait."""

    def build(b):
        x = b.placeholder("x")
        w = b.placeholder("w")
        cur = x
        for i in range(24):
            cur = b.add(b.mul(cur, w, name=f"fm{i}"), x, name=f"fa{i}")
        return {"x": x, "w": w, "out": cur}

    def feeds(ex, step):
        import jax.numpy as jnp

        rs = _rng(2, step)
        return {ex["x"].ref: jnp.asarray(rs.randn(257).astype("f")),
                ex["w"].ref: jnp.asarray(rs.randn(257).astype("f") * 0.5)}

    return ParityCase(
        name="residual_tower", build=build,
        fetches=lambda ex: [ex["out"].ref],
        fetch_classes=("elementwise",), feeds=feeds,
        must_fuse_ops=("Mul", "Add"))


def _case_softmax_layernorm() -> ParityCase:
    """Softmax + a hand-built layernorm: reductions in denominators,
    exp/log rewrites, rsqrt — the transformer-block numerics."""
    import jax.numpy as jnp

    def build(b):
        x = b.placeholder("x")
        labels = b.placeholder("labels")
        # layernorm(x) = (x - mean) / sqrt(var + eps)
        mu = b.reduce_mean(x, axis=-1, name="mu")
        cen = b.sub(x, b.reshape(mu, (16, 1), name="mu_col"), name="cen")
        var = b.reduce_mean(b.square(cen, name="cen2"), axis=-1, name="var")
        eps = b.constant(jnp.float32(1e-5), name="eps")
        denom = b.reshape(
            b.exp(b.mul(b.log(b.add(var, eps, name="veps"), name="lv"),
                        b.constant(jnp.float32(0.5), name="half"),
                        name="hl"), name="rootv"),
            (16, 1), name="denom")
        ln = b.div(cen, denom, name="ln")
        sm = b.softmax(ln, name="sm")
        xent = b.softmax_xent(ln, labels, name="xent")
        return {"x": x, "labels": labels, "ln": ln, "sm": sm, "xent": xent}

    def feeds(ex, step):
        import jax.numpy as jnp

        rs = _rng(3, step)
        return {ex["x"].ref: jnp.asarray(rs.randn(16, 64).astype("f") * 3.0),
                ex["labels"].ref: jnp.asarray(
                    rs.randint(0, 64, 16).astype(np.int32))}

    return ParityCase(
        name="softmax_layernorm", build=build,
        fetches=lambda ex: [ex["ln"].ref, ex["sm"].ref, ex["xent"].ref],
        fetch_classes=("reduction", "softmax", "softmax"),
        feeds=feeds, must_fuse_ops=("SoftMax", "SoftmaxXent", "ReduceMean"))


def _case_multi_device_step() -> ParityCase:
    """2-worker partitioned step: matmuls/reductions fusing on each side
    of Send/Recv cut edges (the b13 shape, with real contraction ops)."""
    import jax.numpy as jnp

    def build(b):
        rs = _rng(4, 0)
        remotes = [
            b.constant(jnp.asarray(rs.randn(24, 24).astype("f") * 0.2),
                       name=f"r{i}", device="/job:worker/task:0")
            for i in range(4)]
        seed = b.placeholder("seed")
        cur = seed
        for i, r in enumerate(remotes):
            mm = b.matmul(cur, r, name=f"mm{i}", device="/job:worker/task:1")
            cur = b.add(mm, cur, name=f"acc{i}", device="/job:worker/task:1")
        out = b.reduce_sum(cur, name="out", device="/job:worker/task:1")
        back = b.reduce_mean(b.square(cur, name="sq",
                                      device="/job:worker/task:0"),
                             name="back", device="/job:worker/task:0")
        return {"seed": seed, "out": out, "back": back}

    def feeds(ex, step):
        import jax.numpy as jnp

        rs = _rng(4, step + 1)
        return {ex["seed"].ref: jnp.asarray(rs.randn(24, 24).astype("f"))}

    def devices():
        from ..runtime.devices import DeviceSet

        return DeviceSet.make_cluster(2, 1, kind="cpu")

    return ParityCase(
        name="multi_device_step", build=build,
        fetches=lambda ex: [ex["out"].ref, ex["back"].ref],
        fetch_classes=("reduction", "reduction"),
        feeds=feeds, devices=devices, must_fuse_ops=("MatMul",))


def _case_while_loop_body() -> ParityCase:
    """A while loop whose surrounding pre/post-compute fuses while the
    frame stays interpreted; the loop body itself does matmul work."""
    import jax.numpy as jnp

    def build(b):
        from .control_flow import while_loop

        rs = _rng(5, 0)
        W = b.constant(jnp.asarray(rs.randn(16, 16).astype("f") * 0.2),
                       name="W")
        x = b.placeholder("x")
        pre = b.relu(b.matmul(x, W, name="premm"), name="pre")
        lim = b.constant(jnp.asarray(4), name="lim")
        one = b.constant(jnp.asarray(1), name="one")
        i0 = b.constant(jnp.asarray(0), name="i0")
        outs = while_loop(
            b, lambda i, a: b.less(i, lim),
            lambda i, a: [b.add(i, one, name="inc"),
                          b.add(b.matmul(a, W, name="bodymm"), a,
                                name="bodyacc")],
            [i0, pre])
        post = b.reduce_sum(b.mul(outs[1], outs[1], name="postsq"),
                            name="post")
        return {"x": x, "loop_out": outs[1], "post": post}

    def feeds(ex, step):
        import jax.numpy as jnp

        rs = _rng(5, step + 1)
        return {ex["x"].ref: jnp.asarray(rs.randn(8, 16).astype("f"))}

    return ParityCase(
        name="while_loop_body", build=build,
        fetches=lambda ex: [ex["loop_out"], ex["post"].ref],
        fetch_classes=("matmul", "reduction"),
        feeds=feeds, must_fuse_ops=("MatMul",))


def _case_call_train_step() -> ParityCase:
    """A ``Call`` closure (the eager train/serve step shape) plus a
    variable read-modify-write — Call closures join regions in fast mode
    and variable commits must still match the reference."""
    import jax.numpy as jnp

    def loss_fn(W, x, y):
        import jax.numpy as jnp

        p = x @ W
        d = p - y
        return (jnp.mean(d * d),)

    def build(b):
        v = b.variable("v", init_value=lambda: jnp.full((4, 1), 0.25,
                                                        jnp.float32))
        x = b.placeholder("x")
        y = b.placeholder("y")
        loss = b.call(loss_fn, [v, x, y], name="loss", n_out=1)
        upd = b.assign_add(v, b.constant(jnp.full((4, 1), 0.01, jnp.float32),
                                         name="delta"))
        return {"x": x, "y": y, "loss": loss, "upd": upd}

    def feeds(ex, step):
        import jax.numpy as jnp

        rs = _rng(6, step)
        return {ex["x"].ref: jnp.asarray(rs.randn(8, 4).astype("f")),
                ex["y"].ref: jnp.asarray(rs.randn(8, 1).astype("f"))}

    return ParityCase(
        name="call_train_step", build=build,
        fetches=lambda ex: [ex["loss"].output(0), ex["upd"].ref],
        fetch_classes=("call", "elementwise"),
        feeds=feeds, var_class="call", n_runs=4, must_fuse_ops=("Call",))


def _case_lm_kernels() -> ParityCase:
    """The registry-matchable LM idioms (rmsnorm, scaled attention, SSD
    scan) built from primitive ops — under ``--backend pallas`` the fused
    candidate dispatches the hand-written kernels for all of them, under
    ``generic`` they lower through plain XLA (DESIGN.md §12)."""
    import jax.numpy as jnp

    def build(b):
        rs = _rng(7, 0)
        x = b.placeholder("x")        # (64, 32)
        kT = b.placeholder("kT")      # (32, 64)
        v = b.placeholder("v")        # (64, 32)
        w = b.constant(jnp.asarray(np.abs(rs.randn(32)).astype("f") + 0.5),
                       name="w")
        Wq = b.constant(jnp.asarray(rs.randn(32, 32).astype("f") * 0.2),
                        name="Wq")
        xn = b.rmsnorm(x, w, name="xn")
        q = b.matmul(xn, Wq, name="q")
        att = b.attention(q, kT, v, scale=0.125, name="att")
        y = b.add(att, x, name="y")
        sx = b.placeholder("sx")      # (1, 64, 2, 16)
        sdt = b.placeholder("sdt")    # (1, 64, 2)
        A_log = b.constant(jnp.asarray(rs.randn(2).astype("f") * 0.1),
                           name="A_log")
        sB = b.placeholder("sB")      # (1, 64, 1, 8)
        sC = b.placeholder("sC")
        D_skip = b.constant(jnp.asarray(rs.randn(2).astype("f") * 0.1),
                            name="D_skip")
        sy = b.ssd_scan(sx, sdt, A_log, sB, sC, D_skip, name="ssd")
        tot = b.reduce_sum(sy, name="tot")
        return {"x": x, "kT": kT, "v": v, "sx": sx, "sdt": sdt,
                "sB": sB, "sC": sC, "y": y, "sy": sy, "tot": tot}

    def feeds(ex, step):
        import jax.numpy as jnp

        rs = _rng(7, step + 1)
        return {
            ex["x"].ref: jnp.asarray(rs.randn(64, 32).astype("f")),
            ex["kT"].ref: jnp.asarray(rs.randn(32, 64).astype("f")),
            ex["v"].ref: jnp.asarray(rs.randn(64, 32).astype("f")),
            ex["sx"].ref: jnp.asarray(rs.randn(1, 64, 2, 16).astype("f")),
            ex["sdt"].ref: jnp.asarray(
                np.abs(rs.randn(1, 64, 2)).astype("f") * 0.1),
            ex["sB"].ref: jnp.asarray(rs.randn(1, 64, 1, 8).astype("f")),
            ex["sC"].ref: jnp.asarray(rs.randn(1, 64, 1, 8).astype("f")),
        }

    return ParityCase(
        name="lm_kernels", build=build,
        fetches=lambda ex: [ex["y"].ref, ex["sy"].ref, ex["tot"].ref],
        fetch_classes=("softmax", "scan", "scan"),
        feeds=feeds,
        must_fuse_ops=("MatMul", "SoftMax", "SSDScan", "Rsqrt"))


def _case_adamw_step() -> ParityCase:
    """A matmul model trained by the graph AdamW of
    ``optim.attach_train_op``: the parameter it rewrites is judged by its
    update drift, the moments by the per-class tolerance."""
    import jax.numpy as jnp

    def build(b):
        from ..optim.optimizers import attach_train_op

        w0 = jnp.asarray(_rng(8, 0).randn(32, 8).astype("f") * 0.2)
        W = b.variable("W", init_value=lambda: w0)
        x = b.placeholder("x")
        y = b.placeholder("y")
        d = b.sub(b.matmul(x, W, name="h"), y, name="d")
        loss = b.reduce_mean(b.square(d, name="d2"), name="loss")
        train = attach_train_op(b, loss, [W], optimizer="adamw", lr=1e-2)
        return {"x": x, "y": y, "loss": loss, "train": train}

    def feeds(ex, step):
        import jax.numpy as jnp

        rs = _rng(8, step + 1)
        return {ex["x"].ref: jnp.asarray(rs.randn(64, 32).astype("f")),
                ex["y"].ref: jnp.asarray(rs.randn(64, 8).astype("f"))}

    return ParityCase(
        name="adamw_step", build=build,
        fetches=lambda ex: [ex["loss"].ref, ex["train"].ref],
        fetch_classes=("reduction", "elementwise"),
        feeds=feeds, var_class="call", must_fuse_ops=("MatMul", "Call"))


def default_cases() -> List[ParityCase]:
    return [
        _case_matmul_chain(),
        _case_residual_tower(),
        _case_softmax_layernorm(),
        _case_multi_device_step(),
        _case_while_loop_body(),
        _case_call_train_step(),
        _case_lm_kernels(),
        _case_adamw_step(),
    ]


# ---------------------------------------------------------------------------
# gate runner + report


@dataclasses.dataclass
class CaseResult:
    name: str
    drift_per_class: Dict[str, Drift]
    breaches: List[str]
    regions: int
    ops_fused: int
    update_drift: float = 0.0  # of optimizer-written variables


@dataclasses.dataclass
class ParityReport:
    """Structured outcome of one gate run (max observed drift per op
    class across all cases, plus per-case detail)."""

    cases: List[CaseResult]
    breaches: List[str]
    backend: str = "generic"

    @property
    def passed(self) -> bool:
        return not self.breaches

    @property
    def per_class(self) -> Dict[str, Drift]:
        agg: Dict[str, Drift] = {}
        for c in self.cases:
            for cls, d in c.drift_per_class.items():
                agg[cls] = agg.get(cls, Drift()) | d
        return agg

    def to_json(self) -> Dict[str, Any]:
        return {
            "passed": self.passed,
            "backend": self.backend,
            "breaches": list(self.breaches),
            "tolerances": {
                c: {"ulp": t.ulp, "rel": t.rel}
                for c, t in sorted(
                    tolerance_table("cpu", self.backend).items())},
            "max_drift_per_class": {
                c: {"ulp": d.ulp, "rel": d.rel}
                for c, d in sorted(self.per_class.items())},
            "cases": [{
                "name": c.name,
                "breaches": c.breaches,
                "regions": c.regions,
                "ops_fused": c.ops_fused,
                "update_drift": c.update_drift,
                "drift_per_class": {
                    cls: {"ulp": d.ulp, "rel": d.rel}
                    for cls, d in sorted(c.drift_per_class.items())},
            } for c in self.cases],
        }

    def to_markdown(self) -> str:
        lines = ["# Numerics parity gate (fused-fast vs unfused-strict)", "",
                 f"**Result: {'PASS' if self.passed else 'BREACH'}** "
                 f"(kernel backend: `{self.backend}`)", "",
                 "| op class | tolerance (ulp \\| rel) | max observed "
                 "(ulp \\| rel) |", "|---|---|---|"]
        per_class = self.per_class
        for cls, tol in sorted(tolerance_table("cpu", self.backend).items()):
            d = per_class.get(cls)
            obs = f"{d.ulp:g} \\| {d.rel:.2e}" if d else "—"
            lines.append(f"| {cls} | {tol.ulp:g} \\| {tol.rel:.0e} | {obs} |")
        lines += ["", "| case | fused regions | ops fused | status |",
                  "|---|---|---|---|"]
        for c in self.cases:
            status = "ok" if not c.breaches else "; ".join(c.breaches)
            if c.update_drift:
                status += (f" (update drift {c.update_drift:.2e} \\| "
                           f"{update_tolerance(['cpu']):g})")
            lines.append(f"| {c.name} | {c.regions} | {c.ops_fused} |"
                         f" {status} |")
        if self.breaches:
            lines += ["", "## Breaches", ""]
            lines += [f"- {b}" for b in self.breaches]
        return "\n".join(lines)


def run_case(case: ParityCase, backend: str = "generic") -> CaseResult:
    """Execute one case fused-fast vs unfused-strict and collect drift.

    The *reference* session is always generic (unfused-strict is the
    oracle); ``backend`` selects the kernel backend of the fused-fast
    candidate, and the drift is gated against that backend's calibrated
    tolerance table (DESIGN.md §12)."""
    from .graph import as_ref
    from .options import SessionOptions
    from .ops import GraphBuilder
    from .session import Session

    built = []
    for fast in (False, True):
        b = GraphBuilder()
        extras = case.build(b)
        sess = Session(b.graph, options=SessionOptions(
            fuse_regions=fast,
            numerics="fast" if fast else "strict",
            parity_guard=False,  # the gate itself is the comparator
            backend=backend if fast else "generic",
            devices=case.devices() if case.devices else None))
        built.append((sess, extras))
    (ref_sess, ref_ex), (cand_sess, cand_ex) = built

    drifts: Dict[str, Drift] = {}
    breaches: List[str] = []

    def record(cls: str, ref_v: Any, got_v: Any, what: str) -> None:
        tol = tolerance_for_classes([cls], "cpu", backend)
        ok, d = compare(ref_v, got_v, tol)
        drifts[cls] = drifts.get(cls, Drift()) | d
        if not ok:
            breaches.append(
                f"{case.name}/{what}: drift {d} exceeds {tol} [{cls}]")

    # variables an optimizer rewrites are judged by their update drift
    opt_vars = sorted(optimizer_written_variables(ref_sess.graph,
                                                  ref_sess.graph.nodes))
    max_update = 0.0
    for step in range(case.n_runs):
        ref_feeds = case.feeds(ref_ex, step) if case.feeds else None
        cand_feeds = case.feeds(cand_ex, step) if case.feeds else None
        before = {vn: (ref_sess.variable_value(vn),
                       cand_sess.variable_value(vn)) for vn in opt_vars}
        rv = ref_sess.run(case.fetches(ref_ex), ref_feeds)
        cv = cand_sess.run(case.fetches(cand_ex), cand_feeds)
        for i, (r, g) in enumerate(zip(rv, cv)):
            record(case.fetch_classes[i], r, g, f"fetch{i}@run{step}")
        for vn in sorted(n for n in ref_sess.graph.nodes
                         if ref_sess.graph.nodes[n].op == "Variable"):
            if vn in before:
                u = update_drift(before[vn][0], ref_sess.variable_value(vn),
                                 cand_sess.variable_value(vn),
                                 got_before=before[vn][1])
                max_update = max(max_update, u)
                if u > update_tolerance(["cpu"]):
                    breaches.append(
                        f"{case.name}/var:{vn}@run{step}: update drift "
                        f"{u:.3g} exceeds {update_tolerance(['cpu']):g}")
            elif ref_sess.variables.has(vn):
                record(case.var_class, ref_sess.variable_value(vn),
                       cand_sess.variable_value(vn), f"var:{vn}@run{step}")

    # the gate must never pass vacuously: fast mode has to have actually
    # fused the contraction ops this case exists to exercise
    fetch_refs = [as_ref(f) for f in case.fetches(cand_ex)]
    feed_keys = frozenset(
        as_ref(k) for k in (case.feeds(cand_ex, 0) or {})) if case.feeds \
        else frozenset()
    exe = cand_sess.executable(fetch_refs, feed_keys)
    regions = exe.fusion.regions if exe.fusion is not None else []
    fused_ops = {spec.subgraph.nodes[m].op
                 for spec in regions for m in spec.members}
    for op in case.must_fuse_ops:
        if op not in fused_ops:
            breaches.append(
                f"{case.name}: op {op} did not join any fused region in "
                f"fast mode (gate would be vacuous)")
    return CaseResult(name=case.name, drift_per_class=drifts,
                      breaches=breaches, regions=len(regions),
                      ops_fused=sum(len(s.members) for s in regions),
                      update_drift=max_update)


def run_parity_gate(cases: Optional[Sequence[ParityCase]] = None, *,
                    backend: str = "generic") -> ParityReport:
    cases = list(cases) if cases is not None else default_cases()
    before = 0
    if backend != "generic":
        from . import kernel_registry

        before = kernel_registry.dispatch_total(backend)
    results = [run_case(c, backend=backend) for c in cases]
    breaches = [b for r in results for b in r.breaches]
    if backend != "generic":
        from . import kernel_registry

        if kernel_registry.dispatch_total(backend) == before:
            # same anti-vacuity contract as must_fuse_ops: a backend gate
            # that never dispatched a registered kernel proved nothing
            breaches.append(
                f"backend {backend!r}: no registered kernel dispatched "
                "across the suite (gate would be vacuous)")
    return ParityReport(cases=results, breaches=breaches, backend=backend)


# ---------------------------------------------------------------------------
# CLI: python -m repro.core.numerics --gate [--json PATH] [--cases SUBSTR]


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m repro.core.numerics",
        description="Numerics parity gate: prove fused-fast execution "
                    "stays within the §9 tolerances of unfused-strict.")
    ap.add_argument("--gate", action="store_true",
                    help="run the parity suite; exit 1 on any breach")
    ap.add_argument("--cases", default=None,
                    help="substring filter on case names")
    ap.add_argument("--json", default=None,
                    help="also write the structured report to this path")
    ap.add_argument("--backend", default="generic",
                    help="kernel backend for the fused-fast candidate "
                         "(generic | pallas); the reference stays generic")
    args = ap.parse_args(argv)
    if not args.gate:
        ap.print_help()
        return 2
    from . import kernel_registry

    if args.backend not in kernel_registry.available_backends():
        print(f"unknown backend {args.backend!r}; available: "
              f"{kernel_registry.available_backends()}", file=sys.stderr)
        return 2
    cases = default_cases()
    if args.cases:
        cases = [c for c in cases if args.cases in c.name]
        if not cases:
            print(f"no parity case matches {args.cases!r}", file=sys.stderr)
            return 2
    report = run_parity_gate(cases, backend=args.backend)
    print(report.to_markdown())
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(report.to_json(), fh, indent=2, sort_keys=True)
        print(f"\n# wrote {args.json}")
    return 0 if report.passed else 1


if __name__ == "__main__":
    raise SystemExit(main())
