"""DESIGN.md §9 numerics policy: the tolerance-gated parity machinery.

Three layers under test: the drift metrics (ULP + scale-relative), the
parity gate itself (a deliberately-divergent op — fp32 sequential
accumulation when compiled vs an fp64-accumulated eager reference —
must trip it; the representative suite must pass it), and the
Session-level guard (a tolerance breach falls back to strict execution
with a warning, leaving results and variable state bit-identical to the
strict engine).
"""
import json
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import GraphBuilder, Session, TensorRef, register
from repro.core import numerics as num
from repro.core.graph import as_ref
from repro.obs import metrics as obs_metrics


# ---------------------------------------------------------------------------
# a deliberately-divergent op: compiled (traced) execution accumulates
# sequentially in fp32 via lax.scan; eager execution accumulates in fp64
# and rounds once.  On a cancellation-heavy input ([1e8, 1 x64, -1e8])
# the fp32 path loses the ones entirely — drift ~= 1.0 relative.


@register("DivergentSum")
def _divergent_sum(ctx, node, xv):
    if isinstance(xv, jax.core.Tracer):
        total, _ = jax.lax.scan(lambda c, v: (c + v, None),
                                jnp.float32(0.0), xv)
        return (total,)
    return (jnp.asarray(np.asarray(xv, np.float64).sum(), jnp.float32),)


CANCEL_INPUT = np.concatenate(
    [[1e8], np.ones(64, np.float32), [-1e8]]).astype(np.float32)


def _divergent_graph():
    b = GraphBuilder()
    y = b.placeholder("y")
    ds = b.graph.add_node("DivergentSum", [y], name="ds")
    fin = b.add(ds, b.constant(jnp.float32(1.0), name="bias"), name="fin")
    v = b.variable("v", init_value=lambda: jnp.float32(10.0))
    upd = b.assign_add(v, b.constant(jnp.float32(0.5), name="half"))
    return b, y, fin, upd


# ---------------------------------------------------------------------------
# drift metrics


def test_ulp_distance_basics():
    one = np.float32(1.0)
    next_up = np.nextafter(one, np.float32(2.0), dtype=np.float32)
    assert num.ulp_distance(one, one) == 0
    assert num.ulp_distance(one, next_up) == 1
    assert num.ulp_distance(np.float32(-0.0), np.float32(0.0)) == 0
    # sign-crossing distances are finite and monotone
    tiny = np.float32(1e-45)
    assert num.ulp_distance(tiny, -tiny) == 2
    nan = np.float32("nan")
    assert num.ulp_distance(nan, nan) == 0
    assert np.isinf(num.ulp_distance(nan, one))


def test_compare_scale_relative_absorbs_near_zero_elements():
    # a tiny absolute wiggle on a near-zero element of a large-scale
    # tensor passes (the allclose atol=rtol*amax convention) ...
    ref = np.array([100.0, 1e-12], np.float32)
    got = np.array([100.0, 2e-12], np.float32)
    ok, drift = num.compare([ref], [got], num.Tolerance(ulp=4, rel=1e-6))
    assert ok
    # ... while the same wiggle on a tensor OF that scale fails
    ref2 = np.array([1e-12, 1e-12], np.float32)
    got2 = np.array([1e-12, 2e-12], np.float32)
    ok2, _ = num.compare([ref2], [got2], num.Tolerance(ulp=4, rel=1e-6))
    assert not ok2


def test_compare_exact_for_non_float_and_structure():
    tol = num.TOLERANCES["cpu"]["elementwise"]
    ok, _ = num.compare([np.arange(4)], [np.arange(4)], tol)
    assert ok
    ok, drift = num.compare([np.arange(4)], [np.arange(1, 5)], tol)
    assert not ok and np.isinf(drift.ulp)
    ok, _ = num.compare([None], [None], tol)
    assert ok
    ok, _ = num.compare([None, 1.0], [1.0], tol)
    assert not ok


def test_compare_handles_pytrees():
    ref = {"a": np.float32(1.0), "b": [np.ones(3, np.float32)]}
    got = {"a": np.float32(1.0),
           "b": [np.ones(3, np.float32)
                 + np.float32(1e-7)]}
    ok, drift = num.compare(ref, got, num.TOLERANCES["cpu"]["reduction"])
    assert ok and drift.ulp > 0


def test_tolerance_for_ops_merges_loosest_class():
    cpu = num.TOLERANCES["cpu"]
    t_elem = num.tolerance_for_ops({"Add", "Mul", "Relu"})
    assert t_elem == cpu["elementwise"]
    t_mm = num.tolerance_for_ops({"Add", "MatMul"})
    assert t_mm.ulp == max(cpu["matmul"].ulp, cpu["elementwise"].ulp)
    # softmax dominates matmul in both bounds
    t_all = num.tolerance_for_ops({"MatMul", "SoftMax", "ReduceSum"})
    assert t_all.ulp >= cpu["softmax"].ulp


def test_tolerance_table_device_and_backend_keying():
    """TPU tables are looser than CPU; a backend calibration overlays
    loosest-wins on top of the device table."""
    cpu = num.tolerance_table("cpu")
    tpu = num.tolerance_table("tpu")
    assert set(cpu) == set(tpu)
    assert tpu["matmul"].ulp >= cpu["matmul"].ulp
    pal = num.tolerance_table("cpu", backend="pallas")
    for cls, tol in pal.items():
        assert tol.ulp >= cpu[cls].ulp and tol.rel >= cpu[cls].rel
    assert pal["softmax"].ulp > cpu["softmax"].ulp
    # merging across device kinds keeps the loosest bound
    t = num.tolerance_for_ops({"MatMul"}, device_kinds=("cpu", "tpu"))
    assert t.ulp == tpu["matmul"].ulp
    # an uncalibrated device kind is an error, never the CPU table
    with pytest.raises(KeyError, match="no tolerance table"):
        num.tolerance_table("npu")


@pytest.mark.parametrize("fault, want", [
    ("exact", 0.0), ("missing", 1.0), ("doubled", 1.0), ("negated", 2.0),
    ("one_flip_in_100", 2 * np.sqrt(1 / 100))])
def test_update_drift_separates_faults_from_sign_flips(fault, want):
    """An AdamW-like ~sign update: flipped elements cost 2*sqrt(k/n),
    while a wrong update of the whole leaf costs O(1)."""
    rs = np.random.RandomState(0)
    before = rs.randn(100).astype("f")
    step = 1e-3 * np.sign(rs.randn(100)).astype("f")
    ref = before - step
    got = {"exact": ref, "missing": before, "doubled": before - 2 * step,
           "negated": before + step,
           "one_flip_in_100": ref + 2 * step * (np.arange(100) == 7)}[fault]
    assert num.update_drift([before], [ref], [got]) == pytest.approx(
        want, rel=1e-3, abs=1e-6)
    assert num.update_drift(before, ref, got * np.nan) == np.inf
    with pytest.raises(KeyError, match="no update tolerance"):
        num.update_tolerance(["npu"])


@register("FlakyUpdate")
def _flaky_update(ctx, node, p, g):
    # the compiled (fused) path steps the wrong way
    sign = -1.0 if isinstance(p, jax.core.Tracer) else 1.0
    return (p - sign * 0.1 * g,)


def test_guard_judges_optimizer_outputs_by_their_update():
    """A variable set from an output declared "optimizer" is judged by
    its update drift: an update in the wrong direction demotes."""
    from repro.core.options import SessionOptions

    b = GraphBuilder()
    g = b.placeholder("g")
    w = b.variable("w", init_value=lambda: jnp.ones(64, jnp.float32))
    upd = b.graph.add_node("FlakyUpdate", [w, g], name="upd",
                           attrs={"numerics_class": (num.OPTIMIZER,)})
    train = b.group([b.assign(w, upd)], name="train")
    assert num.optimizer_written_variables(b.graph, b.graph.nodes) == {"w"}
    sess = Session(b.graph, options=SessionOptions(numerics="fast"))
    with pytest.warns(RuntimeWarning, match=r"update drift 2 of \['w'\]"):
        sess.run([train.ref], {g.ref: jnp.linspace(-1.0, 1.0, 64)})
    # the reference state was kept: one step the right way
    np.testing.assert_allclose(np.asarray(sess.variable_value("w")),
                               1.0 - 0.1 * np.linspace(-1.0, 1.0, 64),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# the gate itself


@pytest.mark.paritygate
def test_parity_gate_passes_on_representative_suite():
    report = num.run_parity_gate()
    assert report.passed, report.breaches
    # every case fused something (never vacuous) ...
    assert all(c.regions >= 1 and c.ops_fused >= 2 for c in report.cases)
    # ... and the suite exercised every tolerance class
    assert set(report.per_class) == set(num.tolerance_table())
    # the structured report round-trips
    js = report.to_json()
    assert js["passed"] and set(js["max_drift_per_class"]) == set(
        num.tolerance_table())
    assert "PASS" in report.to_markdown()


@pytest.mark.paritygate
def test_divergent_op_trips_gate():
    """An injected fp32-accumulation-vs-fp64-reference op must breach."""

    def build(b):
        y = b.placeholder("y")
        ds = b.graph.add_node("DivergentSum", [y], name="ds")
        fin = b.add(ds, b.constant(jnp.float32(1.0), name="bias"),
                    name="fin")
        return {"y": y, "fin": fin}

    case = num.ParityCase(
        name="injected_divergence", build=build,
        fetches=lambda ex: [ex["fin"].ref],
        fetch_classes=("call",),  # loosest class: still must breach
        feeds=lambda ex, step: {ex["y"].ref: jnp.asarray(CANCEL_INPUT)},
        n_runs=1)
    report = num.run_parity_gate([case])
    assert not report.passed
    assert any("injected_divergence" in b for b in report.breaches)
    assert report.per_class["call"].rel > 0.5  # the ones were lost


def test_gate_cli_json_report(tmp_path):
    path = str(tmp_path / "report.json")
    rc = num.main(["--gate", "--cases", "residual_tower", "--json", path])
    assert rc == 0
    with open(path) as fh:
        js = json.load(fh)
    assert js["passed"] and js["cases"][0]["name"] == "residual_tower"
    assert "tolerances" in js


# ---------------------------------------------------------------------------
# Session-level guard: breach -> warn + permanent strict fallback


def test_session_fallback_on_breach_matches_strict_bitwise():
    b, y, fin, upd = _divergent_graph()
    fast = Session(b.graph, numerics="fast")  # parity guard defaults on
    strict = Session(b.graph, numerics="strict", fuse_regions=False)
    feeds = lambda: {y.ref: jnp.asarray(CANCEL_INPUT)}  # noqa: E731
    demotions = obs_metrics.counter("numerics.guard_demotions")
    before = demotions.value
    with pytest.warns(RuntimeWarning, match="parity breach"):
        fv = fast.run([fin.ref, upd.ref], feeds())
    assert demotions.value == before + 1  # the demotion is counted
    sv = strict.run([fin.ref, upd.ref], feeds())
    assert [float(a) for a in fv] == [float(c) for c in sv]
    assert float(fast.variable_value("v")) == float(
        strict.variable_value("v")) == 10.5
    # the fallback is permanent: later runs stay strict, no more warnings
    exe = fast.executable([fin.ref, upd.ref], frozenset({y.ref}))
    assert exe._strict_fallback and not exe._parity_pending
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        fv2 = fast.run([fin.ref, upd.ref], feeds())
    sv2 = strict.run([fin.ref, upd.ref], feeds())
    assert [float(a) for a in fv2] == [float(c) for c in sv2]
    assert float(fast.variable_value("v")) == float(
        strict.variable_value("v")) == 11.0


def test_benign_fast_session_keeps_fusion_and_warns_nothing():
    b = GraphBuilder()
    x = b.placeholder("x")
    cur = x
    for i in range(6):
        cur = b.add(b.mul(cur, x, name=f"m{i}"), x, name=f"a{i}")
    out = b.reduce_sum(cur, name="out")
    sess = Session(b.graph, numerics="fast")
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        v1 = sess.run(out.ref, {x.ref: jnp.linspace(0.1, 0.9, 16)})
        v2 = sess.run(out.ref, {x.ref: jnp.linspace(0.1, 0.9, 16)})
    assert float(v1) == float(v2)
    exe = sess.executable([out.ref], frozenset({x.ref}))
    assert not exe._strict_fallback and not exe._parity_pending
    # fast mode actually fused the reduction (the point of the flip)
    assert any(exe.fusion.graph.nodes[s.name] and
               "ReduceSum" in {s.subgraph.nodes[m].op for m in s.members}
               for s in exe.fusion.regions)


def test_guard_skips_unreplayable_side_effects():
    """Queue ops cannot be double-executed for a reference run: the guard
    must skip, and each run must consume the queue exactly once."""
    from repro.runtime.queues import FIFOQueue

    b = GraphBuilder()
    x = b.placeholder("x")
    sq = b.square(x, name="sq")
    enq = b.graph.add_node("QueueEnqueue", [sq], name="enq",
                           attrs={"queue": "q"})
    deq = b.graph.add_node("QueueDequeue", [], name="deq",
                           attrs={"queue": "q", "n_components": 1},
                           control_inputs=[enq])
    out = b.reduce_sum(b.mul(deq, deq, name="dsq"), name="out")
    sess = Session(b.graph, numerics="fast")
    sess.register_queue("q", FIFOQueue(capacity=4, timeout=5.0))
    for step in range(3):
        v = sess.run(out.ref, {x.ref: jnp.full((3,), 1.0 + step)})
        assert np.isfinite(float(v))
    assert sess.queues["q"].size() == 0  # exactly one enqueue per dequeue
    exe = sess.executable([out.ref], frozenset({x.ref}))
    assert not exe._parity_pending and not exe._strict_fallback


def test_strict_and_fast_executables_cache_separately():
    b = GraphBuilder()
    x = b.placeholder("x")
    out = b.reduce_sum(b.mul(x, x, name="m"), name="out")
    sess = Session(b.graph, numerics="fast", parity_guard=False)
    sess.run(out.ref, {x.ref: jnp.ones(4)})
    exe_fast = sess.executable([out.ref], frozenset({x.ref}))
    assert exe_fast.numerics == "fast"
    # flipping the session's numerics mode must MISS the cache: a stale
    # fast plan silently serving strict (or vice versa) would make
    # results signature-dependent
    sess.numerics = "strict"
    sess.run(out.ref, {x.ref: jnp.ones(4)})
    exe_strict = sess.executable([out.ref], frozenset({x.ref}))
    assert exe_strict is not exe_fast and exe_strict.numerics == "strict"
    sess.numerics = "fast"
    assert sess.executable([out.ref], frozenset({x.ref})) is exe_fast


def test_session_rejects_unknown_numerics():
    with pytest.raises(ValueError, match="numerics"):
        Session(numerics="fastest")


def test_fast_mode_fuses_matmul_at_full_opt():
    """The tentpole behavior: under fast numerics MatMul/reductions join
    regions (strict keeps them eager) and the region spec records the
    fast policy (full XLA optimization; no opt-0 compile option)."""
    b = GraphBuilder()
    x = b.placeholder("x")
    w = b.constant(jnp.eye(4, dtype=jnp.float32), name="w")
    mm = b.matmul(x, w, name="mm")
    out = b.reduce_sum(b.add(mm, x, name="sum_in"), name="out")
    fast = Session(b.graph, numerics="fast", parity_guard=False)
    strict = Session(b.graph, numerics="strict")
    X = jnp.ones((4, 4), jnp.float32)
    fv = fast.run(out.ref, {x.ref: X})
    sv = strict.run(out.ref, {x.ref: X})
    assert float(fv) == float(sv) == 32.0
    fexe = fast.executable([out.ref], frozenset({x.ref}))
    fused_ops = {s.subgraph.nodes[m].op
                 for s in fexe.fusion.regions for m in s.members}
    assert {"MatMul", "ReduceSum"} <= fused_ops
    assert all(s.numerics == "fast" for s in fexe.fusion.regions)
    sexe = strict.executable([out.ref], frozenset({x.ref}))
    strict_fused = {s.subgraph.nodes[m].op
                    for s in (sexe.fusion.regions if sexe.fusion else [])
                    for m in s.members}
    assert "MatMul" not in strict_fused and "ReduceSum" not in strict_fused


def test_guard_sampling_catches_input_shift_drift():
    """REPRO_NUMERICS_GUARD=sample:N (ROADMAP item): the first batch can
    pass the guard while a later input distribution exposes drift — the
    sampled re-verification catches it and demotes to strict."""
    BENIGN = np.ones(66, np.float32)  # fp32 scan == fp64 sum exactly
    b, y, fin, upd = _divergent_graph()
    sess = Session(b.graph, numerics="fast", parity_guard="sample:2")
    assert sess.parity_guard and sess.parity_guard_every == 2
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # first run verifies and passes
        sess.run([fin.ref, upd.ref], {y.ref: jnp.asarray(BENIGN)})
        sess.run([fin.ref, upd.ref], {y.ref: jnp.asarray(BENIGN)})  # unsampled
    with pytest.warns(RuntimeWarning, match="parity breach"):
        # run 3 is the sampled re-verification; the shifted input drifts
        rv = sess.run([fin.ref, upd.ref], {y.ref: jnp.asarray(CANCEL_INPUT)})
    exe = sess.executable([fin.ref, upd.ref], frozenset({y.ref}))
    assert exe._strict_fallback
    # ...and the caller received the strict reference, not the drifted value
    strict = Session(b.graph, numerics="strict", fuse_regions=False)
    for feed in (BENIGN, BENIGN, CANCEL_INPUT):
        sv = strict.run([fin.ref, upd.ref], {y.ref: jnp.asarray(feed)})
    assert float(rv[0]) == float(sv[0])


def test_default_guard_misses_late_drift_without_sampling():
    """The contrast case motivating sample:N — first-run-only verification
    lets a later shifted batch return the drifted fused value silently."""
    BENIGN = np.ones(66, np.float32)
    b, y, fin, upd = _divergent_graph()
    sess = Session(b.graph, numerics="fast", parity_guard=True)
    assert sess.parity_guard_every is None
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        sess.run([fin.ref, upd.ref], {y.ref: jnp.asarray(BENIGN)})
        out = sess.run([fin.ref, upd.ref], {y.ref: jnp.asarray(CANCEL_INPUT)})
    # fused fp32 scan lost the 64 ones entirely: genuine unreported drift
    assert abs(float(out[0]) - (64.0 + 1.0)) > 1.0
    exe = sess.executable([fin.ref, upd.ref], frozenset({y.ref}))
    assert not exe._strict_fallback


def test_guard_sampling_env_and_param_parsing(monkeypatch):
    b = GraphBuilder()
    b.constant(jnp.float32(1.0), name="c")
    monkeypatch.setenv("REPRO_NUMERICS_GUARD", "sample:4")
    s = Session(b.graph)
    assert s.parity_guard and s.parity_guard_every == 4
    monkeypatch.setenv("REPRO_NUMERICS_GUARD", "off")
    s2 = Session(b.graph)
    assert not s2.parity_guard
    s3 = Session(b.graph, parity_guard="sample:1")  # re-verify every run
    assert s3.parity_guard_every == 1
    with pytest.raises(ValueError, match="sample period"):
        Session(b.graph, parity_guard="sample:0")


def test_compare_bf16_judged_in_native_ulps():
    """jax's ml_dtypes floats (the serve cache is bf16) must be drift-
    compared, not exact-compared — and the fp32-calibrated ULP bounds
    must scale to the narrower mantissa (2048 fp32-ULPs carried over to
    bf16 verbatim would span ~16 binades and check nothing)."""
    ml_dtypes = pytest.importorskip("ml_dtypes")
    tol = num.TOLERANCES["cpu"]["call"]
    a = np.array([1.0], ml_dtypes.bfloat16)
    one_ulp = np.array([1.0078125], ml_dtypes.bfloat16)
    ok, drift = num.compare([a], [one_ulp], tol)
    assert ok and drift.ulp == 1  # reassociation-scale drift passes
    binade = np.array([2.0], ml_dtypes.bfloat16)
    ok, drift = num.compare([a], [binade], tol)
    assert not ok and drift.ulp == 128  # genuine divergence still fails
    assert num._effective_ulp(tol.ulp, a.dtype) == 8.0
