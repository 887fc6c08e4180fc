"""§16 observability: metrics registry, span tracing, clock alignment.

Covers the tentpole surfaces (SpanRecorder → merge_streams → Chrome
trace; MetricsRegistry + the legacy-STATS shim) plus the satellite
guarantees: clock-offset estimation under injected skew, merged-trace
monotonicity, trace-off zero-overhead, and the §9.1 summary round-trip.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np
import pytest

from repro.core import Session
from repro.core.graph import Graph
from repro.core.ops import GraphBuilder
from repro.core.options import SessionOptions
from repro.obs import export as export_mod
from repro.obs import metrics as metrics_mod
from repro.obs import spans as spans_mod
from repro.obs.metrics import MetricsRegistry, StatsDict


# ---------------------------------------------------------------------------
# metrics registry


def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("x.count")
    c.inc()
    c.inc(2)
    assert c.value == 3
    assert reg.counter("x.count") is c  # get-or-create

    g = reg.gauge("x.ts")
    assert g.value is None
    g.set(1.5)
    assert g.value == 1.5

    h = reg.histogram("x.lat")
    for v in range(100):
        h.observe(v / 100.0)
    s = h.summary()
    assert s["count"] == 100
    assert s["min"] == 0.0 and s["max"] == 0.99
    assert 0.45 <= s["p50"] <= 0.55
    assert s["p99"] >= 0.95

    snap = reg.snapshot()
    assert snap["counters"]["x.count"] == 3
    assert snap["gauges"]["x.ts"] == 1.5
    assert snap["histograms"]["x.lat"]["count"] == 100


def test_histogram_reservoir_bounded():
    reg = MetricsRegistry()
    h = reg.histogram("h")
    for v in range(10_000):
        h.observe(float(v))
    assert h.count == 10_000  # exact count survives the bounded window
    assert len(h._recent) == h.RESERVOIR
    # quantiles reflect the recent window, not all of history
    assert h.percentile(50) > 5000


def test_stats_dict_is_registry_backed():
    reg = MetricsRegistry()
    stats = StatsDict("mysub", keys=("calls", "hits"), registry=reg)
    stats["calls"] += 1
    stats["calls"] += 1
    stats["hits"] += 1
    assert stats["calls"] == 2
    assert reg.snapshot()["counters"]["mysub.calls"] == 2
    # undeclared keys raise, like a plain dict
    with pytest.raises(KeyError):
        stats["nope"]
    # the legacy reset idiom works and hits the registry too
    for k in stats:
        stats[k] = 0
    assert stats["calls"] == 0
    assert reg.snapshot()["counters"]["mysub.calls"] == 0
    # late declaration through assignment
    stats["new_key"] = 7
    assert dict(stats) == {"calls": 0, "hits": 0, "new_key": 7}


def test_module_stats_dicts_surface_in_global_registry():
    from repro.core import placement

    before = placement.STATS["place_calls"]
    placement.STATS["place_calls"] += 1
    try:
        snap = metrics_mod.snapshot()
        assert snap["counters"]["placement.place_calls"] == before + 1
    finally:
        placement.STATS["place_calls"] = before


def test_verifier_stats_identity_preserved():
    # analysis/__init__.py re-exports the object; the registry-backed
    # swap must not have broken that aliasing
    import repro.analysis as analysis
    from repro.analysis import verifier

    assert analysis.STATS is verifier.STATS
    assert "verify_calls" in verifier.STATS
    assert "frames" in verifier.STATS  # per-pass keys declared via loop


# ---------------------------------------------------------------------------
# spans + export


def _mm_graph():
    # fed input keeps the pre-fusion constant folder from collapsing the
    # whole graph into one Const node
    b = GraphBuilder()
    x = b.placeholder("x")
    y = b.constant(np.eye(4, dtype=np.float32) * 3.0, name="y")
    mm = b.matmul(x, y, name="mm")
    s = b.reduce_sum(mm, name="s")
    return b, s, x


_FEED = np.eye(4, dtype=np.float32) * 2.0  # sum((2I)@(3I)) == 24


def test_traced_run_emits_op_spans_and_chrome_trace(tmp_path):
    b, s, x = _mm_graph()
    sess = Session(b.graph, options=SessionOptions(trace_dir=str(tmp_path)))
    try:
        (val,) = sess.run([s.ref], feed_dict={x.ref: _FEED})
        assert float(np.asarray(val)) == pytest.approx(24.0)
        events = sess._spans.snapshot()
        ops = {e.get("args", {}).get("op") for e in events
               if e["cat"] == spans_mod.CAT_OP}
        assert "MatMul" in ops and "ReduceSum" in ops
        path = sess.export_trace()
        assert path and os.path.exists(path)
        with open(path) as f:
            obj = json.load(f)
        info = export_mod.validate_trace(obj)
        assert info["events"] > 0
        assert "master" in info["processes"]
        names = {e["name"] for e in obj["traceEvents"] if e.get("ph") == "X"}
        assert any(n.startswith("MatMul:") for n in names)
    finally:
        spans_mod.install(None)
        sess.close()


def test_fused_region_is_single_span(tmp_path):
    b, s, x = _mm_graph()
    sess = Session(b.graph, options=SessionOptions(
        trace_dir=str(tmp_path), fuse_regions=True, numerics="fast"))
    try:
        sess.run([s.ref], feed_dict={x.ref: _FEED})
        events = sess._spans.snapshot()
        regions = [e for e in events if e["cat"] == spans_mod.CAT_REGION]
        members = [e for e in events if e["cat"] == spans_mod.CAT_OP
                   and e["name"] in ("mm", "s")]
        if regions:  # fusion actually formed a region on this graph
            # ONE span per region, annotated — no per-member op spans
            assert all(e["args"]["members"] >= 1 for e in regions)
            assert not members
    finally:
        spans_mod.install(None)
        sess.close()


def test_trace_off_is_zero_overhead():
    """Tracing disabled = no recorder anywhere: no global slot, no
    session recorder, and a run records nothing (the disabled path is a
    single ``is None`` check, asserted structurally rather than with a
    flaky wall-clock bound)."""
    spans_mod.install(None)
    b, s, x = _mm_graph()
    sess = Session(b.graph)
    try:
        assert sess._spans is None
        assert spans_mod.get() is None
        sess.run([s.ref], feed_dict={x.ref: _FEED})
        assert sess._spans is None
        assert spans_mod.get() is None
    finally:
        sess.close()


def test_merge_streams_lanes_and_offsets():
    t0 = 1000.0
    streams = [
        {"process": "master", "offset_s": 0.0, "events": [
            {"name": "step:0", "cat": spans_mod.CAT_STEP, "device": "master",
             "ts": t0, "dur": 1.0},
        ]},
        # worker clock runs 5s ahead; offset_s subtracts it back
        {"process": "worker-task0", "offset_s": 5.0, "events": [
            {"name": "mm", "cat": spans_mod.CAT_OP,
             "device": "/job:worker/task:0/device:cpu:0",
             "ts": t0 + 5.2, "dur": 0.3, "args": {"op": "MatMul"}},
            {"name": "r", "cat": spans_mod.CAT_WAIT,
             "device": "/job:worker/task:0/device:cpu:0",
             "ts": t0 + 5.5, "dur": 0.1},
        ]},
    ]
    obj = export_mod.merge_streams(streams)
    info = export_mod.validate_trace(obj)
    assert set(info["processes"]) == {"master", "worker-task0"}
    # the wait event landed in the rendezvous lane
    assert any(lane.endswith(export_mod.RENDEZVOUS_LANE)
               for lane in info["lanes"])
    xs = {e["name"]: e for e in obj["traceEvents"] if e.get("ph") == "X"}
    # after offset subtraction the worker op starts 0.2s into the trace
    assert xs["MatMul:mm"]["ts"] == pytest.approx(0.2e6, rel=1e-6)
    assert xs["step:0"]["ts"] == pytest.approx(0.0, abs=1e-6)


def test_merged_trace_monotone_under_synthetic_skew():
    """Satellite 4: a causally-ordered pair (master step wraps a worker
    op) stays ordered in the merged trace when the worker clock is
    skewed, provided the estimated offset is applied."""
    skew = 120.0  # worker clock is 2 minutes ahead
    t0 = 5000.0
    master_events = [{"name": "step:0", "cat": spans_mod.CAT_STEP,
                      "device": "master", "ts": t0, "dur": 2.0}]
    # the worker op physically happened 0.5s after the step started,
    # but its timestamps carry the skew
    worker_events = [{"name": "op", "cat": spans_mod.CAT_OP,
                      "device": "d0", "ts": t0 + 0.5 + skew, "dur": 0.2,
                      "args": {"op": "MatMul"}}]
    # NTP-style estimate from a synthetic heartbeat exchange with 40ms
    # RTT (the fault harness's delay hook inflates RTT the same way):
    t_send, rtt = t0 - 1.0, 0.040
    worker_clock = (t_send + rtt / 2.0) + skew  # replied at the midpoint
    est = worker_clock - (t_send + (t_send + rtt)) / 2.0
    assert abs(est - skew) <= rtt / 2.0  # estimator error bound
    obj = export_mod.merge_streams([
        {"process": "master", "offset_s": 0.0, "events": master_events},
        {"process": "worker-task0", "offset_s": est,
         "events": worker_events}])
    xs = {e["name"]: e for e in obj["traceEvents"] if e.get("ph") == "X"}
    start, end = xs["step:0"]["ts"], xs["step:0"]["ts"] + xs["step:0"]["dur"]
    assert start <= xs["MatMul:op"]["ts"] <= end  # nested, not 2 minutes away
    # without the offset the merge would be wildly non-causal
    bad = export_mod.merge_streams([
        {"process": "master", "offset_s": 0.0, "events": master_events},
        {"process": "worker-task0", "offset_s": 0.0,
         "events": worker_events}])
    bad_xs = {e["name"]: e for e in bad["traceEvents"] if e.get("ph") == "X"}
    assert bad_xs["MatMul:op"]["ts"] > end


def test_master_clock_offset_estimation_with_injected_delay():
    """Satellite 4, live half: Master._note_clock against a Worker whose
    heartbeat is slowed by the fault harness's client-side delay hook —
    the RTT inflation must widen, not corrupt, the estimate."""
    from repro.distrib.master import Master

    m = Master("127.0.0.1:9", heartbeat_interval=0)  # no hb thread
    try:
        skew = 30.0
        # two samples: a slow (fault-delayed) one first, then a tight one
        t = time.time()
        m._note_clock(0, worker_clock=t + skew + 0.25, t_send=t,
                      t_recv=t + 0.5)  # 500ms RTT — the delayed probe
        est_loose = m.clock_offset(0)
        assert abs(est_loose - skew) <= 0.25 + 1e-6
        m._note_clock(0, worker_clock=t + 1.0 + 0.001 + skew,
                      t_send=t + 1.0, t_recv=t + 1.002)  # 2ms RTT
        est_tight = m.clock_offset(0)
        assert abs(est_tight - skew) <= 0.001 + 1e-6
        # a later, looser sample must not displace the tight one
        m._note_clock(0, worker_clock=t + 2.0 + skew + 1.0, t_send=t + 2.0,
                      t_recv=t + 4.0)
        assert m.clock_offset(0) == est_tight
    finally:
        m.stop()


def test_validate_trace_rejects_malformed():
    with pytest.raises(ValueError):
        export_mod.validate_trace({"not": "a trace"})
    with pytest.raises(ValueError):
        export_mod.validate_trace({"traceEvents": [{"ph": "X"}]})


# ---------------------------------------------------------------------------
# legacy tracer rides the span stream


def test_tracer_adapter_wait_spans():
    from repro.tools.tracing import Tracer

    tr = Tracer()
    t = time.time()
    tr.record("mm", "MatMul", "d0", t, t + 0.001)
    tr.record_wait("recv_x", "d0", t + 0.001, t + 0.010)
    stalls = tr.critical_stalls(threshold_us=100.0)
    assert [e["name"] for e in stalls] == ["recv_x"]
    # a slow *op* is not a stall — only wait spans qualify
    tr.record("big", "MatMul", "d0", t, t + 1.0)
    assert [e["name"] for e in tr.critical_stalls()] == ["recv_x"]


# ---------------------------------------------------------------------------
# §9.1 summary round-trip through train()


def test_train_summary_dir_round_trip(tmp_path):
    from repro.launch.train import train
    from repro.tools.summary import read_events

    res = train(smoke=True, steps=3, batch=2, seq=16, log_every=10,
                summary_dir=str(tmp_path / "sum"))
    assert len(res["step_seconds"]) == 3
    assert set(res["variables"]) == {"params", "opt"}
    events = read_events(str(tmp_path / "sum"))
    assert len(events["train/loss"]) == 3
    assert len(events["train/tokens_per_sec"]) == 3
    steps = [s for s, _ in events["train/loss"]]
    assert steps == [1, 2, 3]
    assert all(v > 0 for _, v in events["train/tokens_per_sec"])


# ---------------------------------------------------------------------------
# profile CLI


def test_profile_cli_renders_and_validates(tmp_path, capsys):
    from repro.obs import profile as profile_mod

    streams = [{"process": "worker-task0", "offset_s": 0.0, "events": [
        {"name": "mm", "cat": spans_mod.CAT_OP, "device": "d0",
         "ts": 100.0, "dur": 0.001, "args": {"op": "MatMul"}},
        {"name": "recv_x", "cat": spans_mod.CAT_WAIT, "device": "d0",
         "ts": 100.001, "dur": 0.05},
    ]}]
    path = str(tmp_path / "trace.json")
    export_mod.write_trace(path, streams)
    assert profile_mod.main([path, "--validate"]) == 0
    out = capsys.readouterr().out
    assert "MatMul" in out
    assert "recv_x" in out  # the stall table names the blocked node
