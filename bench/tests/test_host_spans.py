"""bench/host_spans.py on interval sets, on ``small.xplane.pb`` (harness
spans only) and on ``spans.xplane.pb``, recorded on a TPU v5e by
``record_spans_trace.py``: three ContinuousBatcher steps, each inside
``bench.batcher_step`` with the batcher's ``serve.*`` spans in it and a
20 ms sleep under ``serve.sample``."""
import os

import pytest

from bench import host_spans, trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data")
SERVE = ("serve.step", "serve.admit", "serve.dispatch", "serve.device_wait",
         "serve.logits_to_host", "serve.sample")


def test_spans_in_counts_spans_starting_in_the_window_clipped_to_it():
    spans = [("a", 0.0, 2.0), ("a", 3.0, 4.0), ("a", 9.0, 12.0),
             ("b", 12.0, 13.0)]
    assert host_spans.spans_in(spans, 1.0, 10.0) == {"a": [2, 2.0]}


def test_label_gaps_take_the_innermost_covering_span():
    spans = [("bench.batcher_step", 0.0, 10.0), ("serve.step", 0.5, 9.5),
             ("serve.sample", 6.0, 9.0), ("bench.wait_arrival", 11.0, 12.0)]
    gaps = [(1.0, 2.0), (7.0, 8.0), (9.6, 9.8), (10.2, 10.6), (11.0, 11.5)]
    assert dict(host_spans.label_gaps(spans, gaps)) == pytest.approx({
        "serve.step": 1.0, "serve.sample": 1.0, "bench.batcher_step": 0.2,
        "host:other": 0.4, "bench.wait_arrival": 0.5})


def test_without_program_spans_the_gaps_read_as_trace_reduce_reads_them():
    path = os.path.join(DATA, "small.xplane.pb")
    old, new = trace_reduce.reduce(path), host_spans.reduce(path)
    assert new["window_s"] == old["window_s"]
    assert dict(new["idle_gaps"]) == pytest.approx(dict(old["idle_gaps"]))
    assert new["host_spans"]["bench.step"][0] == 3
    assert new["host_spans"]["bench.host_work"][0] == 3


@pytest.fixture(scope="module")
def spans_trace():
    path = os.path.join(DATA, "spans.xplane.pb")
    return trace_reduce.reduce(path), host_spans.reduce(path)


def test_every_step_phase_is_counted_once_per_step(spans_trace):
    _, new = spans_trace
    counts = {n: c for n, (c, _) in new["host_spans"].items()}
    assert counts["bench.batcher_step"] == 3
    assert all(counts[n] == 3 for n in SERVE), counts
    assert trace_reduce.WINDOW not in counts
    secs = {n: t for n, (_, t) in new["host_spans"].items()}
    # the phases lie inside the step, which lies inside the harness's span
    assert sum(secs[n] for n in SERVE[1:]) <= secs["serve.step"]
    assert secs["serve.step"] <= secs["bench.batcher_step"] <= new["window_s"]
    assert 0.06 <= secs["serve.sample"]


def test_the_sleep_is_idle_time_under_serve_sample(spans_trace):
    old, new = spans_trace
    gaps = dict(new["idle_gaps"])
    # three 20 ms sleeps; a gap takes the label at its midpoint whole
    assert new["idle_gaps"][0][0] == "serve.sample"
    assert 0.055 <= gaps["serve.sample"] <= 0.09
    # the same idle time, labelled more finely than trace_reduce labels it
    idle = old["window_s"] - old["busy_s"]
    assert new["window_s"] == old["window_s"]
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)
    assert "serve.sample" not in dict(old["idle_gaps"])
    assert dict(old["idle_gaps"])["bench.batcher_step"] >= gaps["serve.sample"]
