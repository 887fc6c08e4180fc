"""bench/trace_reduce.py on interval sets and on two traces recorded on a
TPU v5e: ``small.xplane.pb`` by ``record_trace.py`` (three jitted matmul
steps, each followed by 20 ms of host-only work, inside a ``bench.window``
span) and ``spans.xplane.pb`` by ``record_spans_trace.py`` (three
ContinuousBatcher steps, each inside ``bench.batcher_step`` with the
batcher's ``serve.*`` spans in it and a 20 ms sleep under
``serve.sample``)."""
import os

import pytest

from bench import trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data")
TRACE = os.path.join(DATA, "small.xplane.pb")
SERVE = ("serve.step", "serve.admit", "serve.dispatch", "serve.device_wait",
         "serve.logits_to_host", "serve.sample")


def test_merge_unions_overlapping_intervals():
    assert trace_reduce.merge([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [
        (0, 2.5), (3, 4)]
    assert trace_reduce.merge([]) == []


def test_op_times_keep_a_containers_own_time_and_clip_to_the_window():
    ops = [("%while.1 = (s32[], f32[4]{0}) while((s32[], f32[4]{0}) %t), "
            "condition=%c, body=%b", 0, 10),
           ("%fusion.2 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop", 1, 3),
           ("%fusion.2 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop", 4, 9),
           ("%dot.3 = f32[4,4]{1,0} dot(f32[4]{0} %a, f32[4]{0} %b)", 11, 12)]
    # the loop's own time is what its body's ops leave uncovered: 8 - 6
    assert trace_reduce.op_times(ops, 0, 8) == {
        "%fusion.2 fusion f32[4]": 6, "%while.1 while (s32[], f32[4])": 2}
    assert trace_reduce.op_times(ops, 0, 20)["%dot.3 dot f32[4,4]"] == 1


def test_op_times_give_each_instant_to_the_latest_started_op():
    # overlapping, not nested: each op's own seconds still add up to the union
    ops = [("a", 0, 10), ("b", 2, 4), ("c", 3, 6), ("d", 5, 12), ("e", 11, 13)]
    assert trace_reduce.op_times(ops, 0, 20) == {
        "a": 2, "b": 1, "c": 2, "d": 6, "e": 2}
    assert trace_reduce.op_times([("a", 0, 10), ("b", 2, 8), ("c", 7, 9)],
                                 0, 20) == {"a": 3, "b": 5, "c": 2}


def test_short_name_keeps_op_kind_and_result_type():
    hlo = ("%fusion.5 = (f32[4,8]{1,0:T(8,128)}, bf16[2]{0}) fusion(f32[4]{0} "
           "%p), kind=kLoop, calls=%c")
    assert trace_reduce.short_name(hlo) == "%fusion.5 fusion (f32[4,8], bf16[2])"


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(TRACE)


def test_recorded_trace_has_one_device_and_the_window(reduced):
    assert reduced["n_devices"] == 1
    assert 0.06 < reduced["window_s"] < 1.0
    assert 0 < reduced["busy_s"] < reduced["window_s"]


def test_host_work_shows_as_idle_time(reduced):
    gaps = dict(reduced["idle_gaps"])
    # three 20 ms sleeps under bench.host_work, no device op meanwhile
    assert 0.055 <= gaps["bench.host_work"] <= 0.09
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)


def test_device_ops_are_named_and_sum_to_busy(reduced):
    ops = reduced["device_ops"]
    assert ops and all(isinstance(n, str) and t > 0 for n, t in ops)
    # the ops do not overlap here, so their self times add up to busy
    assert sum(t for _, t in ops) == pytest.approx(reduced["busy_s"], rel=1e-6)
    assert any(" fusion f32[2048,2048]" in n for n, _ in ops)
    assert dict(ops) == reduced["op_seconds"]


def test_span_names_have_the_area_phase_form():
    ok = ["bench.window", "serve.logits_to_host", "data.get", "a.b.c2"]
    bad = ["PjitFunction(one_slot_step)", "tpu::System::Execute", "bench",
           "$time sleep", "Serve.step", "serve.", ".step", "serve step"]
    assert all(trace_reduce.SPAN_NAME.match(n) for n in ok)
    assert not any(trace_reduce.SPAN_NAME.match(n) for n in bad)


def test_spans_in_counts_spans_starting_in_the_window_clipped_to_it():
    spans = [("a", 0.0, 2.0), ("a", 3.0, 4.0), ("a", 9.0, 12.0),
             ("b", 12.0, 13.0)]
    assert trace_reduce.spans_in(spans, 1.0, 10.0) == {"a": [2, 2.0]}


def test_split_gaps_give_each_instant_to_the_innermost_span():
    spans = [("bench.batcher_step", 0.0, 10.0), ("serve.step", 0.5, 9.5),
             ("serve.sample", 6.0, 9.0), ("bench.wait_arrival", 11.0, 12.0)]
    gaps = [(1.0, 2.0), (5.0, 6.5), (7.0, 8.0), (9.25, 10.25), (10.5, 10.6),
            (11.0, 11.5)]
    assert dict(trace_reduce.split_gaps(spans, gaps)) == pytest.approx({
        "serve.step": 1.0 + 1.0 + 0.25, "serve.sample": 0.5 + 1.0,
        "bench.batcher_step": 0.5, "host:other": 0.25 + 0.1,
        "bench.wait_arrival": 0.5})


def test_harness_spans_are_kept(reduced):
    assert reduced["host_spans"]["bench.step"][0] == 3
    assert reduced["host_spans"]["bench.host_work"][0] == 3
    assert trace_reduce.WINDOW not in reduced["host_spans"]


@pytest.fixture(scope="module")
def spans_trace():
    return trace_reduce.reduce(os.path.join(DATA, "spans.xplane.pb"))


def test_program_spans_are_kept_and_runtime_events_are_not(spans_trace):
    assert sorted(spans_trace["host_spans"]) == sorted(
        SERVE + ("bench.batcher_step",))


def test_every_step_phase_is_counted_once_per_step(spans_trace):
    counts = {n: c for n, (c, _) in spans_trace["host_spans"].items()}
    assert counts["bench.batcher_step"] == 3
    assert all(counts[n] == 3 for n in SERVE), counts
    secs = {n: t for n, (_, t) in spans_trace["host_spans"].items()}
    # the phases lie inside the step, which lies inside the harness's span
    assert sum(secs[n] for n in SERVE[1:]) <= secs["serve.step"]
    assert secs["serve.step"] <= secs["bench.batcher_step"]
    assert secs["bench.batcher_step"] <= spans_trace["window_s"]
    assert 0.06 <= secs["serve.sample"]


def test_op_seconds_add_up_to_the_first_chips_busy_time(spans_trace):
    ops = spans_trace["op_seconds"]
    # the batcher's layer loop: its ops lie inside a while, which keeps
    # only its own time
    assert any(" while " in n for n in ops)
    assert sum(ops.values()) == pytest.approx(spans_trace["busy_s"], rel=1e-9)
    assert [tuple(x) for x in spans_trace["device_ops"]] == sorted(
        ops.items(), key=lambda kv: -kv[1])[:10]


def test_the_sleep_is_idle_time_under_serve_sample(spans_trace):
    gaps = dict(spans_trace["idle_gaps"])
    # three 20 ms sleeps
    assert spans_trace["idle_gaps"][0][0] == "serve.sample"
    assert 0.055 <= gaps["serve.sample"] <= 0.09
    idle = spans_trace["window_s"] - spans_trace["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)
