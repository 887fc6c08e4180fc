"""bench/trace_reduce.py on a small trace recorded on a TPU v5e by
``record_trace.py`` (three jitted matmul steps, each followed by 20 ms of
host-only work, inside a ``bench.window`` span), and on interval sets."""
import os

import pytest

from bench import trace_reduce

TRACE = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


def test_merge_unions_overlapping_intervals():
    assert trace_reduce.merge([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [
        (0, 2.5), (3, 4)]
    assert trace_reduce.merge([]) == []


def test_op_times_leave_out_containers_and_clip_to_the_window():
    ops = [("%while.1 = (s32[], f32[4]{0}) while((s32[], f32[4]{0}) %t), "
            "condition=%c, body=%b", 0, 10),
           ("%fusion.2 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop", 1, 3),
           ("%fusion.2 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop", 4, 9),
           ("%dot.3 = f32[4,4]{1,0} dot(f32[4]{0} %a, f32[4]{0} %b)", 11, 12)]
    assert dict(trace_reduce.op_times(ops, 0, 8)) == {
        "%fusion.2 fusion f32[4]": 6}


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
