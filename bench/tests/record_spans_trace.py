#!/usr/bin/env python3
"""Record the trace ``test_trace_reduce.py`` reads beside ``small.xplane.pb``,
on a chip.

  python3 bench/tests/record_spans_trace.py <out_dir>

Serves two requests through a ``ContinuousBatcher`` of the program (the
reduced qwen2-0.5b preset, two slots) for three steps under the profiler,
each step inside ``bench.batcher_step`` and all of them inside a
``bench.window`` host span, as ``bench/drive.py`` does.  Every step holds
the batcher's own ``serve.*`` spans: a real step on the device, a real
copy of its logits to the host, and a 20 ms host-only sleep put in front
of the sampling, under ``serve.sample``.  Copies the ``.xplane.pb`` to
``<out_dir>/spans.xplane.pb`` and prints every plane and line with its
event count, and its reduction.
"""
from __future__ import annotations

import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

SLEEP_S = 0.02
STEPS = 3


def main(out_dir: str) -> int:
    from bench import system

    system.import_program()
    import jax
    from jax.profiler import ProfileData

    from bench import trace_reduce
    from repro.configs import get_config
    from repro.models.api import Model
    from repro.serving import ContinuousBatcher, Request

    model = Model.for_config(get_config("qwen2-0.5b", smoke=True))
    b = ContinuousBatcher(model, model.init(jax.random.PRNGKey(0)),
                          n_slots=2, max_seq=64)

    def submit():
        b.submit(Request(rid=0, prompt=[1], max_new_tokens=STEPS))
        b.submit(Request(rid=1, prompt=[2, 3], max_new_tokens=STEPS - 1))

    submit()
    b.run_until_drained()  # compiles outside the trace
    b.results.clear()
    sample = b._sample

    def slow_sample(live, logits_np):
        time.sleep(SLEEP_S)
        return sample(live, logits_np)

    b._sample = slow_sample
    submit()
    tdir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        for _ in range(STEPS):
            with jax.profiler.TraceAnnotation("bench.batcher_step"):
                b.step()
    jax.profiler.stop_trace()
    assert len(b.results) == 2 and not b._live(), b.results
    path = os.path.join(out_dir, "spans.xplane.pb")
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(trace_reduce.find_xplane(tdir), path)
    shutil.rmtree(tdir, ignore_errors=True)
    for plane in ProfileData.from_file(path).planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            if evs:
                print(f"  line {line.name!r}: {len(evs)} events, e.g. "
                      f"{sorted({e.name for e in evs})[:6]}")
    print(f"{os.path.getsize(path)} bytes")
    print(trace_reduce.reduce(path))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
