#!/usr/bin/env python3
"""Record the small trace ``test_trace_reduce.py`` reads, on a chip.

  python3 bench/tests/record_trace.py <out_dir>

Runs a few jitted matmuls inside a ``bench.window`` host span, with a
host-only sleep between them under ``bench.host_work``, under the
profiler; copies the ``.xplane.pb`` to ``<out_dir>/small.xplane.pb`` and
prints every plane and line with its event count and time range, and the
numbers the test expects.
"""
from __future__ import annotations

import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from bench import trace_reduce

    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((2048, 2048), jnp.float32)
    f(x).block_until_ready()
    tdir = tempfile.mkdtemp()
    jax.profiler.start_trace(tdir)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.host_work"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    path = os.path.join(out_dir, "small.xplane.pb")
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(trace_reduce.find_xplane(tdir), path)
    shutil.rmtree(tdir, ignore_errors=True)
    for plane in ProfileData.from_file(path).planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            if evs:
                print(f"  line {line.name!r}: {len(evs)} events, "
                      f"{min(e.start_ns for e in evs):.0f}.."
                      f"{max(e.end_ns for e in evs):.0f} ns, e.g. "
                      f"{sorted({e.name for e in evs})[:6]}")
    print(trace_reduce.reduce(path))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
