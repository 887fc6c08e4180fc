#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process finds.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Steps: find the TPU (or exit non-zero with no result), turn on the compile
cache, make the weights on the device from the seed, warm up the cell's
shapes (set-up), measure for ``--seconds`` (the window), check what the
window produced against the plain reference, and print one JSON line.
With ``--trace 0`` its metrics are the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and its metrics are the
cell's per-layer metrics, read by ``bench/metrics/<metric>.py``.

Everything but the last line goes to earlier lines of stdout or stderr;
the numbers compared with the reference, each beside its limit, are the
last lines of stderr and the last key (``checks``) of the result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import check, drive, peaks, spec, system  # noqa: E402

PLATFORM = "tpu"


def result_line(cell, res, devices, trace: bool) -> dict:
    run = res["run"]
    run["peak"] = res["peak"]
    if trace:
        metrics = {}
        for m in cell.per_layer:
            value = spec.metric_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": res["end_to_end"][m["name"]],
                               "unit": m["unit"]} for m in cell.end_to_end}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": res["memory_peak_bytes"]}
    checks = check.judge(res["readings"], cell.limits)
    line = {"correct": check.correct(checks) and res["failed"] == 0,
            "attempted": res["attempted"], "failed": res["failed"],
            "metrics": metrics, "device": device}
    tr = run.get("trace")
    if trace and tr and tr["busy_s"] is not None:
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    line["checks"] = checks
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = spec.Cell(args.workload)
        system.import_program()
    except (OSError, KeyError, system.ProgramMissing) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    if devices[0].platform != PLATFORM or len(devices) < cell.chips:
        print(f"bench: the cell needs {cell.chips} {PLATFORM} chip(s); JAX "
              f"reports {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    print(f"[bench] {cell.name}: platform {devices[0].platform}, kind "
          f"{devices[0].device_kind}, {len(devices)} device(s); compile cache "
          f"{system.enable_compile_cache()}", flush=True)
    peak = peaks.peaks(devices[0].device_kind)
    res = drive.DRIVERS[cell.traffic["driver"]](
        cell, args.seed, args.seconds, bool(args.trace), T_START, peak)
    res["peak"] = peak
    for line in res["log"]:
        print(f"[bench] {line}", flush=True)
    print(f"[bench] set-up {res['setup_s']:.3f} s, peak memory "
          f"{res['memory_peak_bytes']} B, lowerings in window "
          f"{res['run']['lowerings_in_window']}", flush=True)
    line = result_line(cell, res, devices, bool(args.trace))
    for k, v in line["checks"].items():
        print(f"check {k} {v['value']:.6g} limit {v['limit']:.6g}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
