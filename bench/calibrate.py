#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, and the knee of a mix.

  python3 bench/calibrate.py limits --workload <cell> --seeds 1,2,... \\
      [--control-seeds 1,2,3] [--controls int8,bf16,half_batch] \\
      [--witness-seeds 1] [--seconds 5]
  python3 bench/calibrate.py knee --workload <open-loop cell> \\
      --rates 4,6,8 [--seconds 20] [--prompt-median 128]

``limits`` runs, in one process on the chip, the cell's own driver on each
seed (set-up, a short window at the cell's own load, the check) and prints
the numbers compared: the lower readings.  On ``--control-seeds`` it also
reads each of ``--controls``: ``int8``, the reference in the program's
place with its linear layers on int8 operands (serving: the tokens it puts
first, at the same prompts and served tokens); ``bf16``, the program's own
bfloat16 compute path (serving: the tokens a bfloat16 reference puts
first); and for training ``half_batch``, the planted fault of half of the
batch left out (the reference on half the rows, in the program's place).
On ``--witness-seeds`` (training) it runs the program once more with its
matmuls at ``highest`` precision: a second witness of where a gap comes
from.  Each reading is judged against the cell's limits
(``check.judge``) and says whether it would count as ``correct``.
``knee`` offers the open loop at each rate (``--prompt-median`` replaces
the mix's prompt median) and reports the tails, the requests completed
inside the window and those still unfinished when it closes.  Every
reading is one JSON line on stdout.  The benchmark's runs never call this.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import check, drive, peaks, spec, system  # noqa: E402

TRAIN_KEYS = ("first_loss_gap", "loss_gap", "loss_gaps", "grad_norm_gap",
              "grad_diff", "diff_leaf", "diff_gaps", "update1_gap",
              "update1_gaps", "update_norm_gap",
              "grad_leaf", "update_leaf", "left_out", "losses",
              "reference_losses", "grad_gaps", "update_gaps")


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def judged(cell, readings) -> dict:
    checks = check.judge(readings, cell.limits)
    return {"correct": check.correct(checks),
            "checks": {k: v["value"] for k, v in checks.items()}}


def seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def limits(cell, args, peak) -> None:
    t = cell.traffic
    train = t["driver"] == "train"
    keys = TRAIN_KEYS if train else ("logit_gap", "tokens_compared")
    controls = [x for x in args.controls.split(",") if x]
    for seed in seeds(args.seeds):
        t0 = time.perf_counter()
        res = drive.DRIVERS[t["driver"]](cell, seed, args.seconds, False,
                                         time.perf_counter(), peak)
        r = res["readings"]
        emit(kind="program", seed=seed, seconds=time.perf_counter() - t0,
             **judged(cell, r), **{k: r[k] for k in keys})
        if train and seed in seeds(args.witness_seeds):
            import jax

            with jax.default_matmul_precision("highest"):
                res = drive.train(cell, seed, args.seconds, False,
                                  time.perf_counter(), peak)
            emit(kind="program_highest", seed=seed,
                 **judged(cell, res["readings"]),
                 **{k: res["readings"][k] for k in TRAIN_KEYS})
        if seed not in seeds(args.control_seeds):
            continue
        for kind in controls:
            if not train:
                r = check.serve(cell.config, seed, res["samples"], control=kind)
            elif kind == "bf16":
                r = drive.train(cell, seed, args.seconds, False,
                                time.perf_counter(), peak,
                                compute_dtype="bfloat16")["readings"]
            else:
                low = check.train_reference(
                    cell.config, t, seed, int8=kind == "int8", keep=True,
                    rows=t["batch"] // 2 if kind == "half_batch" else 0)
                r = check.train_readings(low, check.train_reference(
                    cell.config, t, seed, against=low.pop("grad1_tree")))
            emit(kind=kind, seed=seed, **judged(cell, r),
                 **{k: r[k] for k in keys})


def knee(cell, args, peak) -> None:
    for rate in [float(r) for r in args.rates.split(",")]:
        t = copy.deepcopy(cell.traffic)
        t["rate_per_s"] = rate
        if args.prompt_median:
            t["prompt_len"]["median"] = args.prompt_median
        c = spec.Cell(cell.name, config=cell.config, traffic=t)
        res = drive.open_loop(c, 777, args.seconds, False, time.perf_counter(),
                              peak, drain_s=30.0)
        run = res["run"]
        emit(kind="knee", rate=rate, prompt_median=t["prompt_len"]["median"],
             attempted=res["attempted"], failed=res["failed"],
             completed_in_window=res["completed_in_window"],
             window_s=run["window_s"],
             unfinished_at_close=res["backlog_at_close"],
             **res["end_to_end"], steps=len(run["step_s"]),
             step_ms=1e3 * sum(run["step_s"]) / len(run["step_s"]),
             admit_wait_p50_ms=1e3 * float(np.median(run["admit_wait_s"])),
             admit_wait_p95_ms=1e3 * float(np.percentile(run["admit_wait_s"], 95)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("limits", "knee"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--witness-seeds", default="")
    ap.add_argument("--controls", default="int8,bf16,half_batch")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--prompt-median", type=float, default=0.0)
    args = ap.parse_args(argv)
    cell = spec.Cell(args.workload)
    system.import_program()
    import jax

    # a machine-wide cache where the environment names one, so that the
    # programs of one calibration are found again by the next
    system.enable_compile_cache(os.environ.get("JAX_COMPILATION_CACHE_DIR", ""))
    peak = peaks.peaks(jax.devices()[0].device_kind)
    (limits if args.mode == "limits" else knee)(cell, args, peak)
    return 0


if __name__ == "__main__":
    sys.exit(main())
