"""The precision control, at a size a CPU holds, reads well above a sound
run of the same cell and seed.

The program's float32 matmuls take one bfloat16 pass on the chip, so the
control is the next precision below that: the reference with its linear
layers on int8 operands, in the program's place (training: its three
steps; serving: the token it puts first at each position of the same
prompts and served tokens).  On the chip, at the cells' own sizes, the
same controls set the upper readings of the limits (``calibrate.py``;
readings in PERF.md).  Here the CPU computes float32 exactly, so the sound
run reads at the level of summation order and the control far above it.
"""
import time

import pytest

from bench import check, drive, peaks
from bench.tests.conftest import CHAT, TRAIN, small_cell

PEAK = peaks.peaks("TPU v5 lite")
SEED = 2**31 + 7


def test_train_control_reads_far_above_the_program(program):
    cell = small_cell(TRAIN)
    sound = drive.train(cell, SEED, 0.5, False, time.perf_counter(), PEAK)
    int8 = check.train_reference(cell.config, cell.traffic, SEED, int8=True,
                                 keep=True)
    low = check.train_readings(int8, check.train_reference(
        cell.config, cell.traffic, SEED, against=int8.pop("grad1_tree")))
    for k in ("first_loss_gap", "grad_norm_gap", "grad_diff", "update_norm_gap"):
        assert low[k] > 100 * sound["readings"][k], k
    assert low["grad_diff"] > 1e-2


@pytest.mark.parametrize("hidden", [512])
def test_serve_control_reads_above_the_program(program, hidden):
    cell = small_cell(CHAT, hidden=hidden)
    cell.traffic["check_tokens"] = 300
    res = drive.open_loop(cell, 31, 3.0, False, time.perf_counter(), PEAK)
    low = check.serve(cell.config, 31, res["samples"], control="int8")
    assert low["tokens_compared"] == res["readings"]["tokens_compared"] >= 300
    assert low["logit_gap"] > max(10 * res["readings"]["logit_gap"], 1e-3)
