"""A run with the timed path broken underneath must come out not correct.

Each test skips the harness's look for a chip and drives the rest of a
run (set-up, window, check, result line) on the CPU at a small size, once
sound and once with one fault planted in the system under test:

- training: a step that returns its state unchanged; a step that leaves
  out half of the batch and takes the mean over the rest;
- serving: an output token altered where it is produced.

One chip has no exchange between chips, so that fault does not apply.
"""
import time

import jax
import pytest

from bench import drive, peaks, system
from bench.run import result_line
from bench.tests.conftest import CHAT, TRAIN, small_cell

PEAK = peaks.peaks("TPU v5 lite")
SEED = 2**31 + 99


def run(cell, driver, seconds=1.0):
    res = driver(cell, SEED, seconds, False, time.perf_counter(), PEAK)
    res["peak"] = PEAK
    return result_line(cell, res, jax.devices(), False)


def broken_train_step(monkeypatch, fault):
    real = system.train_step

    def train_step(c, batch, seq, compute_dtype="float32"):
        sb, _ = real(c, batch, seq, compute_dtype)
        if fault == "unchanged":
            def fn(feeds, state):
                return sb.fn(feeds, state)[0], state
        else:
            def fn(feeds, state):
                half = {k: v[: batch // 2] for k, v in feeds.items()}
                return sb.fn(half, state)
        return sb, jax.jit(fn)

    monkeypatch.setattr(system, "train_step", train_step)


def test_sound_train_run_is_correct(program):
    line = run(small_cell(TRAIN), drive.train)
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_train_fault_is_caught(program, monkeypatch, fault):
    broken_train_step(monkeypatch, fault)
    line = run(small_cell(TRAIN), drive.train)
    assert not line["correct"], line["checks"]


def altered_batcher(monkeypatch):
    real = system.batcher

    def batcher(c, params):
        b = real(c, params)
        step, V = b.step, c["vocab_size"]

        def altered():
            before = set(b.results)
            done = step()
            for s, req in enumerate(b.slot_req):
                if req is not None and b.slot_out[s]:
                    b.slot_out[s][-1] = (b.slot_out[s][-1] + V // 2) % V
            for rid in set(b.results) - before:
                toks = b.results[rid].tokens
                toks[-1] = (toks[-1] + V // 2) % V
            return done

        b.step = altered
        return b

    monkeypatch.setattr(system, "batcher", batcher)


def test_serve_runs(program, monkeypatch):
    cell = small_cell(CHAT, hidden=256)
    line = run(cell, drive.open_loop)
    assert line["correct"], line["checks"]
    altered_batcher(monkeypatch)
    line = run(cell, drive.open_loop)
    assert not line["correct"], line["checks"]
