"""The hybrid of Mamba-2 and attention layers (``bench/arch/mamba2_hybrid``)
at a size a CPU holds: the program against the plain reference on the
weights the benchmark makes from a seed, through its full forward pass and
through the continuous batcher; the int8 control; the counts of a decode
step at the published sizes; the reader of ``ssm_state_ms.chat``."""
import copy
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, drive, flops, peaks, spec, weights
from bench.arch import mamba2_hybrid as arch
from bench.reference import mamba2_hybrid as ref

CELL = "granite-4.0-h-micro.serve-chat-knee80"
PEAK = peaks.peaks("TPU v5 lite")


def small_config(hidden=64, vocab=512, **deployment):
    c = copy.deepcopy(spec.Cell(CELL).config)
    c.update(hidden_size=hidden, num_attention_heads=4, num_key_value_heads=2,
             num_hidden_layers=4, layer_types=["mamba", "attention", "mamba", "mamba"],
             intermediate_size=2 * hidden, shared_intermediate_size=2 * hidden,
             vocab_size=vocab, mamba_n_heads=2 * hidden // 32, mamba_d_head=32,
             mamba_d_state=16, attention_multiplier=1.0 / 16)
    c["deployment"].update(deployment)
    return c


def wide_cell():
    """Every width as published, three layers (Mamba-2, attention,
    Mamba-2), a 4,096-token vocabulary, 4 slots of 128: the size at which
    the check's controls and planted faults are read on the CPU.  Cut in
    width as well, the greedy tokens stop depending on the state: its
    faults, and the int8 control, then read 0 on some seeds."""
    c = copy.deepcopy(spec.Cell(CELL).config)
    c.update(num_hidden_layers=3, layer_types=["mamba", "attention", "mamba"],
             vocab_size=4096)
    c["deployment"].update(n_slots=4, max_seq=128)
    t = copy.deepcopy(spec.Cell(CELL).traffic)
    t.update(rate_per_s=20.0, check_tokens=300,
             prompt_len=dict(t["prompt_len"], median=8, min=2, max=32),
             output_len=dict(t["output_len"], median=6, min=2, max=16))
    return spec.Cell(CELL, config=c, traffic=t)


def test_groups_follow_layer_types(program):
    from repro.models import lm

    c = spec.Cell(CELL).config
    assert ref.groups(c) == [("mamba", 5), ("attention", 1), ("mamba", 9),
                             ("attention", 1), ("mamba", 4)]
    cfg = program.model_config(c)
    assert [(g.kind, g.count) for g in lm.block_groups(cfg)] == [
        ("ssm_mlp", 5), ("attn", 1), ("ssm_mlp", 9), ("attn", 1), ("ssm_mlp", 4)]


def test_logits_match_the_program(program):
    from repro.models import lm
    from repro.models.api import Model

    c = small_config()
    model = Model.for_config(program.model_config(c))
    w = weights.make(c, weights.key_for(5, 0))
    program.check_layout(model, w)
    toks = np.random.default_rng(0).integers(0, c["vocab_size"], (2, 24))
    with jax.default_matmul_precision("highest"):
        x, _ = lm.forward(model.cfg, model.plan, w, jnp.asarray(toks))
        want = lm.logits_from_hidden(model.cfg, model.plan, w, x)
        got = jnp.stack([ref.logits(c, w, jnp.asarray(t)) for t in toks])
    # float32 on both sides, summed in other orders; at this size the logits
    # spread about 2e-3, the recurrence's own part of them is about 6e-4 and
    # a dropped conv bias's 3e-3
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-5)


def test_batcher_decode_matches_the_reference(program):
    """Requests through two slots, so that a slot is reused: each step's
    logits for each request against the reference's full forward pass
    over the request's prompt and served tokens."""
    from repro.models.api import Model
    from repro.serving import ContinuousBatcher, Request

    c = small_config()
    model = Model.for_config(program.model_config(c))
    w = weights.make(c, weights.key_for(8, 0))
    rng = np.random.default_rng(3)
    sizes = [(9, 8), (2, 14), (5, 4), (3, 6)]
    prompts = [rng.integers(0, c["vocab_size"], n).tolist() for n, _ in sizes]
    with jax.default_matmul_precision("highest"):
        b = ContinuousBatcher(model, w, n_slots=2, max_seq=64)
        for i, (p, (_, k)) in enumerate(zip(prompts, sizes)):
            b.submit(Request(rid=i, prompt=p, max_new_tokens=k))
        rows, slots = {}, {}
        sample = b._sample

        def record(live, logits_np):
            for s in live:
                rid = b.slot_req[s].rid
                rows.setdefault(rid, []).append(logits_np[s, :c["vocab_size"]].copy())
                slots.setdefault(rid, s)
            return sample(live, logits_np)

        b._sample = record
        b.run_until_drained()
        assert sorted(slots.values()) == [0, 0, 1, 1]
        for i, p in enumerate(prompts):
            served = b.results[i].tokens
            want = ref.logits(c, w, jnp.asarray(p + served[:-1], jnp.int32))
            np.testing.assert_allclose(np.stack(rows[i]), want, atol=1e-6,
                                       rtol=1e-5, err_msg=str(i))


def test_control_reads_above_the_program(program):
    """On the CPU the program computes float32 exactly, so it reads at the
    level of summation order; the control puts other tokens first where
    the reference's best logits lie close (its logits are small: the tied
    embedding is drawn at 0.02 / 12)."""
    cell = wide_cell()
    res = drive.open_loop(cell, 41, 3.0, False, time.perf_counter(), PEAK)
    assert res["failed"] == 0
    low = check.serve(cell.config, 41, res["samples"], control="int8")
    assert low["tokens_compared"] == res["readings"]["tokens_compared"] >= 300
    assert low["logit_gap"] > max(10 * res["readings"]["logit_gap"], 1e-3)


def _state_not_carried(monkeypatch):
    from repro.models import layers

    step = layers.ssd_decode_step

    def forgetful(*args):
        y, _ = step(*args)
        return y, args[-1]

    monkeypatch.setattr(layers, "ssd_decode_step", forgetful)


def _reset_skipped(monkeypatch):
    from repro.serving import batcher

    monkeypatch.setattr(batcher.ContinuousBatcher, "_reset_slot_cache",
                        lambda self, s: None)


@pytest.mark.parametrize("plant", [_state_not_carried, _reset_skipped],
                         ids=["state_not_carried", "reset_skipped"])
def test_state_faults_read_above_the_program(program, monkeypatch, plant):
    """A slot step that never writes its SSM state back, and an admission
    that leaves the previous request's state and conv windows in the
    slot, each read far above the sound program on the same seed, and
    above the cell's limit."""
    cell = wide_cell()
    sound = drive.open_loop(cell, 43, 3.0, False, time.perf_counter(), PEAK)
    plant(monkeypatch)
    res = drive.open_loop(cell, 43, 3.0, False, time.perf_counter(), PEAK)
    assert res["failed"] == sound["failed"] == 0
    gap = res["readings"]["logit_gap"]
    assert gap > max(10 * sound["readings"]["logit_gap"],
                     cell.limits["logit_gap"]), (gap, sound["readings"]["logit_gap"])


def test_parameters_and_decode_step_by_hand():
    c = spec.Cell(CELL).config
    D, F, V, I, GN, H, K = 2048, 8192, 100_352, 4096, 128, 64, 4
    mlp = 3 * D * F
    # in_proj (z, x, B, C, dt), conv weights and biases, dt_bias, A_log,
    # D, gated norm, out_proj, two norms, the MLP
    mamba = (D * (2 * I + 2 * GN + H) + (I + 2 * GN) * (K + 1) + 3 * H + I
             + I * D + 2 * D + mlp)
    attn = 2 * D * D + 2 * D * 512 + 2 * D + mlp
    assert weights.n_params(c) == 18 * mamba + 2 * attn + V * D + D \
        == 1_698_459_520
    mm = 18 * (D * (2 * I + 2 * GN + H) + I * D + mlp) + 2 * (
        2 * D * D + 2 * D * 512 + mlp) + V * D
    assert flops.matmul_params(c) == mm
    kv = 2 * 2 * 8 * 64 * 4
    assert flops.kv_entry_bytes(c) == kv == 8192
    ssm = 18 * (H * 64 * 128 * 4 + 3 * (I + 2 * GN) * 4)
    assert arch.ssm_slot_bytes(c) == ssm == 38_688_768
    w = flops.decode_step(c, [0, 9])
    state_flops = 18 * (4 * H * 64 * 128 + 2 * K * (I + 2 * GN))
    assert w["flops"] == 2 * (2 * mm + state_flops) + 4 * 2 * 2048 * (1 + 10)
    assert w["bytes"] == 4 * weights.n_params(c) + (1 + 10) * kv + 2 * kv \
        + 2 * 2 * ssm
    assert flops.decode_step(c, [])["bytes"] == 4 * weights.n_params(c)


def test_init_follows_the_stated_rules():
    c = small_config()
    w = weights.make(c, weights.key_for(9, 0))
    g0 = w["g0"]
    A = np.exp(np.asarray(g0["A_log"]))
    assert A.min() >= 1.0 and A.max() <= 16.0
    np.testing.assert_array_equal(g0["D_skip"], np.float32(0.1))
    dt = np.log1p(np.exp(np.asarray(g0["dt_bias"])))
    assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 1e-1 * 1.001
    bound = 1.0 / np.sqrt(c["mamba_d_conv"])
    for k in ("conv_x", "conv_B", "conv_C", "conv_x_b", "conv_B_b", "conv_C_b"):
        a = np.asarray(g0[k])
        assert np.abs(a).max() <= bound and a.std() > bound / 3, k
    assert abs(float(np.std(g0["wz"])) - 0.02) < 2e-3
    assert abs(float(np.std(w["embed"])) - 0.02 / 12) < 2e-4


def test_conv_bias_and_recurrence_move_the_logits():
    """The reference's conv bias and state each change its logits by far
    more than the tolerance of the comparisons above."""
    c = small_config()
    w = weights.make(c, weights.key_for(5, 0))
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 512, 24))

    def edit(leaf, fn):
        return jax.tree_util.tree_map_with_path(
            lambda p, a: fn(a) if str(p[-1].key).endswith(leaf) else a, w)

    with jax.default_matmul_precision("highest"):
        base = ref.logits(c, w, toks)
        no_bias = ref.logits(c, edit("_b", jnp.zeros_like), toks)
        no_memory = ref.logits(c, edit("A_log", lambda a: a + 20.0), toks)
    assert float(jnp.max(jnp.abs(no_bias - base))) > 1e-3
    assert float(jnp.max(jnp.abs(no_memory - base))) > 3e-4


def _reader():
    return spec.metric_reader("ssm_state_ms.chat")


def test_ssm_state_reader_sums_the_state_ops():
    c = spec.Cell(CELL).config
    ops = {
        "%fusion.43 fusion f32[64,5,1,64,64,128]": 0.5,     # update, group 0
        "%fusion.12 fusion f32[64,9,1,64,64,128]": 0.25,    # update, group 2
        "%fusion.44 fusion f32[64,64,64]": 0.125,           # readout C.h
        "%copy.3 copy f32[64,9,1,3,4096]": 0.0625,          # conv windows
        "%fusion.7 fusion f32[64,4,1,3,128]": 0.03125,
        "%fusion.9 fusion f32[64,1,100352]": 4.0,           # logits: not counted
        "%dynamic-update-slice.1 dynamic-update-slice f32[64,2,1,2048,8,64]": 2.0,
        "%fusion.2 fusion f32[64,2048]": 1.0,
    }
    run = {"config": c, "trace": {"op_seconds": ops,
                                  "host_spans": {"serve.step": [1000, 30.0]}}}
    assert _reader()(run) == pytest.approx(1e3 * 0.96875 / 1000)


@pytest.mark.parametrize("run", [
    {"trace": None},
    {"trace": {"op_seconds": {"%fusion.1 fusion f32[64,2048]": 1.0},
               "host_spans": {"serve.step": [10, 1.0]}}},
    {"trace": {"op_seconds": {"%fusion.43 fusion f32[64,5,1,64,64,128]": 1.0},
               "host_spans": {}}},
], ids=["no_trace", "no_state_op", "no_step"])
def test_ssm_state_reader_reads_nothing_where_nothing_matches(run):
    run = dict(run, config=spec.Cell(CELL).config)
    assert _reader()(run) is None


def test_ssm_state_reader_needs_a_state_space_configuration():
    run = {"config": spec.Cell("qwen2-0.5b.serve-chat").config,
           "trace": {"op_seconds": {"%fusion.43 fusion f32[64,5,1,64,64,128]": 1.0},
                     "host_spans": {"serve.step": [10, 1.0]}}}
    assert _reader()(run) is None
