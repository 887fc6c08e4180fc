"""Compile the main path for a described TPU v5e chip, with no chip.

The TPU compiler refuses what interpret mode passes: blocks that break
the (8, 128) tiling rule, rank-1 blocks, programs that do not fit in
HBM.  Each test here lowers a Pallas kernel or a whole jitted step at
published widths against ``jax.ShapeDtypeStruct`` stand-ins placed on a
described v5e device, and compiles it.  Nothing runs.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports every test file.
"""
import contextlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with contextlib.ExitStack() as stack:
        mp = stack.enter_context(pytest.MonkeyPatch.context())
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep it out
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        stack.callback(jax.config.update, "jax_enable_compilation_cache",
                       enabled)
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _kernel_cases():
    from repro.kernels import ops
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.kernels.flash_decode import flash_decode_pallas
    from repro.kernels.ssd_scan import ssd_scan_pallas

    S = jax.ShapeDtypeStruct
    bf16, f32 = jnp.bfloat16, jnp.float32
    # smollm-360m: 8 sequences x 15 heads of 64 over 2048 positions
    bh, t, d = 120, 2048, 64
    # mamba2-2.7b: 80 SSD heads of 64, state 128, chunk 256
    ssd_bh, ssd_s, ssd_p, ssd_n = 80, 2048, 64, 128
    return {
        "flash_attention": (
            lambda q, k, v: flash_attention_pallas(q, k, v, causal=True),
            (S((bh, t, d), bf16),) * 3),
        # qwen2-0.5b decode: 4 sequences x 14 heads against a 2048 cache
        "flash_decode": (
            flash_decode_pallas,
            (S((56, d), bf16), S((56, t, d), bf16), S((56, t, d), bf16),
             S((56,), jnp.int32))),
        "ssd_scan": (
            lambda x, dt, a, b, c: ssd_scan_pallas(x, dt, a, b, c, chunk=256),
            (S((ssd_bh, ssd_s, ssd_p), f32), S((ssd_bh, ssd_s), f32),
             S((ssd_bh,), f32), S((ssd_bh, ssd_s, ssd_n), f32),
             S((ssd_bh, ssd_s, ssd_n), f32))),
        "rmsnorm": (lambda x, w: ops.rmsnorm(x, w),
                    (S((2048, 960), f32), S((960,), f32))),
        # qwen2-0.5b MLP up-projection
        "matmul": (ops.matmul, (S((2048, 896), bf16), S((896, 4864), bf16))),
        "compress16": (ops.compress16, (S((1 << 20,), f32),)),
    }


@pytest.mark.parametrize("name", ["flash_attention", "flash_decode",
                                  "ssd_scan", "rmsnorm", "matmul",
                                  "compress16"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _kernel_cases()[name]
    compiled = jax.jit(fn).lower(*_on(one_chip, shapes)).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the Mosaic kernel


def test_smollm_train_step_compiles_and_fits_v5e(one_chip):
    """The full-width jitted train step as ``launch.train`` builds it
    (f32 compute, batch 8x256) fits in one chip's HBM."""
    from repro.configs import get_config
    from repro.launch.steps import build_train_step
    from repro.launch.train import TRAIN_HPARAMS
    from repro.models.api import Shape

    sb = build_train_step(get_config("smollm-360m"),
                          Shape("custom", 256, 8, "train"),
                          hparam_overrides=TRAIN_HPARAMS)
    compiled = jax.jit(sb.fn, donate_argnums=(1,)).lower(
        _on(one_chip, sb.feed_specs), _on(one_chip, sb.var_specs)).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes > 4e9  # params + AdamW state, f32
    assert used < V5E_HBM_BYTES, used


def test_qwen2_decode_step_compiles_for_v5e(one_chip):
    from repro.configs import get_config
    from repro.launch.steps import build_serve_step
    from repro.models.api import Shape

    sb = build_serve_step(get_config("qwen2-0.5b"),
                          Shape("custom", 128, 4, "decode"),
                          hparam_overrides={"compute_dtype": jnp.float32})
    compiled = jax.jit(sb.fn).lower(
        _on(one_chip, sb.feed_specs), _on(one_chip, sb.var_specs)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES


def _qwen2_slot_cache(one_chip, n_slots=64, max_seq=2048):
    """qwen2-0.5b's model, parameters and slot-leading cache as the batcher
    holds it, ``(n_slots,) + init_cache_desc(batch=1)``, as shapes."""
    from repro.configs import get_config
    from repro.models.api import Model
    from repro.models.params import abstract_params

    model = Model.for_config(get_config("qwen2-0.5b"))
    one = abstract_params(model.init_cache_desc(batch=1, max_seq=max_seq))
    cache = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n_slots,) + s.shape, s.dtype), one)
    return (model, _on(one_chip, model.abstract_params()),
            _on(one_chip, cache), _on(one_chip, one))


def _hlo_ops(text):
    """(opcode, element type, dims) of each HLO instruction."""
    pat = re.compile(r"^\s*(?:ROOT\s+)?%\S+ = (\w+)\[([\d,]*)\]\S* ([\w-]+)\(")
    return [(m.group(3), m.group(1), m.group(2))
            for m in map(pat.match, text.splitlines()) if m]


def _compile_slot_step(one_chip, model, params, cache, n):
    """The batcher's slot step for ``n`` slots, compiled, and the bytes of
    the cache it is given."""
    from repro.serving.batcher import _slot_step_for

    compiled = _slot_step_for(model).lower(
        params, cache, _on(one_chip, jax.ShapeDtypeStruct((n, 1), jnp.int32)),
        _on(one_chip, jax.ShapeDtypeStruct((n,), jnp.int32))).compile()
    return compiled, sum(s.size * s.dtype.itemsize
                         for s in jax.tree.leaves(cache))


@pytest.fixture(scope="module")
def qwen2_slot_step(one_chip):
    """qwen2-0.5b's slot step at 64 slots x 2048, compiled once."""
    model, params, cache, _ = _qwen2_slot_cache(one_chip, 64, 2048)
    return _compile_slot_step(one_chip, model, params, cache, 64)


@pytest.fixture(scope="module")
def granite_slot_step(one_chip):
    """granite-4.0-h-micro's slot step, cut to two periods (18 Mamba-2 and
    2 attention layers) at published widths, 64 slots x 2048, compiled
    once."""
    import dataclasses

    from repro.configs import get_config
    from repro.models.api import Model
    from repro.models.params import abstract_params

    full = get_config("granite-4.0-h-micro")
    cfg = dataclasses.replace(full, n_layers=20, layer_types=full.layer_types[:20])
    model = Model.for_config(cfg)
    one = abstract_params(model.init_cache_desc(batch=1, max_seq=2048))
    cache = _on(one_chip, jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((64,) + s.shape, s.dtype), one))
    return _compile_slot_step(one_chip, model,
                              _on(one_chip, model.abstract_params()), cache, 64)


def test_qwen2_slot_step_updates_the_cache_in_place_on_v5e(qwen2_slot_step):
    """The batcher's slot step at 64 slots x 2048 aliases the donated
    cache and neither copies, converts, transposes nor restacks it: no
    copy, convert, transpose or fusion yields a whole-cache or
    whole-layer shape, in any element type."""
    n, span = 64, 2048
    compiled, cache_bytes = qwen2_slot_step
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes <= 2.5e9, mem.temp_size_in_bytes
    layers, kv, hd = 24, 2, 64
    whole = {f"{n},{layers},1,{span},{kv},{hd}",
             f"{layers},{n},1,{span},{kv},{hd}",
             f"{n},1,{span},{kv},{hd}",
             f"1,{n},1,{span},{kv},{hd}"}
    moved = [(op, ty, dims) for op, ty, dims in _hlo_ops(compiled.as_text())
             if dims in whole and op in ("copy", "copy-start", "convert",
                                         "transpose", "fusion")]
    assert not moved, moved


def test_qwen2_slot_reset_updates_one_slot_in_place_on_v5e(one_chip):
    """The batcher's slot reset, one executable for every slot, aliases
    the donated cache and copies no array."""
    from repro.serving.batcher import _reset_slot

    _, _, cache, empty = _qwen2_slot_cache(one_chip)
    compiled = _reset_slot.lower(
        cache, empty,
        _on(one_chip, jax.ShapeDtypeStruct((), jnp.int32))).compile()
    mem = compiled.memory_analysis()
    cache_bytes = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(cache))
    assert mem.alias_size_in_bytes >= cache_bytes
    copies = [(op, ty, dims) for op, ty, dims in _hlo_ops(compiled.as_text())
              if op in ("copy", "copy-start") and ty == "f32"]
    assert not copies, copies


def test_granite_slot_step_updates_the_state_in_place_on_v5e(
        granite_slot_step):
    """granite-4.0-h-micro's slot step, cut to two periods (18 Mamba-2 and
    2 attention layers) at published widths, 64 slots x 2048, aliases the
    donated cache, and rewrites each layer's SSM state in place: the only
    ops whose result is a group's whole state are the 18 layer updates
    (fusions), and no op yields one layer's state for all slots (a copy
    of it before its update)."""
    n = 64
    compiled, cache_bytes = granite_slot_step
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes
    state = "64,64,128"   # a layer's state: 64 heads of 64 x state 128
    groups = {f"{n},{g},1,{state}" for g in (5, 9, 4)}
    text = compiled.as_text()
    entry = text[text.index("\nENTRY"):]   # not the fusions' insides
    ops = [(op, dims) for op, _, dims in _hlo_ops(entry)
           if dims.endswith(state) and op != "parameter"]
    assert sorted(dims for op, dims in ops) == sorted(
        [f"{n},5,1,{state}"] * 5 + [f"{n},9,1,{state}"] * 9
        + [f"{n},4,1,{state}"] * 4), ops
    assert all(op == "fusion" and dims in groups for op, dims in ops), ops


@pytest.mark.parametrize("model, vocab", [("qwen2", 151936),
                                          ("granite", 100352)])
def test_slot_step_returns_greedy_tokens_not_a_logits_copy_on_v5e(
        request, model, vocab):
    """The slot step at 64 slots returns each slot's greedy token as
    ``s32[64]`` beside the logits and the donated cache, and no ``copy``
    yields the logits, squeezed or not: no relayout of the whole row.  The
    logits reach their output buffer whole once, by the compiler's
    asynchronous ``copy-start``/``copy-done`` out of the fusion that
    computed them, in the same layout."""
    compiled, cache_bytes = request.getfixturevalue(f"{model}_slot_step")
    assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes
    tokens, logits, _ = compiled.out_info
    assert (tokens.shape, tokens.dtype) == ((64,), jnp.int32)
    assert (logits.shape, logits.dtype) == ((64, vocab), jnp.float32)
    copies = [(op, ty, dims) for op, ty, dims in _hlo_ops(compiled.as_text())
              if op == "copy" and dims in (f"64,{vocab}", f"64,1,{vocab}")]
    assert not copies, copies
