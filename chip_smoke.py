#!/usr/bin/env python3
"""Prove that the main path runs on a TPU at published widths.

  python chip_smoke.py                # one chip
  python chip_smoke.py --four-chips   # the mesh-sharded step on four chips

With no option the script runs these phases in one process, in order:

  device  JAX must report a TPU; there is no CPU fallback.
  train   repro.launch.train.train("smollm-360m") for a few
          steps at published widths (32 layers, d_model 960, vocab 49152,
          batch 8x256): every loss finite, the first near ln(vocab), the
          parameters on the TPU.
  kernels the compiled Pallas kernels the registry sends to the chip
          (flash_decode, ssd_scan) at published widths, against their
          references in repro.kernels.ref.
  serve   repro.launch.serve.serve("qwen2-0.5b") on a few
          requests: token ids in the vocabulary, and the last prompt
          position's logits, produced through the cache by serve_step,
          equal to a plain full forward over the same prompt.  Once at
          JAX's default matmul precision, the program serve.main runs,
          and once at float32, where the two paths agree to summation
          order.

With --four-chips it runs only the full-width smollm-360m train step
sharded over a (data=2, model=2) mesh and the same step, init and batch
on one device, and compares their first-step losses, gradients and
parameter updates.

Any failure exits non-zero.  On success the last line of stdout is one
JSON object: {"ok": true, "device": {"platform", "kind", "count"}}.  The
times it prints are single readings of a smoke run, not benchmark
numbers.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

PLATFORM = "tpu"
TRAIN_ARCH, SERVE_ARCH = "smollm-360m", "qwen2-0.5b"
# |first loss - ln(vocab)|: the synthetic tokens are uniform, and at
# init (std 0.02 weights) the logits are nearly flat
LOSS0_TOL = 0.5
# cached decode vs full forward, on the last prompt position's logits,
# relative to the largest logit.  At JAX's default precision an f32
# matmul on the TPU takes one bf16 pass, and the two paths round
# differently; at float32 they differ by summation order only.  A cache
# fault moves logits by O(1) of their scale.  The argmax is compared at
# float32 only: at one bf16 pass the paths differ by more than the top-2
# gap of random-weight logits.  Readings in PERF.md.
LOGITS_TOL = {"default": 3e-2, "float32": 1e-3}
# compiled kernel vs its reference at float32 matmul precision, relative
# to the largest output.  flash_decode returns bf16, so one rounding of
# its output is up to 2**-7 of the scale.  ssd_scan's f32 chunk matmuls
# take one bf16 pass in Mosaic (2.5e-3 at depth 256 on v5e), as XLA's do
# at the default precision.  A layout or recurrence fault gives O(1).
# Readings in PERF.md.
KERNEL_TOL = {"flash_decode": 8e-3, "ssd_scan": 2e-2}
# sharded (data=2, model=2) vs one device.  First-step loss: partial sums
# of the sharded contractions are added in another order.  After one
# step, per leaf, by numerics.update_drift: the gradient (AdamW's first
# moment, 0.1 x the clipped gradient) and the parameter update.  AdamW's
# first update is ~sign(grad), so summation noise flips near-zero
# gradient elements: k flips of n give 2*sqrt(k/n).  A dropped or wrong
# exchange between chips gives O(1) in both.  Readings in PERF.md.
MESH_LOSS_TOL = 2e-3
MESH_GRAD_TOL = 5e-2
MESH_UPDATE_TOL = 0.2


class SmokeFailure(Exception):
    """A phase found a wrong result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def phase_device() -> dict:
    import jax

    devs = jax.devices()
    d0 = devs[0]
    check(d0.platform == PLATFORM,
          f"JAX found no {PLATFORM} device (platform {d0.platform!r})")
    print(f"[device] platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devs)}", flush=True)
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def phase_train(*, smoke: bool = False, steps: int = 4, batch: int = 8,
                seq: int = 256, platform: str = PLATFORM) -> None:
    import jax

    from repro.configs import get_config
    from repro.launch.train import train

    res = train(TRAIN_ARCH, smoke=smoke, steps=steps,
                batch=batch, seq=seq, log_every=steps)
    losses = res["losses"]
    check(len(losses) == steps and all(math.isfinite(x) for x in losses),
          f"train losses not all finite: {losses}")
    ln_v = math.log(get_config(TRAIN_ARCH, smoke=smoke).vocab_size)
    check(abs(losses[0] - ln_v) <= LOSS0_TOL,
          f"first loss {losses[0]:.4f} is not within {LOSS0_TOL} of "
          f"ln(vocab) = {ln_v:.4f}")
    leaves = jax.tree.leaves(res["variables"]["params"])
    where = {d.platform for leaf in leaves for d in leaf.devices()}
    check(where == {platform}, f"parameters live on {where}, not {platform}")
    secs = res["step_seconds"]
    print(f"[train] losses {[round(x, 4) for x in losses]} "
          f"(ln vocab {ln_v:.4f})", flush=True)
    print(f"[train] first step (compile) {secs[0]:.3f} s", flush=True)
    print(f"[train] steady step {statistics.median(secs[1:]):.4f} s "
          f"(median of {len(secs) - 1})", flush=True)


def phase_kernels(*, smoke: bool = False, seed: int = 0) -> None:
    """flash_decode and ssd_scan, compiled, at the widths
    tests/test_tpu_compile.py compiles them, against repro.kernels.ref at
    float32 matmul precision.  ``smoke``: small widths in interpret mode,
    the CPU rehearsal."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref
    from repro.kernels.flash_decode import flash_decode_pallas
    from repro.kernels.ssd_scan import ssd_scan_pallas

    rs = np.random.RandomState(seed)
    # qwen2-0.5b decode: 4 sequences x 14 heads of 64 against a 2048 cache
    bh, t, d = (8, 256, 64) if smoke else (56, 2048, 64)
    q, k, v = (jnp.asarray(rs.randn(*shape), jnp.bfloat16)
               for shape in ((bh, d), (bh, t, d), (bh, t, d)))
    valid = jnp.asarray(rs.randint(1, t + 1, bh), jnp.int32)
    # mamba2-2.7b: 80 SSD heads of 64, state 128, chunk 256
    sbh, s, p, n, chunk = ((4, 128, 16, 8, 32) if smoke
                           else (80, 2048, 64, 128, 256))
    x = jnp.asarray(rs.randn(sbh, s, p) * 0.5, jnp.float32)
    dt = jnp.asarray(rs.rand(sbh, s) * 0.5, jnp.float32)
    a = -jnp.exp(jnp.asarray(rs.rand(sbh), jnp.float32))
    b_, c_ = (jnp.asarray(rs.randn(sbh, s, n) * 0.3, jnp.float32)
              for _ in range(2))
    cases = {
        "flash_decode": (
            lambda: flash_decode_pallas(q, k, v, valid, interpret=smoke),
            lambda: ref.flash_decode_ref(q, k, v, valid)),
        "ssd_scan": (
            lambda: ssd_scan_pallas(x, dt, a, b_, c_, chunk=chunk,
                                    interpret=smoke),
            lambda: ref.ssd_scan_ref(x, dt, a, b_, c_)),
    }
    for name, (kernel, reference) in cases.items():
        got = np.asarray(kernel(), np.float32)
        with jax.default_matmul_precision("float32"):
            want = np.asarray(jax.jit(reference)(), np.float32)
        diff = float(np.max(np.abs(got - want)))
        scale = float(np.max(np.abs(want)))
        print(f"[kernels] {name} {tuple(got.shape)}: max |diff| {diff:.3e}, "
              f"{diff / scale:.3e} of the largest output (limit "
              f"{KERNEL_TOL[name]})", flush=True)
        check(bool(np.isfinite(got).all()), f"{name} output not finite")
        check(diff <= KERNEL_TOL[name] * scale,
              f"{name} differs from its reference by {diff:.3e}")


def phase_serve(*, smoke: bool = False, batch: int = 4, prompt_len: int = 16,
                gen: int = 16, seed: int = 0) -> None:
    import contextlib

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.launch.serve import serve
    from repro.models import lm
    from repro.models.api import Model

    cfg = get_config(SERVE_ARCH, smoke=smoke)
    V = cfg.vocab_size
    # the plain reference: one full forward over the prompt with the
    # weights serve() made from the same seed
    model = Model.for_config(cfg)
    params = model.init(jax.random.PRNGKey(seed))

    def full_forward(params, tokens):
        x, _ = lm.forward(cfg, model.plan, params, tokens)
        return lm.logits_from_hidden(cfg, model.plan, params, x[:, -1:])

    for precision in LOGITS_TOL:
        with (contextlib.nullcontext() if precision == "default"
              else jax.default_matmul_precision(precision)):
            res = serve(SERVE_ARCH, smoke=smoke, batch=batch,
                        prompt_len=prompt_len, gen=gen, seed=seed)
            want = np.asarray(jax.jit(full_forward)(
                params, res["prompts"]))[:, 0, :V]
        toks = np.asarray(res["generated"])
        check(toks.shape == (batch, gen), f"generated shape {toks.shape}")
        check(bool(((toks >= 0) & (toks < V)).all()),
              f"token ids outside [0, {V})")
        got = np.asarray(res["prompt_logits"])[:, 0, :V]
        diff = float(np.max(np.abs(got - want)))
        limit = LOGITS_TOL[precision] * max(1.0, float(np.max(np.abs(want))))
        top2 = np.sort(want, axis=-1)[:, -2:]
        print(f"[serve] {precision} precision: cached vs full-forward "
              f"logits max |diff| {diff:.3e} (limit {limit:.3e}); smallest "
              f"top-2 gap {float(np.min(top2[:, 1] - top2[:, 0])):.3e}",
              flush=True)
        check(diff <= limit, f"cached logits differ from the full forward "
                             f"by {diff:.3e} at {precision} precision")
        if precision == "float32":
            check(bool((got.argmax(-1) == want.argmax(-1)).all()),
                  f"argmax differs: {got.argmax(-1)} vs {want.argmax(-1)}")
        print(f"[serve] {precision} precision: prefill {res['prefill_s']:.3f}"
              f" s, decode {res['decode_s']:.3f} s for {batch}x{gen} tokens",
              flush=True)


def phase_mesh(*, smoke: bool = False, steps: int = 3, batch: int = 8,
               seq: int = 256, seed: int = 0) -> None:
    """The mesh-sharded train step on (data=2, model=2) against the same
    step, init and batch on one device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core.numerics import update_drift
    from repro.data import SyntheticLMDataset, batch_iterator
    from repro.launch import mesh as mesh_mod
    from repro.launch.steps import build_train_step
    from repro.launch.train import TRAIN_HPARAMS
    from repro.models.api import Shape
    from repro.models.params import init_params
    from repro.optim import adamw_init
    from repro.parallel import sharding as shd

    devs = jax.devices()
    check(len(devs) >= 4, f"need 4 devices, JAX reports {len(devs)}")
    cfg = get_config(TRAIN_ARCH, smoke=smoke)
    shape = Shape("custom", seq, batch, "train")
    mesh = mesh_mod.make_mesh((2, 2), ("data", "model"))
    rules = mesh_mod.mesh_rules(mesh)
    with shd.axis_rules(rules, mesh):
        sb = build_train_step(cfg, shape, mesh, rules, lr=1e-3,
                              hparam_overrides=TRAIN_HPARAMS)

    def fresh_state():
        # made anew for each run: a donated buffer cannot be reused
        params = init_params(sb.model.describe_params(),
                             jax.random.PRNGKey(seed))
        return {"params": params, "opt": adamw_init(params)}

    batches = [{k: jnp.asarray(v) for k, v in b.items()}
               for _, b in zip(range(steps), batch_iterator(
                   SyntheticLMDataset(cfg.vocab_size, seq, seed=seed),
                   batch, 0))]

    def run(step, variables, feeds_of):
        """Losses, final state, and (params, first moment) after step 1."""
        losses, first = [], None
        for b in batches:
            loss, variables = step(feeds_of(b), variables)
            losses.append(float(loss))
            if first is None:
                first = jax.device_get((variables["params"],
                                        variables["opt"].m))
        return losses, variables, first

    one = devs[0]
    single = jax.jit(sb.fn, donate_argnums=(1,))
    v1 = jax.device_put(fresh_state(), one)
    p0 = jax.device_get(v1["params"])
    ref, v1, (p_ref, m_ref) = run(single, v1,
                                  lambda b: jax.device_put(b, one))
    del v1

    with shd.axis_rules(rules, mesh):
        sharded = jax.jit(sb.fn,
                          in_shardings=(sb.feed_shardings, sb.var_shardings),
                          out_shardings=sb.out_shardings,
                          donate_argnums=(1,))
        v4 = jax.device_put(fresh_state(), sb.var_shardings)
        got, v4, (p_got, m_got) = run(
            sharded, v4, lambda b: jax.device_put(b, sb.feed_shardings))
    leaves = jax.tree.leaves(v4["params"])
    spans = {len(leaf.sharding.device_set) for leaf in leaves}
    split = sum(leaf.sharding.shard_shape(leaf.shape) != leaf.shape
                for leaf in leaves)
    per_dev = {}
    for leaf in leaves:
        for s in leaf.addressable_shards:
            per_dev[s.device.id] = per_dev.get(s.device.id, 0) + s.data.nbytes
    total = sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
                for leaf in leaves)
    print(f"[mesh] one-device losses {[round(x, 4) for x in ref]}", flush=True)
    print(f"[mesh] (data=2, model=2) losses {[round(x, 4) for x in got]}",
          flush=True)
    print(f"[mesh] param leaves span {sorted(spans)} devices; {split} of "
          f"{len(leaves)} leaves split; param bytes per device "
          f"{sorted(per_dev.values())} of {total} in all", flush=True)
    check(all(math.isfinite(x) for x in ref + got), "mesh losses not finite")
    check(spans == {4}, f"sharded params span {spans} devices, not 4")
    check(split > 0, "no parameter leaf is split across the mesh")
    diff = abs(got[0] - ref[0])
    # the first moment starts at zero, so its drift is the gradient's
    grad = update_drift(jax.tree.map(np.zeros_like, m_ref), m_ref, m_got)
    upd = update_drift(p0, p_ref, p_got)
    print(f"[mesh] first-step loss |diff| {diff:.3e} (limit "
          f"{MESH_LOSS_TOL}); after step 1: gradient drift {grad:.3e} "
          f"(limit {MESH_GRAD_TOL}), update drift {upd:.3e} (limit "
          f"{MESH_UPDATE_TOL})", flush=True)
    check(diff <= MESH_LOSS_TOL,
          f"sharded first-step loss {got[0]} vs one device {ref[0]}")
    check(grad <= MESH_GRAD_TOL,
          f"sharded gradients differ from one device's by {grad:.3e}")
    check(upd <= MESH_UPDATE_TOL,
          f"sharded parameter update differs from one device's by {upd:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the (data=2, model=2) mesh-sharded train "
                         "step and its one-device comparison")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    try:
        device = phase_device()
        from repro.launch.cli import enable_compile_cache

        print(f"[device] compile cache {enable_compile_cache()}", flush=True)
        if args.four_chips:
            phase_mesh()
        else:
            phase_train()
            phase_kernels()
            phase_serve()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
