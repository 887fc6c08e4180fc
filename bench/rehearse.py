#!/usr/bin/env python3
"""Compile a cell's programs for a described TPU v5e, with no chip.

  JAX_PLATFORMS=cpu python bench/rehearse.py --workload smollm-360m.train-2k
  JAX_PLATFORMS=cpu python bench/rehearse.py --workload qwen2-0.5b.serve-chat --size 128

Prints each program's ``memory_analysis()`` against the chip's memory.
``--size`` tries another micro-batch (training) or slot count (serving) than
the cell's files give; that is how the cell's size was chosen.  Nothing runs.
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import system  # noqa: E402
from bench.spec import Cell  # noqa: E402

HBM_BYTES = 16 * 2**30   # a v5e's 16 GiB


def report(name: str, compiled) -> int:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"[rehearse] {name}: arguments {m.argument_size_in_bytes:,} B, "
          f"outputs {m.output_size_in_bytes:,} B, aliased "
          f"{m.alias_size_in_bytes:,} B, temporaries {m.temp_size_in_bytes:,} B"
          f"; in all {total:,} B = {total / HBM_BYTES:.1%} of 16 GiB", flush=True)
    return total


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--size", type=int, default=0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    system.import_program()
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import weights

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), tree)

    cell = Cell(args.workload)
    c, t = cell.config, cell.traffic
    params = on_chip(jax.eval_shape(
        lambda: weights.make(c, jax.random.PRNGKey(0))))
    if t["driver"] == "train":
        batch = args.size or t["batch"]
        sb, step = system.train_step(c, batch, t["seq"])
        state = on_chip({"params": params,
                         "opt": jax.eval_shape(system.optimizer_init, params)})
        feeds = on_chip({k: jax.ShapeDtypeStruct((batch, t["seq"]), jnp.int32)
                         for k in ("tokens", "labels")})
        report(f"train step {batch}x{t['seq']}",
               step.lower(feeds, state).compile())
        return 0
    from repro.models.api import Model
    from repro.models.params import abstract_params
    from repro.serving.batcher import _slot_step_for

    dep = c["deployment"]
    n = args.size or dep["n_slots"]
    model = Model.for_config(system.model_config(c))
    one = abstract_params(model.init_cache_desc(batch=1, max_seq=dep["max_seq"]))
    cache = on_chip(jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), one))
    step = _slot_step_for(model)
    report(f"slot step, {n} slots x {dep['max_seq']}", step.lower(
        params, cache, on_chip(jax.ShapeDtypeStruct((n, 1), jnp.int32)),
        on_chip(jax.ShapeDtypeStruct((n,), jnp.int32))).compile())
    reset = jax.jit(lambda full, empty: jax.tree.map(
        lambda f, e: f.at[0].set(e), full, empty))
    report(f"slot reset, {n} slots", reset.lower(cache, on_chip(one)).compile())
    return 0


if __name__ == "__main__":
    sys.exit(main())
