import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove every (arch × shape × mesh) lowers + compiles.

For each combination this script builds the step function (through the
repro.core graph + §10 lowering), jits it with the mesh shardings, lowers
against ShapeDtypeStruct stand-ins (no allocation), compiles, and records
``memory_analysis()`` / ``cost_analysis()`` / the collective schedule into
``experiments/dryrun/<arch>__<shape>__<mesh>.json`` for §Roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--archs a,b] [--shapes s,t]
"""
import argparse
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax

from ..configs import ALIASES, get_config
from ..models.api import SHAPES
from ..parallel import sharding as shd
from . import mesh as mesh_mod
from . import roofline as roofline_mod
from .steps import build_step

# the chip the production meshes are made of; its peaks come from
# roofline.PEAKS (the host devices this script compiles on are stand-ins)
TARGET_DEVICE_KIND = "TPU v5 lite"

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            rules_overrides: Optional[Dict[str, Any]] = None,
            hparam_overrides: Optional[Dict[str, Any]] = None,
            out_dir: Optional[str] = None,
            tag: str = "", verbose: bool = True) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = mesh_mod.make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2pod_512" if multi_pod else "1pod_256"
    n_dev = int(np_prod(mesh.devices.shape))
    rules = mesh_mod.mesh_rules(mesh, overrides=rules_overrides)

    t0 = time.time()
    with shd.axis_rules(rules, mesh):
        bundle = build_step(cfg, shape_name, mesh, rules,
                            hparam_overrides=hparam_overrides)
        jf = jax.jit(bundle.fn,
                     in_shardings=(bundle.feed_shardings, bundle.var_shardings),
                     out_shardings=bundle.out_shardings,
                     donate_argnums=(1,))
        lowered = jf.lower(bundle.feed_specs, bundle.var_specs)
        compiled = lowered.compile()
    t1 = time.time()

    mem = compiled.memory_analysis()
    rl = roofline_mod.analyze(compiled, arch=arch, shape=shape,
                              mesh_name=mesh_name, n_devices=n_dev,
                              device_kind=TARGET_DEVICE_KIND,
                              cfg=cfg, model=bundle.model)
    record = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "kind": bundle.kind, "n_devices": n_dev,
        "compile_seconds": round(t1 - t0, 2),
        "graph_nodes": bundle.graph_nodes,
        "memory_analysis": rl.memory,
        "per_device_total_bytes": (mem.argument_size_in_bytes
                                   + mem.temp_size_in_bytes
                                   + mem.output_size_in_bytes
                                   - mem.alias_size_in_bytes),
        "roofline": rl.to_dict(),
        "rules_overrides": rules_overrides or {},
        "hparam_overrides": {k: str(v) for k, v in (hparam_overrides or {}).items()},
        "tag": tag,
    }
    if verbose:
        hbm = record["per_device_total_bytes"] / 2**30
        print(f"[dryrun] {arch:20s} {shape_name:12s} {mesh_name}: "
              f"compile {record['compile_seconds']:6.1f}s  "
              f"HBM/dev {hbm:6.2f} GiB  dominant={rl.dominant:10s} "
              f"c/m/coll = {rl.compute_s*1e3:.1f}/{rl.memory_s*1e3:.1f}/"
              f"{rl.collective_s*1e3:.1f} ms  useful={rl.useful_ratio:.2f}",
              flush=True)

    od = out_dir or OUT_DIR
    os.makedirs(od, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    path = os.path.join(od, f"{arch}__{shape_name}__{mesh_name}{suffix}.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
    return record


def np_prod(shape) -> int:
    n = 1
    for s in shape:
        n *= s
    return n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", help="architecture id (assignment name)")
    ap.add_argument("--shape", choices=list(SHAPES), help="input shape")
    ap.add_argument("--all", action="store_true", help="run every combination")
    ap.add_argument("--archs", help="comma list (with --all)")
    ap.add_argument("--shapes", help="comma list (with --all)")
    ap.add_argument("--multi-pod", action="store_true",
                    help="2x16x16 = 512-chip mesh (default: 16x16)")
    ap.add_argument("--out-dir", default=None)
    args = ap.parse_args(argv)

    archs = (args.archs.split(",") if args.archs else list(ALIASES))
    shapes = (args.shapes.split(",") if args.shapes else list(SHAPES))
    combos = ([(args.arch, args.shape)] if not args.all
              else [(a, s) for a in archs for s in shapes])

    failures = []
    for arch, shape in combos:
        try:
            run_one(arch, shape, multi_pod=args.multi_pod, out_dir=args.out_dir)
        except Exception as e:  # noqa: BLE001
            failures.append((arch, shape, repr(e)))
            print(f"[dryrun] FAIL {arch} {shape}: {e}", flush=True)
            traceback.print_exc()
    if failures:
        print(f"{len(failures)} failures:")
        for f in failures:
            print("  ", f)
        return 1
    print(f"all {len(combos)} dry-runs compiled OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
