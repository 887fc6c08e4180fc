"""Graph lint CLI (DESIGN.md §14).

    python -m repro.analysis.lint [factory ...] [--suite] [--mode error]
                                  [--dot DIR]

A *factory* is ``module:qualname`` or ``path/to/file.py:qualname`` — a
zero-argument callable returning a Graph, GraphBuilder, Session, or any
launch-step bundle (anything with ``.graph`` / ``.session`` / ``.builder``).
With no factories, ``--suite`` (implied) lints the shipped launch/example
graph factories.  Exit status: non-zero iff any error-severity diagnostic
survives suppression — the CI ``lint-graphs`` job gates on it.

Multi-device factories (graphs with >= 2 distinct device constraints)
are additionally placed + partitioned so the Send/Recv pairing and the
per-device schedule get verified, exactly like an Executable build.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import os
import sys
from typing import Callable, List, Optional, Tuple

from .diagnostics import CODES, VerifyReport, make
from .verifier import verify_graph
from ..core.graph import Graph, GraphError


# ---------------------------------------------------------------------------
def _load_factory(spec: str) -> Callable:
    path, _, qual = spec.partition(":")
    if not qual:
        raise SystemExit(f"factory spec {spec!r} is not module:qualname")
    if path.endswith(".py") or os.sep in path:
        modname = "_lint_" + os.path.basename(path).replace(".py", "")
        sl = importlib.util.spec_from_file_location(modname, path)
        if sl is None or sl.loader is None:
            raise SystemExit(f"cannot load {path!r}")
        mod = importlib.util.module_from_spec(sl)
        sys.modules[modname] = mod
        sl.loader.exec_module(mod)
    else:
        mod = importlib.import_module(path)
    obj = mod
    for part in qual.split("."):
        obj = getattr(obj, part)
    return obj


def _as_graph(obj) -> Graph:
    if isinstance(obj, Graph):
        return obj
    if isinstance(obj, (tuple, list)) and obj:
        return _as_graph(obj[0])
    for attr in ("graph", "session", "builder"):
        inner = getattr(obj, attr, None)
        if inner is not None:
            return inner if isinstance(inner, Graph) else _as_graph(inner)
    raise SystemExit(f"factory returned {type(obj).__name__}; expected a "
                     f"Graph/GraphBuilder/Session/step bundle")


def _sink_fetches(g: Graph) -> List[str]:
    cons = g.consumers()
    return [f"{n}:0" for n, node in g.nodes.items() if not cons[n]]


def lint_graph(g: Graph, where: str) -> VerifyReport:
    """Verify one graph; multi-device graphs also place + partition."""
    devices = sorted({n.device for n in g.nodes.values() if n.device})
    fetches = _sink_fetches(g)
    feed_keys = [f"{n}:0" for n, node in g.nodes.items()
                 if node.op == "Placeholder"]
    if len(devices) < 2:
        return verify_graph(g, fetches=fetches, feed_keys=feed_keys,
                            where=where)
    from ..core import partition as partition_mod
    from ..core import placement as placement_mod
    from ..runtime.devices import Device, DeviceName, DeviceSet

    devset = DeviceSet([Device(DeviceName.parse(d)) for d in devices])
    names = set(g.nodes)
    placement = placement_mod.place(g, devset, placement_mod.CostModel(),
                                    names)
    report = verify_graph(g, names, fetches=fetches, feed_keys=feed_keys,
                          placement=placement, where=where)
    try:
        parted = partition_mod.partition(g, placement, names)
    except GraphError as e:
        report.diagnostics.append(make(
            "F303", f"partition rejected the placed graph: {e}",
            fix="see the partition error above"))
        return report
    p_report = verify_graph(parted.graph, None, fetches=fetches,
                            feed_keys=feed_keys,
                            placement=parted.placement,
                            where=f"{where} (partitioned)")
    report.diagnostics.extend(p_report.diagnostics)
    report.suppressed += p_report.suppressed
    return report


# --- the shipped launch/example factories (--suite) ------------------------
def factory_wire_train():
    from ..launch.steps import build_wire_train_step
    return build_wire_train_step([
        "/job:worker/task:0/device:cpu:0",
        "/job:worker/task:1/device:cpu:0",
    ])


def factory_train():
    """The graph ``build_train_step`` lowers, at llama3_8b smoke size."""
    from ..configs import get_config
    from ..launch import steps
    from ..models.api import Model, Shape
    cfg = get_config("llama3_8b", smoke=True)
    shape = Shape("lint", 64, 2, "train")
    model = Model.for_config(cfg)
    loss_kw = steps.loss_kwargs(model, steps.step_hparams(cfg, shape, 1))
    return steps._train_graph(list(model.batch_desc(shape)), cfg, 1, loss_kw,
                              lr=3e-4, n_micro=1)


def factory_serve():
    """The graph ``build_serve_step`` lowers, at llama3_8b smoke size."""
    from ..configs import get_config
    from ..launch import steps
    return steps._serve_graph(get_config("llama3_8b", smoke=True), 1, {})


def _example(fname: str, qual: str) -> Optional[str]:
    here = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    path = os.path.join(here, "examples", fname)
    return f"{path}:{qual}" if os.path.exists(path) else None


def suite_specs() -> List[Tuple[str, str]]:
    specs = [
        ("launch:wire_train_2task", "repro.analysis.lint:factory_wire_train"),
        ("launch:train_smoke", "repro.analysis.lint:factory_train"),
        ("launch:serve_smoke", "repro.analysis.lint:factory_serve"),
    ]
    qs = _example("quickstart.py", "build_graph")
    if qs:
        specs.append(("examples:quickstart", qs))
    return specs


# ---------------------------------------------------------------------------
def _summary_table(rows: List[Tuple[str, str, str, str, str]]) -> str:
    head = ("| graph | code | severity | pass | nodes |\n"
            "|---|---|---|---|---|\n")
    if not rows:
        return head + "| _all clean_ | — | — | — | — |\n"
    return head + "".join(
        f"| {g} | {c} | {s} | {p} | {n} |\n" for g, c, s, p, n in rows)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.lint",
        description="§14 static graph verifier over graph factories")
    ap.add_argument("factories", nargs="*",
                    help="module:qualname or path.py:qualname")
    ap.add_argument("--suite", action="store_true",
                    help="lint the shipped launch/example factories "
                         "(default when no factories given)")
    ap.add_argument("--mode", choices=("warn", "error"), default="error",
                    help="exit non-zero on errors (default) or never (warn)")
    ap.add_argument("--dot", metavar="DIR", default=None,
                    help="write a per-graph diagnostic-annotated .dot here")
    args = ap.parse_args(argv)

    targets: List[Tuple[str, str]] = [(s, s) for s in args.factories]
    if args.suite or not targets:
        targets = suite_specs() + targets

    rows: List[Tuple[str, str, str, str, str]] = []
    n_errors = 0
    for label, spec in targets:
        try:
            g = _as_graph(_load_factory(spec)())
        except SystemExit:
            raise
        except Exception as e:
            print(f"[lint] {label}: factory failed: {type(e).__name__}: {e}")
            n_errors += 1
            rows.append((label, "X000", "error", "factory",
                         f"factory raised {type(e).__name__}"))
            continue
        report = lint_graph(g, label)
        errs, warns = report.errors(), report.warnings()
        n_errors += len(errs)
        status = ("clean" if not report.diagnostics else
                  f"{len(errs)} error(s), {len(warns)} warning(s)")
        print(f"[lint] {label}: {len(g.nodes)} nodes, {status}"
              + (f", {report.suppressed} suppressed"
                 if report.suppressed else ""))
        for d in report.diagnostics:
            print("    " + d.format())
            rows.append((label, d.code, d.severity, d.pass_name,
                         ", ".join(d.nodes[:4])))
        if args.dot:
            from ..tools import graphviz as gv
            os.makedirs(args.dot, exist_ok=True)
            safe = label.replace(":", "_").replace("/", "_")
            out = os.path.join(args.dot, f"{safe}.dot")
            with open(out, "w") as fh:
                fh.write(gv.to_dot_diagnostics(g, report.diagnostics,
                                               title=label))
            print(f"    wrote {out}")

    print()
    print(_summary_table(rows), end="")
    if args.mode == "error" and n_errors:
        print(f"\n[lint] FAILED: {n_errors} error(s) "
              f"(codes: see DESIGN.md §14 / repro.analysis.CODES)")
        return 1
    print("\n[lint] OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
