"""Find a cell's files by the names in ``BENCHMARK.json``."""
from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any, Callable, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    return load_json(os.path.join(CHECKOUT, "BENCHMARK.json"))


class Cell:
    """One workload entry with its configuration, traffic, limits and the
    metrics it reports."""

    def __init__(self, name: str, bench: Optional[Dict[str, Any]] = None,
                 config: Optional[Dict[str, Any]] = None,
                 traffic: Optional[Dict[str, Any]] = None,
                 limits: Optional[Dict[str, float]] = None):
        """``config``, ``traffic`` and ``limits`` stand in for the cell's
        files (the tests run cells at a size a CPU can hold)."""
        bench = bench or benchmark()
        entries = {w["name"]: w for w in bench["workloads"]}
        if name not in entries:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"known: {sorted(entries)}")
        self.name = name
        self.entry = entries[name]
        configs = {c["name"]: c for c in bench["configs"]}
        self.config = config or load_json(os.path.join(
            CHECKOUT, configs[self.entry["config"]]["file"]))
        self.traffic = traffic or load_json(os.path.join(
            BENCH, "traffic", self.entry["traffic"] + ".json"))
        self.limits = limits or load_json(os.path.join(BENCH, "limits",
                                                       name + ".json"))
        self.chips = int(self.entry["chips"])
        self.end_to_end = [m for m in bench["end_to_end"] if _in_cell(m, name)]
        self.per_layer = [m for m in bench["per_layer"] if _in_cell(m, name)]


def _in_cell(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def metric_reader(name: str) -> Callable[[Dict[str, Any]], Optional[float]]:
    """``read(run) -> float | None`` from ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metric_names(bench: Dict[str, Any]) -> List[str]:
    return [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
