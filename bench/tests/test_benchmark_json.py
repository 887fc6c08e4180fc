"""BENCHMARK.json keeps to the contract, and every name it holds finds its
files: configuration, traffic mix, limits, and a reader per per-layer
metric."""
import json
import os

import pytest

from bench import spec

B = spec.benchmark()
ROOT = spec.CHECKOUT
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
METRIC_KEYS = {"name", "unit", "better", "source"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_size():
    assert set(B) == TOP_KEYS
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(B["paths"]) <= 16 and len(B["command"]) <= 32
    for p in B["paths"]:
        assert not p.startswith("/") and ".." not in p.split("/")
        assert os.path.isdir(os.path.join(ROOT, p))
    for word in B["command"]:
        assert not word.startswith("/") and ".." not in word.split("/")


@pytest.mark.parametrize("name", spec.metric_names(B)
                         + [c["name"] for c in B["configs"]]
                         + [w["name"] for w in B["workloads"]]
                         + [w["config"] for w in B["workloads"]]
                         + [w["traffic"] for w in B["workloads"]]
                         + [k for c in B["configs"] for k in c["reduced"]])
def test_names_use_allowed_characters(name):
    assert spec.NAME_RE.match(name), name


def test_names_unique():
    for group in (spec.metric_names(B), [c["name"] for c in B["configs"]],
                  [w["name"] for w in B["workloads"]]):
        assert len(group) == len(set(group))
    pairs = [(w["config"], w["traffic"]) for w in B["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("m", B["end_to_end"] + B["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entries(m):
    assert spec.UNIT_RE.match(m["unit"]), m["unit"]
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    cells = {w["name"] for w in B["workloads"]}
    assert set(m.get("workloads", cells)) <= cells
    if m in B["end_to_end"]:
        assert set(m) - {"workloads"} == METRIC_KEYS | {"bound"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        assert set(m) - {"workloads"} == METRIC_KEYS | {"layer", "moves"}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        moves = {e["name"]: e for e in B["end_to_end"]}[m["moves"]]
        # each cell the metric lists reports the metric it moves
        assert set(m["workloads"]) <= set(moves.get("workloads", cells))
        assert os.path.isfile(os.path.join(spec.BENCH, "metrics",
                                           m["name"] + ".py"))


def test_setup_metric_and_bound():
    setup = {m["name"]: m for m in B["end_to_end"]}["setup_s"]
    assert "workloads" not in setup and setup["bound"] <= 0.25


@pytest.mark.parametrize("w", B["workloads"], ids=lambda w: w["name"])
def test_cell_files_and_metrics(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    cell = spec.Cell(w["name"])
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and cell.per_layer
    assert cell.traffic["driver"] in ("train", "open_loop")
    assert cell.limits and all(v > 0 for v in cell.limits.values())


@pytest.mark.parametrize("c", B["configs"], ids=lambda c: c["name"])
def test_config_files(c):
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert c["file"].startswith(B["paths"][0] + "/")
    with open(os.path.join(ROOT, c["file"])) as f:
        body = json.load(f)
    assert body["name"] == c["name"] and body["source"] == c["source"]
    assert body["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
    assert c["source"].startswith("https://")
    assert any(w["config"] == c["name"] for w in B["workloads"])


def test_four_chip_cells_at_most_half():
    four = sum(w["chips"] == 4 for w in B["workloads"])
    assert four <= max(1, len(B["workloads"]) // 2)


def test_run_seconds_fits_a_full_check():
    rs = B["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_files_under_paths_are_named_from_name_characters():
    for base, _, files in os.walk(spec.BENCH):
        if "__pycache__" in base:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), ROOT)
            assert all(spec.NAME_RE.match(part) for part in rel.split("/")), rel
