"""BENCHMARK.json keeps to its contract, and every name in it is found
by the harness: configuration, cell and metric files."""

import json
import os
import re

import pytest

from bench import run as R

with open(os.path.join(R.ROOT, "BENCHMARK.json")) as f:
    BM = json.load(f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = {w["name"]: w for w in BM["workloads"]}


def test_top_level_keys():
    assert list(BM) == ["command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"]
    assert 1 <= BM["run_seconds"] <= 51
    assert len(json.dumps(BM)) < 64 * 1024


def test_entry_keys():
    for c in BM["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["why"]) <= 200 and "\n" not in c["why"]
    for w in BM["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in BM["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
    for m in BM["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]


def test_names_units_and_lines():
    metrics = BM["end_to_end"] + BM["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for c in BM["configs"] + BM["workloads"]:
        assert NAME.match(c["name"])
    for w in BM["workloads"]:
        assert NAME.match(w["traffic"]) and 1 <= len(w["why"]) <= 200
        assert w["chips"] in (1, 4) and "\n" not in w["why"]


def test_end_to_end():
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    assert set(e2e) == {"tokens_per_s", "itl_p95_ms", "setup_s"}
    for m in e2e.values():
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "workloads" not in e2e["setup_s"]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_files_and_metrics(cell):
    w = CELLS[cell]
    spec = R.load_json("workloads", cell + ".json")
    assert spec["config"] == w["config"] and spec["chips"] == w["chips"]
    assert spec["why"] == w["why"]
    cfg = next(c for c in BM["configs"] if c["name"] == w["config"])
    with open(os.path.join(R.ROOT, cfg["file"])) as f:
        config = json.load(f)
    assert config["reduced"] == cfg["reduced"]
    assert os.path.exists(os.path.join(R.BENCH, "reference",
                                       config["reference"] + ".py"))
    assert os.path.exists(os.path.join(
        R.BENCH, "traffic", spec["traffic"]["generator"] + ".py"))
    e2e = R.select_metrics(cell, False)
    layer = R.select_metrics(cell, True)
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert layer
    for m in e2e + layer:
        assert os.path.exists(os.path.join(R.BENCH, "metrics",
                                           m["name"] + ".py"))
    moved = {m["name"] for m in e2e}
    for m in layer:
        assert m["moves"] in moved
