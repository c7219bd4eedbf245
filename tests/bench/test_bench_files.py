"""Every file BENCHMARK.json names is found by name, and the file keeps
the contract's shape."""

import json
import re

import pytest
from benchlib import CHIP, ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir()
    assert (ROOT / BENCH["command"][1]).is_file()


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_files_found(cell):
    import harness

    files = harness.cell_files(BENCH, cell["name"])
    assert (CHIP / "jobs" / f"{files['workload']['job']}.py").is_file()
    assert files["traffic"]["kind"] in ("packed", "open_loop")
    assert cell["chips"] in (1, 4)
    assert NAME.match(cell["name"]) and len(cell["why"]) <= 200


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files_found(conf):
    import harness

    data = harness.load_json(ROOT / conf["file"])
    assert data["name"] == conf["name"] and data["source"] == conf["source"]
    assert sorted(conf["reduced"]) == sorted(data["reduced"])
    assert any(c["config"] == conf["name"] for c in BENCH["workloads"])
    for key in conf["reduced"]:
        assert not key.endswith(("_dim", "_rank", "_size"))
    harness.program_config(data)  # every shape key agrees with the program


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_reader_found(metric):
    import harness

    reader = harness.load_module(CHIP / "metrics" / f"{metric['name']}.py")
    assert callable(reader.read)
    assert reader.read({}) is None or metric["name"] == "setup_s"
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    for cell in metric.get("workloads", []):
        harness.find(BENCH["workloads"], cell, "workload")


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_moves_a_reported_metric(metric):
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    moved = e2e[metric["moves"]]
    cells = metric.get("workloads", [c["name"] for c in BENCH["workloads"]])
    for cell in cells:
        assert "workloads" not in moved or cell in moved["workloads"]


def test_every_cell_reports_enough():
    for cell in BENCH["workloads"]:
        name = cell["name"]
        e2e = [m for m in BENCH["end_to_end"]
               if "workloads" not in m or name in m["workloads"]]
        layer = [m for m in BENCH["per_layer"]
                 if "workloads" not in m or name in m["workloads"]]
        assert any(m["name"] == "setup_s" for m in e2e) and len(e2e) >= 2
        assert layer
