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


# Widths, which no configuration cuts, besides every key that ends in _dim
# or _rank; and the counts that may be cut to this chip's share of a
# layer, down to a floor: an eighth of the vocabulary, 8 experts.
WIDTHS = {
    "hidden_size", "intermediate_size", "moe_intermediate_size",
    "shared_expert_intermediate_size", "moe_shared_expert_intermediate_size",
    "dense_intermediate_size", "ffn_hidden_size", "moe_ffn_hidden_size",
    "head_dim", "kv_channels", "sliding_window", "sliding_window_size",
    "window_size", "ssm_state_size", "mamba_d_state", "expand",
    "mamba_expand", "num_experts_per_tok", "num_experts_per_token",
    "moe_top_k", "moe_topk",
}
EXPERT_COUNTS = {"num_experts", "num_local_experts", "n_routed_experts",
                 "moe_num_experts"}


def refused_cuts(data):
    """Why the keys a configuration file lists under ``reduced`` (each with
    its published value) may not be cut as the file runs them."""
    out = []
    for key, published in data["reduced"].items():
        run = data.get(key)
        if key in WIDTHS or key.endswith(("_dim", "_rank")):
            out.append(f"{key} is a width")
        elif key == "vocab_size" and 8 * run < published:
            out.append(f"vocab_size {run} is under an eighth of {published}")
        elif key in EXPERT_COUNTS and run < 8:
            out.append(f"{key} {run} is under 8 experts")
    return out


@pytest.mark.parametrize("reduced, run, why", [
    ({"hidden_size": 4096}, {"hidden_size": 2048}, "hidden_size is a width"),
    ({"q_lora_rank": 1536}, {"q_lora_rank": 768}, "q_lora_rank is a width"),
    ({"qk_rope_head_dim": 64}, {"qk_rope_head_dim": 32},
     "qk_rope_head_dim is a width"),
    ({"vocab_size": 200192}, {"vocab_size": 25023},
     "vocab_size 25023 is under an eighth of 200192"),
    ({"num_experts": 128}, {"num_experts": 4}, "num_experts 4 is under 8 experts"),
    ({"n_routed_experts": 256}, {"n_routed_experts": 7},
     "n_routed_experts 7 is under 8 experts"),
], ids=["width", "lora-rank", "head-dim", "vocab", "experts", "routed-experts"])
def test_reduced_guard_refuses(reduced, run, why):
    assert refused_cuts(dict(run, reduced=reduced)) == [why]


def test_reduced_guard_admits_the_chips_share():
    data = {"vocab_size": 25024, "num_experts": 8, "num_hidden_layers": 6,
            "reduced": {"vocab_size": 200192, "num_experts": 128,
                        "num_hidden_layers": 32}}
    assert refused_cuts(data) == []


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files_found(conf):
    import harness

    data = harness.load_json(ROOT / conf["file"])
    assert data["name"] == conf["name"] and data["source"] == conf["source"]
    assert sorted(conf["reduced"]) == sorted(data["reduced"])
    assert any(c["config"] == conf["name"] for c in BENCH["workloads"])
    assert refused_cuts(data) == []
    harness.program_config(data)  # every field agrees with the program


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
