"""Helpers of the benchmark harness's CPU tests: the harness's own
directory on the path, and a small configuration in the program's form."""

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CHIP = ROOT / "benchmarks" / "chip"
for p in (str(ROOT / "src"), str(CHIP)):
    if p not in sys.path:
        sys.path.insert(0, p)

SMALL = dict(
    num_hidden_layers=2, hidden_size=48, num_attention_heads=3,
    num_key_value_heads=1, intermediate_size=96, vocab_size=256,
)
SMALL_PROGRAM = dict(
    n_layers=2, d_model=48, n_heads=3, n_kv_heads=1, d_ff=96, vocab=256,
    head_dim=16,
)


def small_conf(name: str = "stablelm-3b") -> dict:
    """A configuration file of the benchmark, shrunk for the CPU."""
    conf = json.loads((CHIP / "configs" / f"{name}.json").read_text())
    conf.update(SMALL)
    conf["overrides"] = dict(conf.get("overrides", {}), **SMALL_PROGRAM)
    return conf


def peak() -> dict:
    import harness

    return harness.peaks()["TPU v5 lite"]
