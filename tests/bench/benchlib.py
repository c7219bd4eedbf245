"""Helpers of the benchmark harness's CPU tests: the harness's own
directory on the path, and a configuration shrunk by its reference module."""

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CHIP = ROOT / "benchmarks" / "chip"
for p in (str(ROOT / "src"), str(CHIP)):
    if p not in sys.path:
        sys.path.insert(0, p)


def small_conf(name: str = "stablelm-3b") -> dict:
    """A configuration file of the benchmark, shrunk for the CPU by the
    reference module it names."""
    import harness

    conf = json.loads((CHIP / "configs" / f"{name}.json").read_text())
    return harness.reference(conf).small(conf)


def peak() -> dict:
    import harness

    return harness.peaks()["TPU v5 lite"]
