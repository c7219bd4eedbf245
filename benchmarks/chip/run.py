#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; its files are found by name:
``workloads/<cell>.json`` (job, tuning budget, serving settings, limits),
the configuration file the entry names, ``traffic/<mix>.json``, the
window driver ``jobs/<job>.py`` and one reader ``metrics/<metric>.py``
per metric.  With ``--trace 0`` the line carries the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics, read from a short
profiled window after the measured one.

The run fails, and prints no result, when JAX finds no TPU, a device
kind ``peaks.json`` lacks, or fewer chips than the cell asks for.  Each
number compared with the reference is printed beside its limit, as the
last lines on standard error and as the last key of the result line.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict

T_START = time.perf_counter()
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402

harness.setup_paths()


@dataclass
class Run:
    """What a job reads (the cell's files, seed, length) and fills in
    (observations for the metric readers, the numbers compared)."""

    name: str
    workload: Dict
    conf: Dict
    traffic: Dict
    seed: int
    seconds: float
    trace: bool
    peak: Dict
    t_start: float = T_START
    backend: str = "pallas"
    spans: Any = field(default_factory=harness.Spans)
    obs: Dict[str, Any] = field(default_factory=dict)
    checks: Dict[str, Dict[str, float]] = field(default_factory=dict)
    attempted: int = 0
    failed: int = 0

    def check(self, name: str, value: float, limit: float) -> None:
        self.checks[name] = {"value": float(value), "limit": float(limit)}


def metric_names(bench: Dict, cell: str, trace: bool):
    entries = bench["per_layer"] if trace else bench["end_to_end"]
    return [
        m for m in entries if "workloads" not in m or cell in m["workloads"]
    ]


def read_metrics(bench: Dict, run: Run) -> Dict[str, Dict]:
    out = {}
    for m in metric_names(bench, run.name, run.trace):
        reader = harness.load_module(HERE / "metrics" / f"{m['name']}.py")
        value = reader.read(run.obs)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def correct(run: Run) -> bool:
    return bool(run.checks) and all(
        c["value"] <= c["limit"] for c in run.checks.values()
    )


def result_line(run: Run, info: Dict, metrics: Dict) -> Dict:
    device = dict(info, memory_peak_bytes=run.obs.get("memory_peak_bytes"))
    line: Dict[str, Any] = {
        "correct": correct(run),
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": metrics,
        "device": device,
    }
    if run.trace and "trace" in run.obs:
        tr = run.obs["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        line["breakdown"] = {
            "device_ops": tr["ops_top"], "idle_gaps": tr["gaps_top"],
        }
    line["checks"] = run.checks
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = harness.benchmark()
    files = harness.cell_files(bench, args.workload)
    try:
        info, peak = harness.device_check(int(files["spec"]["chips"]))
    except harness.NotRunnable as e:
        print(f"not runnable: {e}", file=sys.stderr)
        return 2
    harness.compile_cache()
    run = Run(
        name=args.workload, workload=files["workload"],
        conf=files["conf"], traffic=files["traffic"], seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), peak=peak,
    )
    run.obs["job"] = run.workload["job"]
    run.obs["peak"] = peak
    job = harness.load_module(HERE / "jobs" / f"{run.workload['job']}.py")
    job.run(run)
    metrics = read_metrics(bench, run)
    line = result_line(run, info, metrics)
    spans = {k: round(v, 3) for k, v in run.spans.total.items()}
    print(f"host spans (s): {json.dumps(spans)}", file=sys.stderr)
    for name, c in run.checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
