#!/usr/bin/env python3
"""Run a cell several times and report each metric's spread.

    python3 benchmarks/chip/spread.py --workload <cell> --seeds 1,2,3,4,5,6 \
        --sets 2 --seconds 51 [--trace 1] [--out runs.jsonl]

Each run is its own process (``run.py``), one after another; this parent
never touches JAX, so each child has the chip.  Every set runs the same
seeds.  For each metric it prints the median of each set and the spread:
the distance between the first and third quartiles
(``statistics.quantiles(values, n=4)``) as a share of the median.  Result
lines go to ``--out`` as they come.
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent


def spread(values):
    if len(values) < 2:
        return None
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2 if q2 else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    out = open(args.out, "a") if args.out else None
    sets = []
    for k in range(args.sets):
        lines = []
        for seed in seeds:
            t0 = time.perf_counter()
            proc = subprocess.run(
                [sys.executable, str(HERE / "run.py"), "--workload",
                 args.workload, "--seed", str(seed), "--seconds",
                 str(args.seconds), "--trace", str(args.trace)],
                capture_output=True, text=True,
            )
            wall = time.perf_counter() - t0
            tail = proc.stdout.strip().splitlines()[-1:] or ["null"]
            try:
                line = json.loads(tail[0])
            except json.JSONDecodeError:
                line = None
            rec = {"set": k, "seed": seed, "rc": proc.returncode,
                   "wall_s": wall, "line": line,
                   "stderr_tail": proc.stderr[-1500:]}
            if out:
                out.write(json.dumps(rec) + "\n")
                out.flush()
            brief = {m: v["value"] for m, v in (line or {}).get("metrics", {}).items()}
            print(json.dumps({"set": k, "seed": seed, "rc": proc.returncode,
                              "wall_s": round(wall, 1),
                              "correct": (line or {}).get("correct"),
                              "checks": (line or {}).get("checks"),
                              "metrics": brief}), flush=True)
            if proc.returncode != 0:
                print(proc.stderr[-3000:], file=sys.stderr, flush=True)
            lines.append(line or {})
        sets.append(lines)
    names = sorted({m for s in sets for ln in s for m in ln.get("metrics", {})})
    for m in names:
        per = [[ln["metrics"][m]["value"] for ln in s if m in ln.get("metrics", {})]
               for s in sets]
        print(json.dumps({
            "metric": m,
            "medians": [statistics.median(v) if v else None for v in per],
            "spreads": [spread(v) for v in per],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
