#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's number compared on
many seeds and the control's on a few, from one set-up in one process.

    python3 benchmarks/chip/readings.py --workload <cell> \
        --seeds 101,102,...,112 --control 3 --seconds 30

For every seed the weights and traffic are made anew from it and driven
through the cell's own compiled path at the cell's size: a few forwards
(prefill) or a window at the cell's rate, drained to its end (serve).
The control is the reference computed with int8 linear layers, one step
below the configuration's bf16, put in the program's place; it runs on
the first ``--control`` seeds.  One JSON line per seed; not part of a
benchmark run.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402

harness.setup_paths()
import run as bench_run  # noqa: E402

CONTROL = "int8"


def prefill_readings(r, job, seeds, n_control, _seconds):
    st = job.setup(r)
    k = int(r.workload["check"]["sample_steps"])
    for i, seed in enumerate(seeds):
        job.reseed(r, st, seed)
        _, kept = job.steps(r, st, 0, count=k, keep=k, seed=seed)
        yield seed, job.compare(r, st, kept, CONTROL if i < n_control else None)


def serve_readings(r, job, seeds, n_control, seconds):
    import time as _t

    import traffic

    st = job.setup(r)
    wl = r.workload
    for i, seed in enumerate(seeds):
        job.reset(st, harness.make_params(r.conf, seed, st.sched.model), seed)
        arr = traffic.open_loop(r.traffic, wl["rate"], seconds, st.z["V"], seed)
        w = job.window(r, st, arr, seconds, account=False)
        t_end = _t.perf_counter() + float(wl["check"]["drain_s"])
        while st.sched.pending() and _t.perf_counter() < t_end:
            st.sched.step()
        got = job.served_gaps(r, st, w["recs"], seed,
                              CONTROL if i < n_control else None)
        got["unfinished"] = sum(1 for rec in w["recs"] if not rec["req"].done)
        yield seed, got


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    bench = harness.benchmark()
    files = harness.cell_files(bench, args.workload)
    try:
        _, peak = harness.device_check(int(files["spec"]["chips"]))
    except harness.NotRunnable as e:
        print(f"not runnable: {e}", file=sys.stderr)
        return 2
    harness.compile_cache()
    r = bench_run.Run(
        name=args.workload, workload=files["workload"],
        conf=files["conf"], traffic=files["traffic"], seed=seeds[0],
        seconds=args.seconds, trace=False, peak=peak,
    )
    kind = r.workload["job"]
    job = harness.load_module(HERE / "jobs" / f"{kind}.py")
    fn = {"prefill": prefill_readings, "serve": serve_readings}[kind]
    t0 = time.perf_counter()
    for seed, got in fn(r, job, seeds, args.control, args.seconds):
        got = dict(got, seed=seed, at_s=round(time.perf_counter() - t0, 1))
        print(json.dumps(got), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
