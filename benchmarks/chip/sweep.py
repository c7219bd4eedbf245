#!/usr/bin/env python3
"""Find a serving cell's knee: one set-up, then a window at each offered
rate, in one process.

    python3 benchmarks/chip/sweep.py --workload <cell> --seed 1 \
        --rates 0.5,1,2,4,8 --seconds 30

For each rate, in the order given, the scheduler is drained, the mix is
offered for ``--seconds`` and the waiting requests (queued or still
prefilling) are counted at each quarter of the window.  The queue grows
when the last count exceeds the half-way count by more than
``GROWTH``; the sweep stops at the first such rate.  The knee is the
highest rate below it, and the last line proposes four fifths of it as
the cell's fixed rate.  One JSON line per rate; not part of a benchmark
run.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
GROWTH = 2  # requests: a waiting count that rises by more has grown
sys.path.insert(0, str(HERE))

import harness  # noqa: E402

harness.setup_paths()
import run as bench_run  # noqa: E402
import traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--drain", type=float, default=90.0)
    args = ap.parse_args(argv)
    files = harness.cell_files(harness.benchmark(), args.workload)
    try:
        _, peak = harness.device_check(int(files["spec"]["chips"]))
    except harness.NotRunnable as e:
        print(f"not runnable: {e}", file=sys.stderr)
        return 2
    harness.compile_cache()
    r = bench_run.Run(
        name=args.workload, workload=files["workload"],
        conf=files["conf"], traffic=files["traffic"], seed=args.seed,
        seconds=args.seconds, trace=False, peak=peak,
    )
    job = harness.load_module(HERE / "jobs" / "serve.py")
    st = job.setup(r)
    sched = st.sched
    knee = None
    for rate in [float(x) for x in args.rates.split(",")]:
        arr = traffic.open_loop(r.traffic, rate, args.seconds, st.z["V"],
                                args.seed)
        waiting = []
        for q in range(1, 5):
            # each quarter is its own window over that quarter's arrivals
            lo, hi = (q - 1) * args.seconds / 4, q * args.seconds / 4
            part = [a for a in arr if lo <= a.due_s < hi]
            for a in part:
                a.due_s -= lo
            w = job.window(r, st, part, args.seconds / 4, account=False)
            waiting.append(len(sched.queue) + len(sched.prefilling))
            lat = job.latencies(w)
        ttft = [x["ttft_s"] for x in lat]
        tpot = [x["tpot_s"] for x in lat if x["tpot_s"] is not None]
        line = {
            "rate": rate, "offered": len(arr), "waiting_by_quarter": waiting,
            "active_at_end": len(sched.active),
            "last_quarter_ttft_p95_ms": 1e3 * harness.p95(ttft) if ttft else None,
            "last_quarter_tpot_p95_ms": 1e3 * harness.p95(tpot) if tpot else None,
            "tick_ms": 1e3 * (w["t_end"] - w["t0"]) / max(1, w["ticks"]),
        }
        line["grew"] = waiting[3] > waiting[1] + GROWTH
        print(json.dumps(line), flush=True)
        if line["grew"]:
            break
        knee = rate
        t_end = time.perf_counter() + args.drain
        while sched.pending() and time.perf_counter() < t_end:
            sched.step()
        if sched.pending():
            print(json.dumps({"rate": rate, "drain": "not drained"}), flush=True)
            break
    print(json.dumps({"knee": knee,
                      "rate": None if knee is None else round(0.8 * knee, 3)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
