"""Fold structured trace JSONL into a tuning diagnostics report.

Reads one or more trace files produced by running with ``REPRO_TRACE``
set (see :mod:`repro.obs.trace`), folds them with
:func:`repro.obs.report.fold`, prints the human-readable rendering (the
tuning wall-clock split into build, compile, timing and search
overhead, cost-model correlation, dispatch coverage, ...), and writes
the machine-readable ``BENCH_tuning_report.json`` consumed by the CI gate
(``check_regression.py --report ... --min-dispatch-hit-rate``).

Usage::

    REPRO_TRACE=results/trace.jsonl python benchmarks/end_to_end.py
    python benchmarks/report.py results/trace.jsonl \
        [--json-out BENCH_tuning_report.json] [--top 10]
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.obs.report import fold, load_events, render_text  # noqa: E402

REPO_ROOT = Path(__file__).resolve().parents[1]
DEFAULT_JSON = REPO_ROOT / "BENCH_tuning_report.json"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "traces", nargs="+", help="trace JSONL file(s) to fold",
    )
    ap.add_argument(
        "--json-out", default=str(DEFAULT_JSON),
        help="machine-readable report path (default BENCH_tuning_report.json)",
    )
    ap.add_argument(
        "--top", type=int, default=10,
        help="how many slowest candidates to list",
    )
    args = ap.parse_args(argv)
    missing = [p for p in args.traces if not Path(p).exists()]
    if missing:
        print(f"FAIL: missing trace file(s): {', '.join(missing)}")
        return 1
    events = load_events(args.traces)
    if not events:
        print(f"FAIL: no events in {', '.join(args.traces)} — "
              "was the producer run with REPRO_TRACE set?")
        return 1
    report = fold(events, top_n=args.top)
    # write the artifact before printing: the report must survive a
    # consumer closing stdout early (e.g. piping through head)
    Path(args.json_out).write_text(json.dumps(report, indent=2) + "\n")
    print(render_text(report))
    print(f"wrote {args.json_out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
