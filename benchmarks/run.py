"""Benchmark harness — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV per the harness contract.

    PYTHONPATH=src python -m benchmarks.run [--only operators,...]
    REPRO_BENCH_TRIALS=64 ... for deeper searches.
"""

from __future__ import annotations

import argparse
import sys
import time

SECTIONS = ["operators", "end_to_end", "composition", "use_mxu", "tuning_time", "roofline"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="comma-separated section list")
    args = ap.parse_args()
    picked = args.only.split(",") if args.only else SECTIONS
    from repro.launch.runtime import enable_compile_cache

    enable_compile_cache()
    t0 = time.time()
    print("name,us_per_call,derived")
    if "operators" in picked:  # Figure 8
        from . import operators

        operators.run()
    if "end_to_end" in picked:  # Figure 9
        from . import end_to_end

        end_to_end.run()
    if "composition" in picked:  # Figure 10a
        from . import composition

        composition.run()
    if "use_mxu" in picked:  # Figure 10b
        from . import use_mxu

        use_mxu.run()
    if "tuning_time" in picked:  # Table 1
        from . import tuning_time

        tuning_time.run()
    if "roofline" in picked:  # assignment §Roofline (from dry-run artifacts)
        from . import roofline

        roofline.run()
    print(f"# total benchmark time: {time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
