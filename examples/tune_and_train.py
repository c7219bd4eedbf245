"""End-to-end driver (Appendix A.6): extract the hot matmul shapes from a
model, tune them with MetaSchedule, store traces in the database, then
train the model for a few hundred steps with fault-tolerant checkpointing.

    PYTHONPATH=src python examples/tune_and_train.py [--steps 200]
"""
import argparse
import tempfile

import repro
from repro.configs.base import get_config
from repro.launch import train as train_launcher


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--rounds", type=int, default=6)
    args = ap.parse_args()

    cfg = get_config("smollm-135m", smoke=True)
    db = repro.Database("results/tune_and_train_db.json")

    print("== phase 1: tune the model's tensor programs (task scheduler) ==")
    # tasks extracted automatically from the model's forward jaxpr —
    # shapes, occurrence weights and dedup all come from the program
    sched = repro.TaskScheduler(
        repro.extract_tasks(cfg, batch=1, seq=128, dispatchable_only=True),
        database=db,
        config=repro.TuneConfig(
            search=repro.SearchConfig(max_trials=24, init_random=6,
                                      population=8, measure_per_round=6),
            verbose=True,
        ),
    )
    best = sched.tune(total_rounds=args.rounds)
    for k, v in best.items():
        print(f"  {k}: {v*1e6:.1f} us")

    print("\n== phase 2: train with tuned kernels in the database ==")
    with tempfile.TemporaryDirectory() as ckpt_dir:
        losses = train_launcher.main([
            "--arch", "smollm-135m", "--smoke",
            "--steps", str(args.steps), "--batch", "8", "--seq", "128",
            "--ckpt-dir", ckpt_dir, "--ckpt-every", "50",
        ])
    assert losses[-1] < losses[0], "loss should decrease"
    print("training improved loss; tuned records live in", db.path)


if __name__ == "__main__":
    main()
