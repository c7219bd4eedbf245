"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --smoke \
        --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Full-size configs target the production mesh (see dryrun.py for the
compile-only proof); on this CPU host use --smoke reduced configs.
The driver is fault-tolerant: checkpoint every N steps, resume from
LATEST, straggler detection on step times.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from ..configs.base import ARCHS, get_config
from ..data.pipeline import SyntheticTokenPipeline
from ..models.registry import build_model
from ..training import checkpoint as ckpt
from ..training.fault_tolerance import StragglerDetector, retry
from ..training.optimizer import OptConfig, adamw_init
from ..training.train_loop import make_train_step
from .runtime import enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="smollm-135m")
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--compress-bits", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    model = build_model(cfg)
    opt_cfg = OptConfig(
        lr=args.lr, total_steps=args.steps, warmup_steps=max(args.steps // 10, 1),
        compress_bits=args.compress_bits,
    )
    step_fn = jax.jit(
        make_train_step(model, opt_cfg, num_microbatches=args.microbatches),
        donate_argnums=(0, 1),
    )
    pipe = SyntheticTokenPipeline(cfg, args.seq, args.batch)

    start = 0
    if args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
        start, state, _ = ckpt.restore(args.ckpt_dir)
        params, opt_state = state["params"], state["opt"]
        print(f"resumed from step {start}")
    else:
        params = model.init(jax.random.PRNGKey(0))
        opt_state = adamw_init(params)

    detector = StragglerDetector()
    losses = []
    for step, batch in enumerate(pipe.iter_from(start), start=start):
        if step >= args.steps:
            break
        t0 = time.perf_counter()
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        params, opt_state, metrics = retry(
            lambda: step_fn(params, opt_state, batch)
        )
        dt = time.perf_counter() - t0
        detector.record(step, dt)
        losses.append(float(metrics["loss"]))
        if step % args.log_every == 0:
            print(f"step {step:5d} loss {losses[-1]:.4f} ({dt*1e3:.0f} ms)")
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            ckpt.save(
                args.ckpt_dir, step + 1, {"params": params, "opt": opt_state}
            )
            ckpt.gc_old(args.ckpt_dir)
    print(
        f"done: first loss {losses[0]:.4f} -> last {losses[-1]:.4f} "
        f"(median step {detector.median_step_s*1e3:.0f} ms)"
    )
    return losses


if __name__ == "__main__":
    main()
