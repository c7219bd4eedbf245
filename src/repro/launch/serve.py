"""Serving launcher: requests through the paged continuous-batching tier.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
        --requests 8 --prompt-len 32 --new-tokens 16 \
        [--smoke] [--db results/tuning_db_pallas.json]

The model runs at its published widths with random weights;
``--smoke`` takes the reduced config instead.  ``--db`` serves the
database's tuned kernels (lowered by ``REPRO_BACKEND``) through a
``DispatchContext`` built from the decode tasks of this serving
configuration.
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from ..configs.base import ARCHS, get_config
from ..models.registry import build_model
from ..serving import ContinuousBatchingScheduler, ServeConfig
from .runtime import enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="smollm-135m")
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--db", default="", help="tuning database to dispatch")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    config = ServeConfig(
        max_slots=args.max_slots, max_seq=args.prompt_len + args.new_tokens,
        temperature=args.temperature,
    )
    if args.db:
        from ..integration.dispatch import DispatchContext
        from ..integration.extract import extract_decode_tasks
        from ..search.database import Database

        tasks = extract_decode_tasks(
            cfg, batch=config.max_slots, max_seq=config.max_seq,
            dispatchable_only=True, chunk=config.prefill_chunk, paged=True,
            page_size=config.page_size,
        )
        config.dispatch = DispatchContext(Database(args.db), tasks=tasks)
    sched = ContinuousBatchingScheduler(cfg, params, config=config)
    rng = np.random.default_rng(0)
    for _ in range(args.requests):
        sched.submit(
            rng.integers(0, cfg.vocab, args.prompt_len),
            max_new_tokens=args.new_tokens,
        )
    reqs = sched.run()
    for r in reqs[:4]:
        print(f"req {r.rid}: {r.generated[:10]} ...")
    s = sched.stats
    print(
        f"prefill {s['prefill_tokens']} tok in {s['prefill_s']:.2f}s | "
        f"decode {s['decode_tokens']} tok in {s['decode_steps']} steps, "
        f"{s['decode_s']:.2f}s (host clock, compiles included)"
    )
    if config.dispatch is not None:
        print(f"dispatch: {config.dispatch.stats}")
    return reqs


if __name__ == "__main__":
    main()
