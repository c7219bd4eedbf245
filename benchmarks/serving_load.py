"""Serving load benchmark: continuous batching under Poisson arrivals.

Drives :class:`repro.serving.ContinuousBatchingScheduler` with an open
arrival process (exponential inter-arrival times, a small palette of
prompt lengths so each distinct prefill shape compiles exactly once,
mixed generation budgets) twice — once through the **untuned** dispatch
context (``mode="default"``: the first valid schedule of every decode
task, the canonical baseline the tuner starts from) and once through the
**tuned** context (``mode="best"``: database-best traces) — and reports
decode/prefill throughput plus request-level latency percentiles for
both.

Decode-shape tasks come from ``extract_decode_tasks`` (the jaxpr of one
arena ``decode_step``), so the keys tuned here are exactly the keys the
scheduler's decode tick looks up.  Tasks without a database record are
tuned in-process first (same scheduler/search stack as
``benchmarks/end_to_end.py``); a CI-cached database skips straight to
dispatch.

Serving runs the **paged** tier (page-table KV arena + in-tick chunked
prefill, :class:`repro.serving.ServeConfig`); a **saturation sweep**
then replays the same arrival schedule at increasing offered rates
through both the paged tier and the PR 7 contiguous slot-pool baseline,
recording sustained tok/s and p95 latency per rate.

Outputs ``BENCH_serving.json`` — gated in CI by
``benchmarks/check_regression.py --serving``, which asserts the
tuned/untuned decode tok/s ratio, that at least one decode-shape
attention task *and* one dense/batch_matmul task actually dispatched,
and that the paged tier sustains strictly greater tok/s than the
slot-pool baseline at the highest swept arrival rate.

Usage::

    PYTHONPATH=src python benchmarks/serving_load.py --smoke \
        [--arch smollm-135m] [--slots 3] [--requests 12] [--rate 50]
        [--max-seq 64] [--max-new 8] [--trials 16] [--repeats 2]
        [--backend jnp] [--db results/tuning_db.json]
        [--json-out BENCH_serving.json]

Env: ``REPRO_TIMEOUT_S`` caps per-candidate measurement during tuning;
``REPRO_TRACE=<path>`` records the structured trace (serve.admit /
serve.evict / dispatch.hit events) that ``benchmarks/report.py`` folds.
"""

from __future__ import annotations

import argparse
import json
import os
import time
from pathlib import Path
from typing import Dict, List, Optional

import jax
import numpy as np

from repro.configs.base import get_config
from repro.integration.dispatch import DispatchContext
from repro.integration.extract import extract_decode_task_specs
from repro.launch.runtime import enable_compile_cache
from repro.models.registry import build_model
from repro.search.database import Database
from repro.search.evolutionary import SearchConfig
from repro.search.task_scheduler import TaskScheduler
from repro.serving import ContinuousBatchingScheduler, ServeConfig

REPO_ROOT = Path(__file__).resolve().parents[1]
JSON_PATH = REPO_ROOT / "BENCH_serving.json"


def make_load(
    rng: np.random.Generator,
    n_requests: int,
    rate: float,
    vocab: int,
    prompt_lens: List[int],
    max_new: int,
):
    """An open-loop arrival schedule: (arrival_s, prompt, max_new) rows.

    Prompt lengths cycle through a small palette (bounded jit retraces);
    generation budgets vary so releases interleave and slots recycle.
    """
    gaps = rng.exponential(1.0 / rate, n_requests)
    arrivals = np.cumsum(gaps)
    arrivals[0] = 0.0  # first request lands immediately
    load = []
    for i in range(n_requests):
        n = prompt_lens[i % len(prompt_lens)]
        prompt = rng.integers(0, vocab, n).astype(np.int32)
        budget = 2 + int(rng.integers(0, max(max_new - 1, 1)))
        load.append((float(arrivals[i]), prompt, budget))
    return load


def replay(sched: ContinuousBatchingScheduler, load) -> List:
    """Feed the arrival schedule in wall-clock time and tick to drain."""
    n0 = len(sched._requests)
    t_start = time.perf_counter()
    i = 0
    while i < len(load) or sched.pending():
        now = time.perf_counter() - t_start
        while i < len(load) and load[i][0] <= now:
            _, prompt, budget = load[i]
            sched.submit(prompt, max_new_tokens=budget)
            i += 1
        if sched.pending():
            sched.step()
        elif i < len(load):
            time.sleep(min(0.0005, load[i][0] - now))
    return sched._requests[n0:]


def _quantile(vals: List[float], q: float) -> Optional[float]:
    if not vals:
        return None
    return float(np.quantile(np.asarray(vals), q))


def _make_sched(
    cfg, params, ctx, *, slots: int, max_seq: int,
    paged: bool, page_size: int, prefill_chunk: int,
) -> ContinuousBatchingScheduler:
    return ContinuousBatchingScheduler(
        cfg, params,
        config=ServeConfig(
            max_slots=slots, max_seq=max_seq, paged=paged,
            page_size=page_size, prefill_chunk=prefill_chunk,
            dispatch=ctx,
        ),
    )


def _warmup(sched: ContinuousBatchingScheduler, cfg, lens: List[int]) -> None:
    """One request per distinct prompt length compiles every prefill
    shape plus both tick widths before anything is timed."""
    rng = np.random.default_rng(1234)
    for n in sorted(lens):
        sched.submit(rng.integers(0, cfg.vocab, n).astype(np.int32),
                     max_new_tokens=2)
    sched.run()


def run_mode(
    cfg, params, ctx, load, *, slots: int, max_seq: int, repeats: int,
    page_size: int = 16, prefill_chunk: int = 8,
) -> Dict:
    """One serving run per repeat through a single scheduler (jit caches
    are per-scheduler, so the warmup drain pays all compiles once);
    throughput is best-of-repeats, latency comes from the same best run."""
    sched = _make_sched(
        cfg, params, ctx, slots=slots, max_seq=max_seq,
        paged=True, page_size=page_size, prefill_chunk=prefill_chunk,
    )
    _warmup(sched, cfg, sorted({len(p) for _, p, _ in load}))
    best = None
    for _ in range(max(repeats, 1)):
        for k in sched.stats:
            sched.stats[k] = 0
        reqs = replay(sched, load)
        ttft = [r.ttft_s for r in reqs if r.ttft_s is not None]
        lat = [r.latency_s for r in reqs if r.latency_s is not None]
        summary = {
            "requests": len(reqs),
            "decode_tok_s": round(sched.decode_tok_s, 3),
            "prefill_tok_s": round(sched.prefill_tok_s, 3),
            "decode_steps": int(sched.stats["decode_steps"]),
            "decode_tokens": int(sched.stats["decode_tokens"]),
            "peak_active": int(sched.stats["peak_active"]),
            "ttft_s_p50": _quantile(ttft, 0.5),
            "ttft_s_p99": _quantile(ttft, 0.99),
            "latency_s_p50": _quantile(lat, 0.5),
            "latency_s_p99": _quantile(lat, 0.99),
            "outputs": [list(map(int, r.generated)) for r in reqs],
        }
        if best is None or summary["decode_tok_s"] > best["decode_tok_s"]:
            best = summary
    return best


def run_sweep(
    cfg, params, ctx, rates: List[float], *, slots: int, max_seq: int,
    max_new: int, requests: int, lens: List[int],
    page_size: int, prefill_chunk: int, seed: int,
) -> List[Dict]:
    """Saturation sweep: offered load vs sustained throughput and p95
    latency, paged+in-tick-prefill against the PR 7 slot-pool baseline.

    Both arenas replay the *same* arrival schedule at every rate (same
    prompts, budgets, and arrival times), so any throughput gap is the
    serving tier, not the load.  ``tok_s`` counts every processed token
    (prefill + decode) over the replay's wall clock — the slot-pool
    baseline pays a blocking batch=1 prefill call per admission, which
    is exactly the head-of-line cost the in-tick chunked path removes.
    """
    scheds = {
        "paged": _make_sched(
            cfg, params, ctx, slots=slots, max_seq=max_seq,
            paged=True, page_size=page_size, prefill_chunk=prefill_chunk,
        ),
        "slot_pool": _make_sched(
            cfg, params, ctx, slots=slots, max_seq=max_seq,
            paged=False, page_size=page_size, prefill_chunk=0,
        ),
    }
    for sched in scheds.values():
        _warmup(sched, cfg, lens)
    rows: List[Dict] = []
    for rate in sorted(rates):
        # per-rate deterministic load, identical across both arenas
        rng = np.random.default_rng(seed + int(round(rate * 1000)))
        load = make_load(rng, requests, rate, cfg.vocab, lens, max_new)
        row: Dict = {"rate_req_s": float(rate)}
        for name, sched in scheds.items():
            for k in sched.stats:
                sched.stats[k] = 0
            t0 = time.perf_counter()
            reqs = replay(sched, load)
            dt = time.perf_counter() - t0
            processed = (
                sched.stats["prefill_tokens"] + sched.stats["decode_tokens"]
            )
            gen = sum(len(r.generated) for r in reqs)
            lat = [r.latency_s for r in reqs if r.latency_s is not None]
            ttft = [r.ttft_s for r in reqs if r.ttft_s is not None]
            row[name] = {
                "tok_s": round(processed / dt, 3) if dt > 0 else 0.0,
                "gen_tok_s": round(gen / dt, 3) if dt > 0 else 0.0,
                "latency_s_p95": _quantile(lat, 0.95),
                "ttft_s_p95": _quantile(ttft, 0.95),
                "elapsed_s": round(dt, 4),
            }
        rows.append(row)
        print(
            f"  rate={rate:g} req/s: paged={row['paged']['tok_s']} tok/s "
            f"(p95 {row['paged']['latency_s_p95']:.4f}s)  "
            f"slot_pool={row['slot_pool']['tok_s']} tok/s "
            f"(p95 {row['slot_pool']['latency_s_p95']:.4f}s)"
        )
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny same-family config (CPU CI)")
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--rate", type=float, default=50.0,
                    help="mean arrival rate (req/s)")
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--trials", type=int, default=16,
                    help="tuning trials per decode task lacking a record")
    ap.add_argument("--repeats", type=int, default=2,
                    help="serving runs per mode; throughput is best-of")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV page size for the paged arena")
    ap.add_argument("--prefill-chunk", type=int, default=8,
                    help="in-tick prefill chunk width (tokens)")
    ap.add_argument("--sweep-rates", default="4,16,64",
                    help="comma-separated arrival rates (req/s) for the "
                         "paged-vs-slot-pool saturation sweep; empty skips")
    ap.add_argument("--sweep-requests", type=int, default=0,
                    help="requests per sweep point (default: --requests)")
    ap.add_argument("--backend", default="jnp")
    ap.add_argument("--runner", default="local")
    ap.add_argument("--db", default=str(REPO_ROOT / "results" / "tuning_db.json"))
    ap.add_argument("--json-out", default=str(JSON_PATH))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--retune", action="store_true",
                    help="re-tune decode tasks that already hold records")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    db_path = args.db
    if args.backend != "jnp":
        # per-backend database, same convention as end_to_end.py: best
        # traces must come from measurements through the serving backend
        root, ext = os.path.splitext(db_path)
        db_path = f"{root}_{args.backend}{ext}"
    Path(db_path).parent.mkdir(parents=True, exist_ok=True)

    # 1. decode-shape tasks from the arena serve/decode jaxprs — keyed on
    # m = slots, t = kv_len: exactly what the scheduler's tick looks up.
    # chunk/paged extend the walk over the mixed-tick serve_step program
    specs = extract_decode_task_specs(
        cfg, batch=args.slots, max_seq=args.max_seq, dispatchable_only=True,
        chunk=args.prefill_chunk, paged=True, page_size=args.page_size,
    )
    tasks = [s.to_tune_task(use_mxu=True) for s in specs]
    key_ops = {s.key: s.op for s in specs}
    print(f"{cfg.name}: {len(tasks)} dispatchable decode tasks")
    for t in tasks:
        print(f"  {t.key} (weight {t.weight})")

    # 2. tune the record-less keys (a warm database skips this entirely)
    db = Database(db_path)
    prior = {t.key: db.best(t.key) for t in tasks}
    to_tune = [t for t in tasks if args.retune or prior[t.key] is None]
    if to_tune:
        from repro.search.measure import create_runner

        runner_kwargs = {}
        if os.environ.get("REPRO_TIMEOUT_S"):
            runner_kwargs["timeout_s"] = float(os.environ["REPRO_TIMEOUT_S"])
        per_round = min(8, max(args.trials, 1))
        sched = TaskScheduler(
            to_tune,
            database=db,
            config=SearchConfig(
                max_trials=args.trials, init_random=per_round,
                population=12, measure_per_round=per_round,
            ),
            runner=create_runner(
                args.runner, backend=args.backend, **runner_kwargs
            ),
            backend=args.backend,
        )
        sched.tune(total_rounds=len(to_tune) * max(args.trials // 8, 2))
        sched.runner.close()

    # 3. symmetric coverage: tuned and untuned contexts serve the same
    # key set (keys whose traces compile in both), so the ratio isolates
    # what tuning changed rather than what coverage changed
    tuned_ctx = DispatchContext(
        db, tasks=tasks, mode="best", backend=args.backend
    )
    covered = [t for t in tasks if tuned_ctx.kernel(t.key) is not None]
    untuned_ctx = DispatchContext(
        db, tasks=covered, mode="default", backend=args.backend
    )
    both = [t for t in covered if untuned_ctx.kernel(t.key) is not None]
    if len(both) != len(covered):
        covered = both
    tuned_ctx = DispatchContext(
        db, tasks=covered, mode="best", backend=args.backend
    )
    untuned_ctx = DispatchContext(
        db, tasks=covered, mode="default", backend=args.backend
    )
    print(f"covered keys: {len(covered)}/{len(tasks)}")

    # 4. one load, two contexts: identical arrivals/prompts/budgets
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)
    lens = sorted({
        max(4, args.max_seq // 8),
        max(6, args.max_seq // 4),
        max(8, args.max_seq // 2),
    })
    load = make_load(
        rng, args.requests, args.rate, cfg.vocab, lens, args.max_new
    )

    untuned = run_mode(
        cfg, params, untuned_ctx, load,
        slots=args.slots, max_seq=args.max_seq, repeats=args.repeats,
        page_size=args.page_size, prefill_chunk=args.prefill_chunk,
    )
    tuned = run_mode(
        cfg, params, tuned_ctx, load,
        slots=args.slots, max_seq=args.max_seq, repeats=args.repeats,
        page_size=args.page_size, prefill_chunk=args.prefill_chunk,
    )
    # greedy streams should agree across schedules of the same workload;
    # recorded (not gated) because reduction order differs tuned/untuned
    outputs_match = untuned.pop("outputs") == tuned.pop("outputs")

    # 5. saturation sweep: paged+in-tick-prefill vs the slot-pool
    # baseline across offered arrival rates (same tuned context for both)
    rates = [float(r) for r in args.sweep_rates.split(",") if r.strip()]
    sweep: List[Dict] = []
    if rates:
        print("saturation sweep (paged vs slot_pool):")
        sweep = run_sweep(
            cfg, params, tuned_ctx, rates,
            slots=args.slots, max_seq=args.max_seq, max_new=args.max_new,
            requests=args.sweep_requests or args.requests, lens=lens,
            page_size=args.page_size, prefill_chunk=args.prefill_chunk,
            seed=args.seed,
        )

    ratio = (
        tuned["decode_tok_s"] / untuned["decode_tok_s"]
        if untuned["decode_tok_s"] > 0 else 0.0
    )
    decode_dispatch_keys = sorted(
        k for k in tuned_ctx.hits_by_key if k in key_ops
    )
    payload = {
        "benchmark": "serving_load",
        "model": cfg.name,
        "backend": args.backend,
        "smoke": bool(args.smoke),
        "slots": args.slots,
        "requests": args.requests,
        "rate_req_s": args.rate,
        "max_seq": args.max_seq,
        "trials": args.trials,
        "tasks": [
            {
                "key": s.key,
                "op": s.op,
                "weight": s.weight,
                "dispatched": s.key in tuned_ctx.hits_by_key,
            }
            for s in specs
        ],
        "decode_dispatch_keys": decode_dispatch_keys,
        "serving_config": {
            "paged": True,
            "page_size": args.page_size,
            "prefill_chunk": args.prefill_chunk,
        },
        "sweep": sweep,
        "untuned": untuned,
        "tuned": tuned,
        "decode_ratio": round(ratio, 4),
        "outputs_match": outputs_match,
        "dispatch_stats": dict(tuned_ctx.stats),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    Path(args.json_out).write_text(json.dumps(payload, indent=2) + "\n")
    print(
        f"decode tok/s: untuned={untuned['decode_tok_s']} "
        f"tuned={tuned['decode_tok_s']} (ratio {ratio:.3f}x)  "
        f"outputs_match={outputs_match}"
    )
    print(f"decode dispatch keys: {decode_dispatch_keys}")
    print(f"wrote {args.json_out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
