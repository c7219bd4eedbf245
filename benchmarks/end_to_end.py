"""Figure 9: end-to-end model optimization — measured, not estimated.

The full loop the paper's headline number comes from:

  1. **extract** — ``integration.extract`` walks the model's forward jaxpr
     into weighted tensor-program tasks (no hand-coded per-model shapes);
  2. **tune** — the gradient ``TaskScheduler`` allocates measurement
     trials across tasks by occurrence weight, persisting best traces to
     the database;
  3. **dispatch** — ``integration.dispatch.DispatchContext`` swaps the
     tuned kernels into the model forward, and we time *actual forward
     passes* end to end.

Reported per model (and written to ``BENCH_end_to_end.json`` at the repo
root, machine-readable for the CI artifact):

* ``untuned_forward_ms`` — forward with every dispatched workload on its
  *default* schedule (first valid space sample: the canonical untuned
  tensor program, as in the paper's untuned baseline);
* ``tuned_forward_ms``   — same forward with the database's best traces;
* ``xla_forward_ms``     — the pure-XLA forward (no dispatch), context;
* ``speedup``            — untuned / tuned: what the search bought,
  measured in wall-clock through the whole model.

Candidates are measured *and* served through the same lowering backend
(``REPRO_BACKEND`` / ``--backend``: ``jnp`` default, ``pallas`` for the
Mosaic-compiled Pallas kernels on a TPU, ``pallas-interpret`` for the
Pallas interpreter on CPU) — the measured artifact is the dispatched
artifact, per-backend.

Env knobs: ``REPRO_BENCH_TRIALS`` (per-task measurement budget, default
24), ``REPRO_RUNNER`` (measurement runner spec, default ``cached+local``:
in-process, because a chip belongs to one process),
``REPRO_BACKEND`` (lowering backend, default ``jnp``),
``REPRO_E2E_MODELS`` (comma list, default ``smollm-135m``),
``REPRO_E2E_TASKS`` (task cap by weight x flops, default 6 — enough to
cover both attention contractions), ``REPRO_E2E_OPS`` (comma list
restricting extraction to these op classes — the pallas-interpret CI
job uses ``attention,batch_matmul`` so its budget goes to the ops its
dispatch gate checks), ``REPRO_E2E_SEQ`` (token tile,
default 128), ``REPRO_TIMEOUT_S`` (per-candidate measurement timeout;
CI smoke lowers it so pathological interpret-mode candidates get cut
off early), ``REPRO_E2E_SKIP_TUNED=1`` (skip tuning for tasks that
already hold a database record — the CI database cache relies on this
to avoid re-tuning identical tasks on every push),
``REPRO_E2E_SERVE=0`` (skip the short serving leg that reports
prefill/decode tok/s), ``REPRO_TRACE=<path>`` (structured trace JSONL
of the whole run — fold it with ``benchmarks/report.py``).
"""

from __future__ import annotations

import json
import os
import time
from pathlib import Path
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.backends.registry import resolve_backend_spec
from repro.configs.base import get_config
from repro.integration.dispatch import DispatchContext
from repro.integration.extract import extract_task_specs
from repro.launch.runtime import enable_compile_cache
from repro.models.registry import build_model
from repro.search.database import Database
from repro.search.evolutionary import SearchConfig
from repro.search.task_scheduler import TaskScheduler
from repro.search.tune import TuneConfig

REPO_ROOT = Path(__file__).resolve().parents[1]
JSON_PATH = REPO_ROOT / "BENCH_end_to_end.json"


def _models() -> List[str]:
    raw = os.environ.get("REPRO_E2E_MODELS", "smollm-135m")
    return [m.strip() for m in raw.split(",") if m.strip()]


def task_selection_env():
    """The env knobs that define the tuning problem: (models, seq,
    max_tasks, ops).  Shared with ``benchmarks/task_cache_key.py`` — the
    CI database cache key must hash exactly the task set this benchmark
    tunes, so there is one parser, not two."""
    from repro.integration.extract import EXTRACTABLE_OPS

    seq = int(os.environ.get("REPRO_E2E_SEQ", "128"))
    max_tasks = int(os.environ.get("REPRO_E2E_TASKS", "6"))
    ops = tuple(
        o.strip()
        for o in os.environ.get("REPRO_E2E_OPS", "").split(",")
        if o.strip()
    ) or EXTRACTABLE_OPS
    return _models(), seq, max_tasks, ops


def _timed_forward(model, params, toks, ctx=None, repeats: int = 3):
    """(median wall-clock ms, logits) of a jitted forward traced under ``ctx``."""
    from repro.integration.dispatch import maybe_dispatch

    fwd = jax.jit(lambda p, t: model.forward(p, tokens=t))  # fresh cache per ctx
    with maybe_dispatch(ctx):
        out = jax.block_until_ready(fwd(params, toks))  # compile + first call
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(fwd(params, toks))
            times.append(time.perf_counter() - t0)
    return float(np.median(times)) * 1e3, out


def run(
    db_path: str = "results/tuning_db.json",
    csv: bool = True,
    json_path: Path = JSON_PATH,
    backend: str = None,
) -> List[Dict]:
    trials = int(os.environ.get("REPRO_BENCH_TRIALS", "24"))
    runner_spec = os.environ.get("REPRO_RUNNER", "cached+local")
    backend = resolve_backend_spec(backend)
    if backend != "jnp":
        # per-backend database and report: best-trace selection must come
        # from measurements taken through the backend that will serve
        # them, and a pallas run must not clobber the committed jnp
        # BENCH_end_to_end.json
        root, ext = os.path.splitext(db_path)
        db_path = f"{root}_{backend}{ext}"
        json_path = json_path.with_name(
            f"{json_path.stem}_{backend}{json_path.suffix}"
        )
    models, seq, max_tasks, ops = task_selection_env()
    repeats = int(os.environ.get("REPRO_E2E_REPEATS", "3"))
    rounds_per_task = max(trials // 8, 2)
    out: List[Dict] = []
    for arch in models:
        cfg = get_config(arch)
        # 1. extract weighted tasks from the real model config.  Only
        # dispatchable sites: trials spent on layouts the model can't
        # consume yet (e.g. the transposed unembed) would never show up in
        # the measured forward.  The attention score/value contractions
        # are dispatchable batch_matmul sites since the bmm_op hook.
        specs = extract_task_specs(
            cfg, batch=1, seq=seq, max_tasks=max_tasks, ops=ops,
            dispatchable_only=True,
        )
        tasks = [s.to_tune_task(use_mxu=True) for s in specs]
        # 2. tune: warmup round-robin, then gradient allocation; round
        # size scales down with small smoke budgets.  Candidates build
        # through the selected lowering backend.
        per_round = min(8, max(trials, 1))
        db = Database(db_path)
        # REPRO_E2E_SKIP_TUNED=1: tune only tasks without a database record
        # — with a CI-cached database (see .github/workflows/ci.yml) an
        # unchanged task set skips straight to dispatch instead of
        # re-tuning identical tasks on every push
        skip_tuned = os.environ.get("REPRO_E2E_SKIP_TUNED") == "1"
        prior = {t.key: db.best(t.key) for t in tasks}
        to_tune = [
            t for t in tasks if not (skip_tuned and prior[t.key] is not None)
        ]
        rounds_run = 0
        if to_tune:
            from repro.search.measure import create_runner

            runner_kwargs = {}
            if os.environ.get("REPRO_TIMEOUT_S"):
                runner_kwargs["timeout_s"] = float(
                    os.environ["REPRO_TIMEOUT_S"]
                )
            sched = TaskScheduler(
                to_tune,
                database=db,
                config=TuneConfig(
                    search=SearchConfig(
                        max_trials=trials, init_random=per_round,
                        population=12, measure_per_round=per_round,
                    ),
                    runner_spec=create_runner(
                        runner_spec, backend=backend, **runner_kwargs
                    ),
                    backend=backend,
                ),
            )
            sched.tune(total_rounds=len(to_tune) * rounds_per_task)
            sched.runner.close()
            rounds_run = sched.rounds_run
        best = {}
        for t in tasks:
            rec = db.best(t.key)
            best[t.key] = rec.latency_s if rec is not None else float("inf")
        # 3. dispatch: measure real forward passes, serving the *same*
        # backend-lowered artifacts the tuner measured.  Untuned and
        # tuned contexts cover the same key set (keys whose stored trace
        # compiles) so the comparison isolates what the search changed.
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        toks = jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab, (1, seq)),
            jnp.int32,
        )
        tuned_ctx = DispatchContext(db, tasks=tasks, mode="best", backend=backend)
        # cover exactly the keys served in *both* contexts: a
        # stale/corrupt record passes db.best() but fails validation, and
        # a space may have no valid default schedule — either way the key
        # must fall back in both contexts or the comparison skews
        covered = [t for t in tasks if tuned_ctx.kernel(t.key) is not None]
        untuned_ctx = DispatchContext(
            db, tasks=covered, mode="default", backend=backend
        )
        both = [t for t in covered if untuned_ctx.kernel(t.key) is not None]
        if len(both) != len(covered):
            covered = both
            tuned_ctx = DispatchContext(
                db, tasks=covered, mode="best", backend=backend
            )
            untuned_ctx = DispatchContext(
                db, tasks=covered, mode="default", backend=backend
            )
        covered_keys = {t.key for t in covered}
        xla_ms, ref = _timed_forward(model, params, toks, None, repeats)
        untuned_ms, _ = _timed_forward(model, params, toks, untuned_ctx, repeats)
        tuned_ms, got = _timed_forward(model, params, toks, tuned_ctx, repeats)
        hits, misses = tuned_ctx.stats["hits"], tuned_ctx.stats["misses"]
        # 4. serve: a short batched prefill+decode leg through the tuned
        # context — emits serve.prefill / serve.decode trace events and
        # the tok/s the report's serving section summarizes.  Off with
        # REPRO_E2E_SERVE=0 (forward-only timing runs).
        prefill_tok_s = decode_tok_s = None
        if os.environ.get("REPRO_E2E_SERVE", "1") == "1":
            from repro.serving.engine import ServingEngine

            eng = ServingEngine(
                cfg, params, max_batch=2, max_seq=min(seq, 64),
                dispatch=tuned_ctx,
            )
            rng = np.random.default_rng(0)
            for _ in range(2):
                eng.submit(
                    rng.integers(0, cfg.vocab, 8), max_new_tokens=4
                )
            eng.run()
            prefill_tok_s = round(eng.prefill_tok_s, 2)
            decode_tok_s = round(eng.decode_tok_s, 2)
        # numeric check: tuned forward vs the pure-XLA reference, reusing
        # the logits the timed runs already produced
        max_err = float(
            jnp.max(jnp.abs(got.astype(jnp.float32) - ref.astype(jnp.float32)))
        )
        ref_scale = float(jnp.max(jnp.abs(ref.astype(jnp.float32)))) or 1.0
        # "dispatched" = the tuned kernel was actually looked up (hit) at
        # forward trace time, not merely compiled — a hook that silently
        # stops consulting the context must fail the coverage gate
        task_rows = []
        for s in specs:
            trow = {
                "key": s.key,
                "op": s.op,
                "weight": s.weight,
                "flops": s.flops,
                "dispatched": (
                    s.key in covered_keys
                    and tuned_ctx.hits_by_key.get(s.key, 0) > 0
                ),
                "best_latency_us": (
                    round(best[s.key] * 1e6, 2)
                    if np.isfinite(best[s.key])
                    else None
                ),
            }
            kern = tuned_ctx.kernel(s.key)
            if kern is not None and kern.meta:
                # lowering provenance: for attention this is where the
                # tuned (block_q, block_kv) vs the pre-tuning fixed
                # default becomes visible in the artifact
                for mk in (
                    "pallas_blocks_sampled",
                    "pallas_blocks_snapped",
                    "pallas_kernel",
                ):
                    if mk in kern.meta:
                        trow[mk] = kern.meta[mk]
            task_rows.append(trow)
        attn_total = sum(1 for t in task_rows if t["op"] == "batch_matmul")
        attn_disp = sum(
            1 for t in task_rows if t["op"] == "batch_matmul" and t["dispatched"]
        )
        fused_total = sum(1 for t in task_rows if t["op"] == "attention")
        fused_disp = sum(
            1 for t in task_rows if t["op"] == "attention" and t["dispatched"]
        )
        row = {
            "model": arch,
            "seq": seq,
            "backend": backend,
            "trials_per_task": trials,
            "rounds_run": rounds_run,
            "untuned_forward_ms": round(untuned_ms, 3),
            "tuned_forward_ms": round(tuned_ms, 3),
            "xla_forward_ms": round(xla_ms, 3),
            "speedup": round(untuned_ms / tuned_ms, 3) if tuned_ms else 0.0,
            "dispatch_hits": hits,
            "dispatch_misses": misses,
            "attention_contractions": attn_total,
            "attention_contractions_dispatched": attn_disp,
            "attention_fused_tasks": fused_total,
            "attention_fused_dispatched": fused_disp,
            "attention_tuned_hits": tuned_ctx.stats.get("attention_tuned", 0),
            "numerics_max_abs_err": round(max_err, 6),
            "numerics_rel_err": round(max_err / ref_scale, 6),
            "serving_prefill_tok_s": prefill_tok_s,
            "serving_decode_tok_s": decode_tok_s,
            "tasks": task_rows,
        }
        out.append(row)
        if csv:
            print(
                f"end_to_end/{arch},backend={backend},"
                f"untuned={untuned_ms:.1f}ms,"
                f"tuned={tuned_ms:.1f}ms,xla={xla_ms:.1f}ms,"
                f"speedup={row['speedup']:.2f}x,"
                f"hits={row['dispatch_hits']},"
                f"attn_bmm_dispatched={attn_disp}/{attn_total},"
                f"attn_fused_dispatched={fused_disp}/{fused_total},"
                f"rel_err={row['numerics_rel_err']:.2e}"
                + (
                    f",prefill={prefill_tok_s}tok/s,decode={decode_tok_s}tok/s"
                    if prefill_tok_s is not None else ""
                )
            )
    payload = {
        "benchmark": "end_to_end",
        "runner": runner_spec,
        "backend": backend,
        "models": out,
    }
    json_path.write_text(json.dumps(payload, indent=2) + "\n")
    if csv:
        print(f"wrote {json_path}")
    return out


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--backend", default=None,
        help="lowering-backend spec (jnp, pallas, ...); default "
             "REPRO_BACKEND env or jnp",
    )
    ap.add_argument("--db", default="results/tuning_db.json")
    args = ap.parse_args(argv)
    enable_compile_cache()
    run(db_path=args.db, backend=args.backend)


if __name__ == "__main__":
    main()
