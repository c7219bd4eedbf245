#!/usr/bin/env python3
"""Bring-up smoke run: smollm-135m through extract -> tune -> dispatch ->
serve -> train on a TPU, at the model's published widths (30 layers,
d_model 576, 9/3 heads, d_ff 1536, vocab 49152) with random weights.

    python3 chip_smoke.py                # one chip: phases (a)-(e)
    python3 chip_smoke.py --four-chips   # a 4-chip host: mesh + router only

One chip, one process: (a) the device; (b) extract tasks and tune a few
of them with Mosaic-compiled Pallas kernels; (c) the jitted forward in
float32, XLA alone against the tuned and the untuned dispatch context,
all at the highest matmul precision; (d) requests through the paged
continuous-batching scheduler in float32 with the tuned decode context,
teacher-forced against the same scheduler without dispatch and compared
on logits; (e) bf16 AdamW train steps under the tuned context.

Four chips: the train step and forward on a (data=2, model=2) mesh with
mesh-aware dispatch against the same step on one device, run in a child
that holds all four chips; then a router over four serving workers, one
per chip, against greedy streams from a reference process that exits
before the workers start.  This parent never touches the device.

Every phase prints one line; times are set-up times, not speeds.  The
last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
any failure exits non-zero without it, and so does a run whose JAX finds
no TPU.  The tuning database goes to a fresh directory under ``--out``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
# children started as ``python -m repro...`` (serving workers) import it too
os.environ["PYTHONPATH"] = os.pathsep.join(
    filter(None, [str(ROOT / "src"), os.environ.get("PYTHONPATH")])
)

ARCH = "smollm-135m"
SEED = 0
SEQ = 128  # prefill tile: batch 1 x 128 tokens, the extraction default
SLOTS, MAX_SEQ, PAGE, CHUNK = 4, 384, 16, 32  # paged serving configuration
PROMPT_LENS = (7, 33, 64, 100, 129, 200, 257, 300)
NEW_TOKENS = 16
TRIALS = 8  # measured candidates per tuned task
TRAIN_STEPS = 3
# Dispatched against undispatched logits, both in float32 at the highest
# matmul precision (phases c and d): max |diff| over max |ref|, per
# forward and per sampled serving position.  Both sides then compute in
# f32 and differ only in summation order; a kernel that rounds to bf16
# is off by ~1e-2, one with a wrong tile, mask or page by more.
LOGIT_RTOL = 1e-4
# Mesh against one device, both bf16: the mesh never splits a
# contraction dim, so each logit is the same dot in the same order on
# either side; allow one bf16 rounding step of the largest logit.  The
# loss differs only in the order of its reduction over the data axis.
MESH_LOGIT_RTOL = 2.0 ** -8
LOSS_ATOL = 2e-4
ROUTER_PROMPT_LENS = (5, 17, 33, 48, 64, 80, 96, 100)
ROUTER_NEW_TOKENS = 8
ROUTER_MAX_SEQ = 128


class SmokeFailure(Exception):
    pass


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def rel_err(got, ref) -> float:
    import jax.numpy as jnp

    got, ref = got.astype(jnp.float32), ref.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))


def device_info() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def require_tpu() -> dict:
    """Phase (a): the device JAX gives this process must be a TPU."""
    info = device_info()
    say("a", f"platform={info['platform']} kind={info['kind']} "
             f"count={info['count']}")
    check(info["platform"] == "tpu", f"no TPU: JAX's device is {info}")
    return info


# ---------------------------------------------------------------------------
# One chip
# ---------------------------------------------------------------------------


def phase_tune(cfg, backend: str, db_path: Path):
    """(b) Extract prefill and decode tasks; tune a few of each kind."""
    from repro.integration.extract import (
        extract_decode_task_specs, extract_task_specs,
    )
    from repro.search.database import Database
    from repro.search.evolutionary import SearchConfig
    from repro.search.task_scheduler import TaskScheduler
    from repro.search.tune import TuneConfig

    t0 = time.perf_counter()
    prefill = extract_task_specs(cfg, batch=1, seq=SEQ, dispatchable_only=True)
    decode = extract_decode_task_specs(
        cfg, batch=SLOTS, max_seq=MAX_SEQ, dispatchable_only=True,
        chunk=CHUNK, paged=True, page_size=PAGE,
    )
    # the MLP up/down projections, the tied unembed, prefill attention
    # and decode attention: every kernel template on the main path
    want = [
        f"dense/k={cfg.d_model}/m={SEQ}/n={cfg.d_ff}",
        f"dense/k={cfg.d_ff}/m={SEQ}/n={cfg.d_model}",
        f"dense/k={cfg.d_model}/m={SEQ}/n={cfg.vocab}",
    ]
    by_key = {s.key: s for s in prefill + decode}
    chosen = [by_key[k] for k in want]
    chosen += [next(s for s in prefill if s.op == "attention")]
    chosen += [next(s for s in decode if s.op == "attention_decode")]
    tasks = [s.to_tune_task(use_mxu=True) for s in chosen]
    db = Database(str(db_path))
    sched = TaskScheduler(
        tasks, database=db,
        config=TuneConfig(
            search=SearchConfig(
                max_trials=TRIALS, init_random=4, population=8,
                measure_per_round=4, seed=SEED,
            ),
            runner_spec="cached+local", backend=backend, warm_start=False,
            seed=SEED,
        ),
    )
    sched.tune(total_rounds=2 * len(tasks))
    sched.runner.close()
    say("b", f"extracted {len(prefill)} prefill + {len(decode)} decode "
             f"tasks, tuned {len(tasks)} on {backend} "
             f"(setup {time.perf_counter() - t0:.1f}s)")
    failed = []
    for t, s in zip(tasks, sched.searches):
        finite = sum(1 for v in s.measured.values() if v != float("inf"))
        errs = "; ".join(e[:160] for _, e in s.errors[-2:])
        say("b", f"  {t.key}: measured={len(s.measured)} finite={finite} "
                 f"failures={s.total_failures}" + (f" errors: {errs}" if errs else ""))
        if finite == 0:
            failed.append(t.key)
    check(not failed, f"no candidate measured for {failed}")
    return db, prefill, decode, [t.key for t in tasks]


def _check_context(phase: str, ctx, tuned_keys) -> None:
    """Dispatch outcome of one context (a lowering or mesh failure has
    already raised): at least one hit, a Mosaic kernel in every tuned
    kernel's HLO, and the keys that ran a jnp lowering named."""
    from repro.core.tir import random_inputs

    stats = ctx.stats_by_key()
    check(ctx.stats["hits"] > 0, f"{ctx.mode} context served no kernel")
    jnp_lowered = sorted(
        k for k in ctx.hits_by_key
        if (ctx.kernel(k).meta or {}).get("lowered_with") == "jnp-fallback"
    )
    misses = sorted(k for k, r in stats.items() if r["misses"])
    for key in sorted(set(tuned_keys) & set(ctx.hits_by_key)):
        kern = ctx.kernel(key)
        hlo = kern.fn.lower(random_inputs(kern.func, SEED)).compile().as_text()
        check("tpu_custom_call" in hlo, f"{key}: no Mosaic kernel in its HLO")
    say(phase, f"  {ctx.mode}: hits={ctx.stats['hits']} "
               f"keys={sorted(ctx.hits_by_key)} jnp-lowered={jnp_lowered} "
               f"missed={misses}")


def f32_model(cfg):
    """The model with its seeded (bf16) weights widened to float32 and a
    float32 KV cache, for the checks against XLA at the highest matmul
    precision."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.models.registry import build_model

    cfg32 = dataclasses.replace(cfg, dtype="float32")
    model = build_model(cfg32)
    params = jax.tree.map(
        lambda x: x.astype(jnp.float32)
        if jnp.issubdtype(x.dtype, jnp.floating) else x,
        model.init(jax.random.PRNGKey(SEED)),
    )
    return cfg32, model, params


def phase_forward(cfg, model, params, db, prefill, tuned_keys, backend):
    """(c) Jitted float32 forward: XLA alone against the tuned (db best)
    and untuned (default) dispatch contexts."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.integration.dispatch import DispatchContext

    t0 = time.perf_counter()
    toks = jnp.asarray(
        np.random.default_rng(SEED).integers(0, cfg.vocab, (1, SEQ)), jnp.int32
    )

    def forward(ctx):
        fwd = jax.jit(lambda p, t: model.forward(p, tokens=t))
        with jax.default_matmul_precision("highest"):
            if ctx is None:
                return fwd(params, toks)
            with ctx:
                return jax.block_until_ready(fwd(params, toks))

    tasks = [s.to_tune_task(use_mxu=True) for s in prefill]
    ref = forward(None)
    tuned = DispatchContext(db, tasks=tasks, mode="best", backend=backend)
    untuned = DispatchContext(None, tasks=tasks, mode="default", backend=backend)
    errs = {}
    for ctx in (tuned, untuned):
        got = forward(ctx)
        check(got.shape == ref.shape, f"logits {got.shape} != {ref.shape}")
        check(bool(jnp.all(jnp.isfinite(got))), f"{ctx.mode}: non-finite logits")
        errs[ctx.mode] = rel_err(got, ref)
    say("c", f"f32 logits {tuple(ref.shape)}: rel err vs XLA tuned="
             f"{errs['best']:.3e} untuned={errs['default']:.3e} "
             f"(tol {LOGIT_RTOL}) (setup {time.perf_counter() - t0:.1f}s)")
    _check_context("c", tuned, tuned_keys)
    _check_context("c", untuned, tuned_keys)
    check(all(e <= LOGIT_RTOL for e in errs.values()), f"logits off: {errs}")
    return tuned


def phase_serve(cfg, params, db, decode, tuned_keys, backend):
    """(d) Paged continuous batching in float32 with the tuned decode
    context, teacher-forced: the dispatched scheduler samples the tokens
    the undispatched one chose, so both run the same ticks on the same
    inputs, and their logits are compared at every sampled position."""
    import jax
    import numpy as np

    from repro.integration.dispatch import DispatchContext
    from repro.serving import ContinuousBatchingScheduler, ServeConfig

    t0 = time.perf_counter()
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab, n) for n in PROMPT_LENS]
    ctx = DispatchContext(
        db, tasks=[s.to_tune_task(use_mxu=True) for s in decode],
        mode="best", backend=backend,
    )

    def scheduler(dispatch, sample):
        sched = ContinuousBatchingScheduler(
            cfg, params,
            config=ServeConfig(
                max_slots=SLOTS, max_seq=MAX_SEQ, paged=True, page_size=PAGE,
                prefill_chunk=CHUNK, seed=SEED, dispatch=dispatch,
            ),
        )
        sched._sample = sample
        for p in prompts:
            sched.submit(p, max_new_tokens=NEW_TOKENS)
        with jax.default_matmul_precision("highest"):
            return [list(map(int, r.generated)) for r in sched.run()], sched

    ref_rows, got_rows, chosen = [], [], []

    def greedy(logits, temperature):
        ref_rows.append(logits.copy())
        chosen.append(int(np.argmax(logits)))
        return chosen[-1]

    def forced(logits, temperature):
        got_rows.append(logits.copy())
        return chosen[len(got_rows) - 1]

    want, _ = scheduler(None, greedy)
    got, sched = scheduler(ctx, forced)
    s = sched.stats
    say("d", f"{len(prompts)} requests (prompts {min(PROMPT_LENS)}-"
             f"{max(PROMPT_LENS)} tokens, {NEW_TOKENS} new, greedy, f32): "
             f"decode ticks={s['decode_steps']} prefill chunks="
             f"{s['prefill_chunks']} programs with tuned decode attention="
             f"{ctx.stats['attention_decode_tuned']} "
             f"(setup {time.perf_counter() - t0:.1f}s)")
    _check_context("d", ctx, tuned_keys)
    check(ctx.stats["attention_decode_tuned"] > 0, "decode kernel never served")
    check(got == want and len(got_rows) == len(ref_rows),
          "teacher-forced run sampled a different schedule")
    errs = np.array([
        np.max(np.abs(g - r)) / np.max(np.abs(r))
        for g, r in zip(got_rows, ref_rows)
    ])
    agree = sum(int(np.argmax(g)) == c for g, c in zip(got_rows, chosen))
    say("d", f"  {len(errs)} sampled positions: logits rel err vs the "
             f"undispatched scheduler max={errs.max():.3e} "
             f"median={np.median(errs):.3e} (tol {LOGIT_RTOL}); greedy "
             f"token equal at {agree}/{len(errs)}")
    check(errs.max() <= LOGIT_RTOL,
          f"decode logits off at positions {np.flatnonzero(errs > LOGIT_RTOL)}")


def phase_train(cfg, model, params, ctx):
    """(e) AdamW train steps at full width under the tuned context."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import ShapeConfig
    from repro.models.registry import make_train_batch
    from repro.training.optimizer import OptConfig, adamw_init
    from repro.training.train_loop import make_train_step

    t0 = time.perf_counter()
    step = jax.jit(make_train_step(model, OptConfig(), dispatch=ctx))
    batch = make_train_batch(cfg, ShapeConfig("smoke", SEQ, 1, "train"), SEED)
    opt = adamw_init(params)
    hits0 = ctx.stats["hits"]
    losses = []
    for _ in range(TRAIN_STEPS):
        params, opt, metrics = step(params, opt, batch)
        losses.append(float(metrics["loss"]))
    say("e", f"{TRAIN_STEPS} AdamW steps, batch 1x{SEQ}: losses="
             f"{[round(x, 4) for x in losses]} tuned kernels traced="
             f"{ctx.stats['hits'] - hits0} "
             f"(setup {time.perf_counter() - t0:.1f}s)")
    check(all(map(jnp.isfinite, losses)), f"non-finite loss {losses}")


def run_one_chip(out: Path) -> dict:
    import jax

    from repro.configs.base import get_config
    from repro.models.registry import build_model

    info = require_tpu()
    cfg = get_config(ARCH)
    tune_dir = out / "tune"
    shutil.rmtree(tune_dir, ignore_errors=True)
    tune_dir.mkdir(parents=True)
    db, prefill, decode, tuned_keys = phase_tune(
        cfg, "pallas", tune_dir / "tuning_db.json"
    )
    cfg32, model32, params32 = f32_model(cfg)
    ctx = phase_forward(
        cfg32, model32, params32, db, prefill, tuned_keys, "pallas"
    )
    phase_serve(cfg32, params32, db, decode, tuned_keys, "pallas")
    del params32
    model = build_model(cfg)
    phase_train(cfg, model, model.init(jax.random.PRNGKey(SEED)), ctx)
    return info


# ---------------------------------------------------------------------------
# Four chips
# ---------------------------------------------------------------------------


def phase_mesh(backend: str) -> dict:
    """Train step + forward on a (data=2, model=2) mesh with mesh-aware
    dispatch, against the same step and forward on one device."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import ShapeConfig, get_config
    from repro.distributed import sharding as shd
    from repro.integration.dispatch import DispatchContext
    from repro.integration.extract import extract_tasks
    from repro.launch.mesh import make_mesh
    from repro.models.registry import build_model, make_train_batch
    from repro.training.optimizer import OptConfig, adamw_init
    from repro.training.train_loop import make_train_step

    info = require_tpu()
    check(info["count"] == 4, f"--four-chips needs 4 devices, got {info}")
    t0 = time.perf_counter()
    cfg = get_config(ARCH)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(SEED))
    opt = adamw_init(params)
    batch = make_train_batch(cfg, ShapeConfig("mesh", SEQ, 2, "train"), SEED)
    toks = batch["tokens"][:, :-1]

    # one device: global-shape tasks, the untuned (default) kernels
    one = DispatchContext(
        None, tasks=extract_tasks(cfg, batch=2, seq=SEQ, dispatchable_only=True,
                                  mesh=None),
        mode="default", backend=backend,
    )
    with one:
        ref_logits = jax.jit(lambda p, t: model.forward(p, tokens=t))(params, toks)
    _, _, ref_m = jax.jit(make_train_step(model, OptConfig(), dispatch=one))(
        params, opt, batch
    )
    mesh = make_mesh((2, 2), ("data", "model"))
    with shd.use_mesh(mesh):
        ctx = DispatchContext(
            None,
            tasks=extract_tasks(cfg, batch=2, seq=SEQ, dispatchable_only=True),
            mode="default", backend=backend,
        )
        p_sh = shd.param_shardings(mesh, params)
        o_sh = shd.opt_state_shardings(mesh, params)
        b_sh = shd.batch_shardings(mesh, batch)
        params_m = jax.device_put(params, p_sh)
        opt_m = jax.device_put(opt, o_sh)
        batch_m = jax.device_put(batch, b_sh)
        leaves = jax.tree.leaves(params_m)
        devices = set().union(*(x.sharding.device_set for x in leaves))
        split = sum(
            1 for x in leaves
            if len({s.device for s in x.addressable_shards}) == 4
            and x.addressable_shards[0].data.shape != x.shape
        )
        check(len(devices) == 4, f"parameters on {len(devices)} devices")
        check(split > 0, "no parameter is split across the mesh")
        with ctx:
            logits = jax.jit(lambda p, t: model.forward(p, tokens=t))(
                params_m, batch_m["tokens"][:, :-1]
            )
        step = jax.jit(
            make_train_step(model, OptConfig(), dispatch=ctx),
            in_shardings=(p_sh, o_sh, b_sh),
            out_shardings=(p_sh, o_sh, None),
        )
        _, _, m = step(params_m, opt_m, batch_m)
    loss, ref_loss = float(m["loss"]), float(ref_m["loss"])
    err = rel_err(logits, ref_logits)
    say("mesh", f"(data=2, model=2) over {len(devices)} devices, {split}/"
                f"{len(leaves)} parameters split; mesh_sharded="
                f"{ctx.stats['mesh_sharded']} hits={ctx.stats['hits']}; "
                f"loss {loss:.5f} vs one device {ref_loss:.5f} (atol "
                f"{LOSS_ATOL}); logits rel err {err:.3e} (tol "
                f"{MESH_LOGIT_RTOL:.3e}) "
                f"(setup {time.perf_counter() - t0:.1f}s)")
    check(ctx.stats["mesh_sharded"] > 0, "no kernel was served per shard")
    check(abs(loss - ref_loss) <= LOSS_ATOL, "mesh loss differs")
    check(err <= MESH_LOGIT_RTOL, "mesh logits differ")
    return info


def router_prompts(vocab: int):
    import numpy as np

    rng = np.random.default_rng(SEED + 1)
    return [rng.integers(0, vocab, n).tolist() for n in ROUTER_PROMPT_LENS]


ROUTER_WORKER = dict(
    max_slots=SLOTS, max_seq=ROUTER_MAX_SEQ, page_size=PAGE,
    prefill_chunk=CHUNK,
)


def phase_reference(out_file: Path) -> None:
    """Greedy streams of the router's prompts from one scheduler built
    exactly as a serving worker builds its own."""
    from repro.configs.base import get_config
    from repro.serving.worker import build_scheduler

    require_tpu()
    sched = build_scheduler(ARCH, seed=SEED, smoke=False, **ROUTER_WORKER)
    for p in router_prompts(get_config(ARCH).vocab):
        sched.submit(p, max_new_tokens=ROUTER_NEW_TOKENS)
    streams = [list(map(int, r.generated)) for r in sched.run()]
    out_file.write_text(json.dumps(streams))


def _child(args, env=None, timeout=900) -> list:
    """Run this script as a child; its output is echoed and returned as
    lines."""
    proc = subprocess.run(
        [sys.executable, str(Path(__file__).resolve()), *args],
        env={**os.environ, **(env or {})}, capture_output=True, text=True,
        timeout=timeout,
    )
    sys.stdout.write(proc.stdout)
    sys.stderr.write(proc.stderr[-4000:])
    check(proc.returncode == 0, f"child {args} exited {proc.returncode}")
    return proc.stdout.splitlines()


def run_four_chips(out: Path) -> dict:
    from repro.configs.base import get_config
    from repro.launch.runtime import chip_child_envs
    from repro.serving.router import ServingRouter

    info = json.loads(_child(["--phase", "mesh"])[-1])["device"]
    t0 = time.perf_counter()
    ref_file = out / "router_reference.json"
    _child(["--phase", "reference", "--out", str(ref_file)],
           env=chip_child_envs(1)[0])
    want = json.loads(ref_file.read_text())
    prompts = router_prompts(get_config(ARCH).vocab)
    router = ServingRouter.spawn(
        4, model=ARCH, extra_args=["--full-size", "--seed", str(SEED)],
        **ROUTER_WORKER,
    )
    try:
        reqs = [router.submit(p, max_new=ROUTER_NEW_TOKENS) for p in prompts]
        router.drain(timeout_s=600)
        got = [r.tokens for r in reqs]
        per_worker = [w.completed for w in router.workers]
    finally:
        router.shutdown()
    say("router", f"4 workers (one chip each), {len(prompts)} requests, "
                  f"completed per worker {per_worker}; streams equal the "
                  f"reference: {got == want} "
                  f"(setup {time.perf_counter() - t0:.1f}s)")
    check(got == want, f"router streams {got} != reference {want}")
    check(sum(1 for c in per_worker if c) > 1, "one worker served everything")
    return info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the mesh and router paths on a 4-chip host")
    ap.add_argument("--phase", choices=("mesh", "reference"),
                    help=argparse.SUPPRESS)  # the --four-chips children
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "chip_smoke"))
    args = ap.parse_args(argv)
    out = Path(args.out)
    try:
        if args.phase or not args.four_chips:
            # the four-chip parent must not touch JAX; its children do
            from repro.launch.runtime import enable_compile_cache

            enable_compile_cache()
        if args.phase == "reference":
            phase_reference(out)
            return 0
        out.mkdir(parents=True, exist_ok=True)
        if args.phase == "mesh":
            info = phase_mesh("pallas")
        elif args.four_chips:
            info = run_four_chips(out)
        else:
            info = run_one_chip(out)
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
