"""Prefill job: a closed loop of back-to-back tuned forwards.

Set-up makes the weights from the seed, extracts the forward's tasks at
the mix's batch and length, tunes the heaviest with the cell's budget,
builds the tuned dispatch context and compiles the one forward program.
The window then runs that program on fresh token ids every step and
blocks on each result.  A seeded reservoir keeps a few steps' logits;
once the window has closed they are compared, sequence by sequence, with
the plain float32 reference on the same weights and tokens.

Observations: ``tokens`` and ``window_s`` of the window, the forward's
FLOPs, tuning and dispatch counts, and in a traced run the tuned-over-XLA
step ratio and the reduced trace of a few more steps.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import harness
import traffic


@dataclass
class State:
    ref: Any
    z: Any
    model: Any
    params: Any
    ctx: Any
    fwd: Any
    batch: Any


def setup(r) -> State:
    import jax

    from repro.integration.dispatch import DispatchContext
    from repro.integration.extract import extract_task_specs
    from repro.models.registry import build_model

    cfg = harness.program_config(r.conf)
    model = build_model(cfg)
    ref = harness.reference(r.conf)
    z = ref.sizes(r.conf)
    mix, sp = r.traffic, r.spans
    with sp("weights"):
        params = harness.make_params(r.conf, r.seed, model)
    with sp("extract"):
        specs = extract_task_specs(
            cfg, batch=int(mix["batch"]), seq=int(mix["seq"]),
            dispatchable_only=True, mesh=None,
        )
    db, r.obs["tune"] = harness.tune(
        cfg, specs, r.workload["tuning"], r.backend, sp
    )
    ctx = DispatchContext(
        db, tasks=[s.to_tune_task(use_mxu=True) for s in specs],
        mode="best", backend=r.backend,
    )
    fwd = jax.jit(lambda p, t: model.forward(p, tokens=t))
    batch = traffic.packed_batch_fn(mix, z.V, r.seed)
    with sp("compile"), ctx:
        jax.block_until_ready(fwd(params, batch(0)))
    r.obs["dispatch"] = harness.dispatch_counts(ctx)
    return State(ref, z, model, params, ctx, fwd, batch)


def reseed(r, st: State, seed: int) -> None:
    """New weights and token stream from ``seed``, same compiled program."""
    st.params = None
    st.params = harness.make_params(r.conf, seed, st.model)
    st.batch = traffic.packed_batch_fn(r.traffic, st.z.V, seed)


def steps(r, st: State, first: int, count: int = 0, seconds: float = 0.0,
          keep: int = 0, seed: int = 0) -> Tuple[int, Dict]:
    """Run forwards from step ``first``: ``count`` of them, or until
    ``seconds`` have passed; a seeded reservoir keeps ``keep`` outputs."""
    import numpy as np

    sp = r.spans
    pick = np.random.default_rng([seed % (1 << 64), 7])
    kept: Dict[int, Tuple[int, Any]] = {}
    t0 = time.perf_counter()
    n = 0
    with st.ctx:
        while True:
            with sp("prep"):
                toks = st.batch(first + n)
            with sp("step"):
                out = st.fwd(st.params, toks)
            with sp("sync"):
                out.block_until_ready()
            j = n if n < keep else int(pick.integers(0, n + 1))
            if j < keep:
                kept[j] = (first + n, out)
            del out
            n += 1
            if count and n >= count:
                break
            if not count and time.perf_counter() - t0 >= seconds:
                break
    return n, kept


def _kl(ref, got):
    """Mean over positions of KL(softmax(ref) || softmax(got))."""
    import jax
    import jax.numpy as jnp

    lr = jax.nn.log_softmax(ref, axis=-1)
    lg = jax.nn.log_softmax(got, axis=-1)
    return float(jnp.mean(jnp.sum(jnp.exp(lr) * (lr - lg), axis=-1)))


def compare(r, st: State, kept: Dict, quant: Optional[str] = None) -> Dict:
    """Worst over the kept sequences of the relative error (Frobenius) and
    of the mean next-token KL divergence of the program's logits against
    the float32 reference; with ``quant`` also those of the reference
    computed at that precision, put in the program's place."""
    import jax.numpy as jnp

    out = {"logits_rel_err": 0.0, "logits_kl": 0.0, "bad": 0}
    if quant:
        out.update(control_rel_err=0.0, control_kl=0.0)

    def worst(key, value):
        out[key] = max(out[key], value)

    for step_i, logits in sorted(kept.values(), key=lambda x: x[0]):
        toks = st.batch(step_i)
        for b in range(toks.shape[0]):
            got = logits[b:b + 1].astype(jnp.float32)
            if not bool(jnp.all(jnp.isfinite(got))):
                out["bad"] += 1
                worst("logits_rel_err", float("inf"))
                worst("logits_kl", float("inf"))
                continue
            ref = st.ref.forward(st.z, st.params, toks[b:b + 1])
            norm = jnp.linalg.norm(ref)
            worst("logits_rel_err", float(jnp.linalg.norm(got - ref) / norm))
            worst("logits_kl", _kl(ref, got))
            if quant:
                low = st.ref.forward(st.z, st.params, toks[b:b + 1], quant)
                worst("control_rel_err", float(jnp.linalg.norm(low - ref) / norm))
                worst("control_kl", _kl(ref, low))
    return out


def _median_step_s(r, st: State, fwd, first: int, count: int = 3) -> float:
    import jax

    times: List[float] = []
    for i in range(count):
        toks = st.batch(first + i)
        jax.block_until_ready(toks)
        t0 = time.perf_counter()
        jax.block_until_ready(fwd(st.params, toks))
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


def run(r) -> None:
    import jax

    wl, mix = r.workload, r.traffic
    st = setup(r)
    z, B, S = st.z, int(mix["batch"]), int(mix["seq"])
    r.obs["step_flops"] = st.ref.forward_flops(z, B, S)
    if r.trace:
        # tuned against XLA alone, on the same batches, before the window
        xla = jax.jit(lambda p, t: st.model.forward(p, tokens=t))
        with r.spans("compile_xla"):
            jax.block_until_ready(xla(st.params, st.batch(0)))
        with st.ctx:
            tuned_s = _median_step_s(r, st, st.fwd, 1)
        r.obs["over_xla"] = tuned_s / _median_step_s(r, st, xla, 1)
        del xla

    t0 = time.perf_counter()
    r.obs["setup_s"] = t0 - r.t_start
    n, kept = steps(r, st, 0, seconds=r.seconds,
                    keep=int(wl["check"]["sample_steps"]), seed=r.seed)
    r.obs["window_s"] = time.perf_counter() - t0
    r.obs["steps"] = n
    r.obs["tokens"] = n * B * S
    r.attempted = n
    if r.trace:
        prof = harness.Profiler()
        with prof:
            steps(r, st, n, count=int(wl["trace_steps"]))
        r.obs["trace"] = prof.reduce()
    r.obs["memory_peak_bytes"] = harness.memory_peak()

    st.ctx = st.fwd = None
    harness.free_device()
    got = compare(r, st, kept)
    r.failed = got["bad"]
    for name in ("logits_rel_err", "logits_kl"):
        r.check(name, got[name], wl["limits"][name])
