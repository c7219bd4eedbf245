"""Serve job: open-loop arrivals into the paged continuous-batching
scheduler with tuned decode and prefill dispatch.

Set-up makes the weights, extracts the serving program's tasks (decode
ticks and in-tick prefill chunks at the cell's slot count), tunes the
heaviest, builds the scheduler under the tuned context and compiles both
tick programs by serving one request through them.  The window submits
each request when it falls due (the mix's Poisson schedule at the cell's
fixed rate) and ticks the scheduler whenever work is pending; token times
are read after every tick.  Latency counts from when a request was due,
so a stall of the loop shows in every request it delays.

Correct: after the window, a seeded sample of the greedy requests that
finished in it, the longest among them, is run through the plain float32
reference over prompt and served tokens; the number compared is the
widest gap by which a served token's reference logit lies below the
reference's best at that position.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import harness
import traffic
import work


@dataclass
class State:
    cfg: Any
    ref: Any
    z: Any
    params: Any
    ctx: Any
    sched: Any


def setup(r) -> State:
    import jax

    from repro.integration.dispatch import DispatchContext
    from repro.integration.extract import extract_decode_task_specs
    from repro.models.registry import build_model
    from repro.serving import ContinuousBatchingScheduler, ServeConfig

    cfg = harness.program_config(r.conf)
    model = build_model(cfg)
    ref = harness.reference(r.conf)
    z = ref.sizes(r.conf)
    sv, sp = r.workload["serving"], r.spans
    with sp("weights"):
        params = harness.make_params(r.conf, r.seed, model)
    with sp("extract"):
        specs = extract_decode_task_specs(
            cfg, batch=sv["max_slots"], max_seq=sv["max_seq"],
            dispatchable_only=True, chunk=sv["prefill_chunk"], paged=True,
            page_size=sv["page_size"], mesh=None,
        )
    db, r.obs["tune"] = harness.tune(
        cfg, specs, r.workload["tuning"], r.backend, sp
    )
    ctx = DispatchContext(
        db, tasks=[s.to_tune_task(use_mxu=True) for s in specs],
        mode="best", backend=r.backend,
    )
    sched = ContinuousBatchingScheduler(
        cfg, params,
        config=ServeConfig(
            max_slots=sv["max_slots"], max_seq=sv["max_seq"], paged=True,
            page_size=sv["page_size"], prefill_chunk=sv["prefill_chunk"],
            token_budget=sv["token_budget"],
            temperature=float(r.traffic["temperature"]),
            seed=r.seed % (1 << 64),
            dispatch=ctx,
        ),
    )
    with sp("compile"):
        # one request through a chunk tick and a decode tick compiles both
        warm = sched.submit(
            [1] * (sv["prefill_chunk"] + 1), max_new_tokens=3, temperature=0.0
        )
        sched.run()
        if not warm.done:
            raise RuntimeError("the warm-up request did not finish")
        # the arena updates its page table and scrubs pages with eager ops
        # shaped by a request's page count: run each count once here so
        # that none compiles inside the window
        arena = sched.arena
        for pages in range(1, arena.pages_per_slot + 1):
            arena.reserve(0, pages * arena.page_size)
            arena.release_slot(0)
        jax.block_until_ready(sched.arena.cache)
    r.obs["dispatch"] = harness.dispatch_counts(ctx)
    return State(cfg, ref, z, params, ctx, sched)


def reset(st: State, params, seed: int) -> None:
    """Serve the next window with new weights and a fresh sampler."""
    import numpy as np

    st.params = st.sched.params = params
    st.sched.rng = np.random.default_rng(seed % (1 << 64))


def window(r, st: State, arrivals, seconds: float, account: bool) -> Dict:
    """Serve ``arrivals`` (due times relative to now) for ``seconds``."""
    sched, sp, z = st.sched, r.spans, st.z
    recs: List[Dict] = []
    live: List[Dict] = []
    ideal = 0.0
    ticks, i, n = 0, 0, len(arrivals)
    t0 = time.perf_counter()
    end = t0 + seconds
    while True:
        now = time.perf_counter()
        if now >= end:
            break
        if i < n and arrivals[i].due_s <= now - t0:
            with sp("admit"):
                while i < n and arrivals[i].due_s <= now - t0:
                    a = arrivals[i]
                    req = sched.submit(
                        a.prompt, max_new_tokens=a.max_new,
                        temperature=a.temperature,
                    )
                    rec = {"due": t0 + a.due_s, "req": req, "first": None,
                           "last": None, "seen": 0}
                    recs.append(rec)
                    live.append(rec)
                    i += 1
        if not sched.pending():
            nxt = t0 + arrivals[i].due_s if i < n else end
            with sp("wait"):
                time.sleep(max(0.0, min(nxt, end) - time.perf_counter()))
            continue
        if account:
            # prompts this tick may advance: those prefilling and those
            # the tick admits first
            chunks = list(sched.prefilling.values()) + list(sched.queue)[
                : sched.n_slots]
            pre = {id(q): q.prefill_done for q in chunks}
            lanes = [(1, len(q.prompt) + len(q.generated))
                     for q in sched.active.values()]
        with sp("tick"):
            sched.step()
        ticks += 1
        t = time.perf_counter()
        with sp("record"):
            still = []
            for rec in live:
                q = rec["req"]
                got = len(q.generated)
                if got > rec["seen"]:
                    if rec["first"] is None:
                        rec["first"] = t
                    rec["last"], rec["seen"] = t, got
                if not q.done:
                    still.append(rec)
            live = still
            if account:
                for q in chunks:
                    done = q.prefill_done
                    if done > pre[id(q)]:
                        lanes.append((done - pre[id(q)], done))
                flops, nbytes = st.ref.serve_tick(z, lanes)
                ideal += work.ideal_s(flops, nbytes, r.peak)
    t_end = time.perf_counter()
    # due before the close but never submitted (a tick overran the close)
    late = [t0 + a.due_s for a in arrivals[i:] if t0 + a.due_s < t_end]
    return {"t0": t0, "t_end": t_end, "recs": recs, "late": late, "ticks": ticks,
            "ideal_s": ideal}


def latencies(w: Dict) -> List[Dict]:
    """Per request due in the window: time to first token, time per
    output token and queueing, each from when it was due; a request still
    waiting at the window's end counts with its wait so far."""
    out = []
    t_end = w["t_end"]
    for rec in w["recs"]:
        q = rec["req"]
        first = rec["first"] if rec["first"] is not None else t_end
        admit = q.admit_s if q.admit_s is not None and q.admit_s <= t_end else t_end
        tpot = None
        if rec["seen"] >= 2:
            tpot = (rec["last"] - rec["first"]) / (rec["seen"] - 1)
        out.append({"ttft_s": first - rec["due"], "tpot_s": tpot,
                    "queue_s": admit - rec["due"]})
    for due in w["late"]:
        out.append({"ttft_s": t_end - due, "tpot_s": None,
                    "queue_s": t_end - due})
    return out


def served_gaps(r, st: State, recs: List[Dict], seed: int,
                quant: Optional[str] = None) -> Dict[str, float]:
    """Widest reference-logit gap of the served greedy tokens over a seeded
    sample of finished greedy requests (the longest always in it); with
    ``quant`` also the gap of the tokens that reference computed at that
    precision would put first, at the same positions."""
    import jax.numpy as jnp
    import numpy as np

    done = [rec["req"] for rec in recs
            if rec["req"].done and rec["req"].temperature == 0.0]
    if not done:
        return {"served_gap": float("inf"), "tokens": 0}
    done.sort(key=lambda q: -len(q.generated))
    pick = np.random.default_rng([seed % (1 << 64), 9])
    order = [done[0]] + [done[1:][j] for j in pick.permutation(len(done) - 1)]
    want = int(r.workload["check"]["sample_tokens"])
    worst, worst_q, count = 0.0, 0.0, 0
    for q in order:
        if count >= want:
            break
        gen = np.asarray(q.generated, np.int32)
        seq = np.concatenate([np.asarray(q.prompt, np.int32), gen])[None, :-1]
        rows = slice(len(q.prompt) - 1, seq.shape[1])
        ref = st.ref.forward(st.z, st.params, seq)[0, rows]
        best = jnp.max(ref, axis=-1)
        gap = best - jnp.take_along_axis(ref, jnp.asarray(gen)[:, None], 1)[:, 0]
        worst = max(worst, float(jnp.max(gap)))
        if quant:
            low = st.ref.forward(st.z, st.params, seq, quant)
            top = jnp.argmax(low[0, rows], axis=-1)
            gq = best - jnp.take_along_axis(ref, top[:, None], 1)[:, 0]
            worst_q = max(worst_q, float(jnp.max(gq)))
        count += len(gen)
    out = {"served_gap": worst, "tokens": count}
    if quant:
        out["control_gap"] = worst_q
    return out


def run(r) -> None:
    wl = r.workload
    st = setup(r)
    vocab = st.z.V
    arrivals = traffic.open_loop(r.traffic, wl["rate"], r.seconds, vocab, r.seed)
    r.obs["setup_s"] = time.perf_counter() - r.t_start
    w = window(r, st, arrivals, r.seconds, account=r.trace)
    r.obs["window_s"] = w["t_end"] - w["t0"]
    r.obs["ticks"] = w["ticks"]
    r.obs["requests"] = latencies(w)
    if r.trace:
        r.obs["tick_ideal_s"] = w["ideal_s"]
        more = traffic.open_loop(
            r.traffic, wl["rate"], wl["trace_seconds"], vocab, r.seed + 1
        )
        prof = harness.Profiler()
        with prof:
            window(r, st, more, wl["trace_seconds"], account=False)
        r.obs["trace"] = prof.reduce()
    r.attempted = len(r.obs["requests"])
    r.obs["memory_peak_bytes"] = harness.memory_peak()
    recs = w["recs"]
    for rec in recs:
        rec["req"]._pump = None  # the scheduler and its KV arena go now
    st.sched = st.ctx = None
    harness.free_device()
    gaps = served_gaps(r, st, recs, r.seed)
    r.obs["checked_tokens"] = gaps["tokens"]
    r.check("served_gap", gaps["served_gap"], wl["limits"]["served_gap"])
