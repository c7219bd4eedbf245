#!/usr/bin/env python3
"""What the program's own tracing shows in a traced run, reduced here.

    python3 benchmarks/chip/program_trace.py --workload <cell> --seed <n> \
        [--steps 4] [--out chiprun_out/program_trace.jsonl]

Runs a prefill cell's set-up with the program's tracer installed
(``repro.obs`` with an in-memory ``RingBufferSink``, so ``tune.session``
and every measurement inside it are captured), then a few forwards under
the JAX profiler, and prints one JSON line: the five numbers of
:data:`METRICS`, the benchmark's own ``search.tune_s``, each Pallas kernel
of the traced window with the identity it was compiled with and its
tuned record's latency, the window's idle gaps named by the spans over
them, and the count and summed duration of each of the program's events
inside the tuning session (:func:`span_totals`).  It is a reading tool,
not part of a benchmark run; the functions below are what a cell's
metric readers would call once the harness keeps the program's events,
the kernel identities and the records' latencies among a traced run's
observations (``search_split``, ``kernels``, ``tuned_latency_s``).

* :func:`search_split`: from the program's raw events, inside the
  ``tune.session`` span(s), the sum of ``measure.build`` durations
  (validate and lower), of ``measure.run``'s ``compile_s`` (each
  candidate's first call: trace, compile or cache load) and ``timing_s``
  (warm-up and timed repeats), and the session less the three (``self_s``:
  sampling, evolution, cost model, each task's inputs).  Summed here, not
  by ``repro.obs.report.fold``, so that a program change cannot move them.
* :func:`kernels`: the Pallas ops of a profiler trace's window grouped by
  the template name and ``kernel_metadata`` (task, blocks, dtype) the
  program compiled into them, with their calls and device time.
* :func:`timing_error`: over the tuned kernels, weighted by device time,
  how far each record's latency (host clock, in the search) lies from the
  kernel's device time per call in the served program.
* :func:`gaps`: the window's idle gaps, each named as ``reduce_trace``
  names them (the span that overlaps it most, then the innermost) but
  among the spans whose names start with ``bench.`` or ``repro.``.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

HERE = Path(__file__).resolve().parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import harness  # noqa: E402

harness.setup_paths()

from trace_reduce import (  # noqa: E402
    DEVICE_PREFIX,
    DONE_EVENT,
    MIN_GAP_NS,
    MODULES_LINE,
    OPS_LINE,
    TOP,
    WINDOW_SPAN,
    _run_id,
    _span_for,
    _union,
    clock_shift_ns,
    find_xplane,
    parse_op,
)
from work import kernel_work  # noqa: E402

METRICS = (
    "search.build_s",
    "search.compile_s",
    "search.timing_s",
    "search.self_s",
    "search.timing_error.prefill",
)
SPAN_PREFIXES = ("bench.", "repro.")
META_ATTR = "kernel_metadata="
RING_EVENTS = 1 << 20


# ---------------------------------------------------------------------------
# The program's events
# ---------------------------------------------------------------------------


def _in_sessions(events: List[Dict]) -> Optional[Tuple[float, List[Dict]]]:
    """(summed length of the ``tune.session`` spans, the events emitted
    inside them), or None when no session was recorded."""
    wins = [
        (float(e["ts"]) - float(e["dur_s"]), float(e["ts"]))
        for e in events if e.get("ev") == "tune.session" and "dur_s" in e
    ]
    if not wins:
        return None
    inside = [e for e in events if e.get("ev") != "tune.session"
              and any(a <= float(e["ts"]) <= b for a, b in wins)]
    return sum(b - a for a, b in wins), inside


def search_split(events: List[Dict]) -> Optional[Dict[str, float]]:
    """Build, compile, timing and self seconds of the tuning session(s) in
    ``events``; None when no ``tune.session`` span was recorded."""
    got = _in_sessions(events)
    if got is None:
        return None
    session, inside = got
    build = sum(float(e["dur_s"]) for e in inside if e["ev"] == "measure.build")
    runs = [e for e in inside if e["ev"] == "measure.run"]
    compile_s = sum(float(e.get("compile_s", 0.0)) for e in runs)
    timing_s = sum(float(e.get("timing_s", 0.0)) for e in runs)
    return {
        "session_s": session,
        "build_s": build,
        "compile_s": compile_s,
        "timing_s": timing_s,
        "self_s": session - build - compile_s - timing_s,
    }


def span_totals(events: List[Dict]) -> Dict[str, Dict[str, float]]:
    """For each event type inside the tuning session(s), its count and the
    sum of each numeric field (``dur_s``, ``tries``, ...): where the
    session's self time goes."""
    got = _in_sessions(events)
    out: Dict[str, Dict[str, float]] = {}
    for e in got[1] if got else []:
        row = out.setdefault(e["ev"], {"count": 0})
        row["count"] += 1
        for k, v in e.items():
            if k not in ("ts", "pid", "span", "parent") and isinstance(
                    v, (int, float)) and not isinstance(v, bool):
                row[k] = row.get(k, 0) + v
    return out


# ---------------------------------------------------------------------------
# The profiler's trace
# ---------------------------------------------------------------------------


def identity(text: str) -> Tuple[str, Dict[str, str]]:
    """(template, kernel metadata) of a Pallas op as the trace prints it:
    the op is named ``<template>.<n>``, and its ``kernel_metadata``
    frontend attribute is a JSON object ({} where none was given)."""
    name = text.partition(" = ")[0].lstrip("%")
    template = name.rsplit(".", 1)[0]
    at = text.find(META_ATTR)
    if at < 0:
        return template, {}
    try:
        meta, _ = json.JSONDecoder().raw_decode(text, at + len(META_ATTR))
    except ValueError:
        return template, {}
    return template, meta


def _label(op: Dict) -> str:
    """The breakdown's label of a Pallas op (kind and shapes), as
    ``trace_reduce.reduce_trace`` writes it."""
    w = kernel_work(op["result"], op["operands"])
    kind = w[0] if w else "unknown"
    dims = op["operands"][:2] if kind in ("dense", "batch_matmul") else [
        op["result"]]
    return f"pallas {kind} " + " ".join("x".join(map(str, d)) for d in dims)


def read_trace(path: str):
    """(spans, device ops per chip on the host clock, window) of the
    ``.xplane.pb`` at or under ``path``: the planes ``reduce_trace``
    reads, with host spans of both prefixes."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(path))
    spans: List[Tuple[str, float, float]] = []
    raw: Dict[str, List[Tuple[float, float, str]]] = {}
    module_ends: Dict[str, float] = {}
    done: Dict[str, float] = {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = raw.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    for e in line.events:
                        rid = _run_id(e)
                        if rid is not None:
                            module_ends[rid] = e.start_ns + e.duration_ns
                elif line.name == OPS_LINE:
                    evs.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
                    elif e.name == DONE_EVENT:
                        rid = _run_id(e)
                        if rid is not None:
                            done[rid] = min(done.get(rid, e.start_ns), e.start_ns)
    if not any(raw.values()):
        raise ValueError("the trace holds no device op")
    shift = clock_shift_ns(module_ends, done)
    chips = {k: [(a + shift, b + shift, t) for a, b, t in evs]
             for k, evs in raw.items()}
    win = [(a, b) for n, a, b in spans if n == WINDOW_SPAN]
    if win:
        window = win[0]
    else:
        window = (min(a for evs in chips.values() for a, _, _ in evs),
                  max(b for evs in chips.values() for _, b, _ in evs))
    return spans, chips, window


def kernels(path: str) -> List[Dict[str, Any]]:
    """The window's Pallas ops grouped by breakdown label, template, task,
    blocks and dtype, with calls and device time, longest first."""
    _, chips, (w0, w1) = read_trace(path)
    rows: Dict[Tuple, Dict[str, Any]] = {}
    parsed: Dict[str, Optional[Tuple]] = {}
    for evs in chips.values():
        for a, b, text in evs:
            a, b = max(a, w0), min(b, w1)
            if b <= a:
                continue
            if text not in parsed:
                op = parse_op(text)
                parsed[text] = (
                    (_label(op), *identity(text)) if op["pallas"] else None
                )
            hit = parsed[text]
            if hit is None:
                continue
            label, template, meta = hit
            key = (label, template, meta.get("task", ""),
                   meta.get("blocks", ""), meta.get("dtype", ""))
            row = rows.setdefault(key, dict(zip(
                ("label", "template", "task", "blocks", "dtype"), key),
                count=0, device_s=0.0))
            row["count"] += 1
            row["device_s"] += (b - a) * 1e-9
    return sorted(rows.values(), key=lambda r: -r["device_s"])


def timing_error(rows: List[Dict], latency_s: Dict[str, float]) -> Optional[float]:
    """Over the kernels whose task has a tuned record, weighted by device
    time: |record latency - device time per call| / device time per call,
    in percent; None when no such kernel ran."""
    tuned = [r for r in rows if r["task"] in latency_s and r["count"]]
    weight = sum(r["device_s"] for r in tuned)
    if weight <= 0:
        return None
    err = 0.0
    for r in tuned:
        per_call = r["device_s"] / r["count"]
        err += r["device_s"] * abs(latency_s[r["task"]] - per_call) / per_call
    return 100.0 * err / weight


def gaps(path: str) -> List[List]:
    """The ten longest idle gaps of the window, each named by the
    ``bench.*`` or ``repro.*`` span that overlaps it most, then the
    innermost, other than the window (``host:untraced`` where none does)."""
    spans, chips, (w0, w1) = read_trace(path)
    found = []
    for evs in chips.values():
        busy = _union([(max(a, w0), min(b, w1)) for a, b, _ in evs
                       if min(b, w1) > max(a, w0)])
        prev = w0
        for a, b in busy + [(w1, w1)]:
            if a - prev >= MIN_GAP_NS:
                found.append((prev, a))
            prev = max(prev, b)
    found.sort(key=lambda g: g[1] - g[0], reverse=True)
    return [[_span_for(g, spans), (g[1] - g[0]) * 1e-9] for g in found[:TOP]]


# ---------------------------------------------------------------------------
# Readers: what each metric reads from a run's observations
# ---------------------------------------------------------------------------


def read(obs: Dict, metric: str) -> Optional[float]:
    """The value of one of :data:`METRICS`; None where the run holds
    nothing to read (no program events, or no kernel of a tuned task)."""
    if metric == "search.timing_error.prefill":
        if obs.get("job") != "prefill" or not obs.get("kernels"):
            return None
        return timing_error(obs["kernels"], obs.get("tuned_latency_s") or {})
    split = obs.get("search_split")
    if not split:
        return None
    return split[metric[len("search."):]]


# ---------------------------------------------------------------------------
# The reading tool
# ---------------------------------------------------------------------------


def measure(r, steps: int, program_trace: bool = True) -> Dict[str, Any]:
    """Set up the prefill cell of ``r`` (a ``run.Run``) with the program's
    tracer on (or off), run ``steps`` profiled forwards, and return what
    this module reads."""
    from repro.obs import RingBufferSink, configure_tracing, disable_tracing

    obs = r.obs
    obs.update(job=r.workload["job"], peak=r.peak)
    if obs["job"] != "prefill":
        raise harness.NotRunnable(f"{r.name}: only prefill cells are read")
    job = harness.load_module(HERE / "jobs" / "prefill.py")
    ring = RingBufferSink(capacity=RING_EVENTS)
    if program_trace:
        configure_tracing(sink=ring)
    try:
        st = job.setup(r)
    finally:
        disable_tracing()
    obs["search_split"] = search_split(ring.events)
    best = {k: st.ctx.db.best(k) for k in obs["tune"]["tasks"]}
    obs["tuned_latency_s"] = {
        k: rec.latency_s for k, rec in best.items() if rec is not None
    }
    out: Dict[str, Any] = {
        "workload": r.name, "seed": r.seed, "program_trace": program_trace,
        "events": len(ring.events), "search.tune_s": obs["tune"]["tune_s"],
        "tune": obs["tune"], "search_split": obs["search_split"],
        "span_totals": span_totals(ring.events),
        "tuned_latency_s": obs["tuned_latency_s"],
    }
    if steps:
        prof = harness.Profiler()
        try:
            with prof:
                job.steps(r, st, 0, count=steps)
            obs["kernels"] = out["kernels"] = kernels(prof.dir)
            out["gaps_top"] = gaps(prof.dir)
        finally:
            shutil.rmtree(prof.dir, ignore_errors=True)
        out["untasked_s"] = sum(k["device_s"] for k in obs["kernels"]
                                if not k["task"])
    out["metrics"] = {m: read(obs, m) for m in METRICS}
    return out


def main(argv=None) -> int:
    import run as bench_run

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    files = harness.cell_files(harness.benchmark(), args.workload)
    try:
        _, peak = harness.device_check(int(files["spec"]["chips"]))
        harness.compile_cache()
        r = bench_run.Run(
            name=args.workload, workload=files["workload"],
            conf=files["conf"], traffic=files["traffic"], seed=args.seed,
            seconds=0.0, trace=True, peak=peak,
        )
        got = measure(r, args.steps)
    except harness.NotRunnable as e:
        print(f"not runnable: {e}", file=sys.stderr)
        return 2
    line = json.dumps(got)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "a") as fh:
            fh.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
