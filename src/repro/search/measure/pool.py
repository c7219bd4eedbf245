"""Process-pool measurement: parallel build+time with fault isolation.

Each worker process takes a candidate (the pre-validated schedule when
it ships, else a trace replay), lowers it through the lowering backend
named in the payload (jnp, pallas, ... — see
:mod:`repro.backends.registry`), jits, and times it — build and run are
fused inside the worker because compiled artifacts cannot cross a
process boundary.
The parent enforces:

* **wall-clock timeouts** — a batch gets ``timeout_s`` per candidate
  (scaled by pool width); candidates still pending at the deadline are
  rejected with ``inf`` and the pool is torn down so hung workers cannot
  leak into the next round;
* **failure quarantine** — when a worker process dies (OOM, segfault in
  the toolchain, ...) the batch's unfinished candidates are retried one
  at a time in a fresh pool to attribute the crash; a trace whose
  structural hash crashes ``crash_threshold`` times is blacklisted and
  never submitted again;
* **deterministic ordering** — results always align with the input list,
  regardless of which worker finished first.

Workers are spawned (not forked): the parent has a live JAX runtime and
forking it is unsound.  On a TPU host each worker is pinned to a chip of
its own (:func:`repro.launch.runtime.chip_child_envs`): the pool
defaults to one worker per chip and refuses more, or a parent that
already holds the chips.  Worker startup (~seconds for the JAX import) is
amortized by keeping the pool alive across ``run()`` batches; ``warm()``
pre-spawns workers so the import overlaps the parent's own search work.
"""

from __future__ import annotations

import concurrent.futures as cf
import math
import multiprocessing as mp
import os
import sys
import time
from typing import Callable, Dict, List, Optional, Set, Tuple

from ...obs import emit, trace_enabled
from .hashing import structural_hash
from .protocol import MeasureInput, MeasureResult, Runner


_WORKER_INPUT_CACHE: dict = {}  # per worker process: func signature -> device arrays


def _measure_worker(payload: dict) -> dict:
    """Runs inside a worker process: replay -> build -> jit -> time.

    Takes/returns plain dicts so stub workers in tests can swap in
    without touching the pool logic.
    """
    t_start = time.perf_counter()
    try:
        import jax

        from ...backends.registry import get_backend
        from ...core.tir import random_inputs
        from ...core.trace import Trace
        from ...core.validator import validate_trace
        from .local import time_artifact

        func = payload["func"]
        sch = payload.get("schedule")
        if sch is None:
            # no pre-validated schedule shipped: replay the trace here
            trace = Trace.from_json(payload["trace_json"])
            v = validate_trace(func, trace)
            if not v.ok:
                return {
                    "latency_s": float("inf"),
                    "error": f"invalid trace: {v.reason}",
                    "build_time_s": 0.0,
                    "run_time_s": 0.0,
                }
            sch = v.schedule
        be = get_backend(payload.get("backend", "jnp"))
        lowered = be.lower(sch, workload_key=payload.get("workload_key", ""))
        fn = jax.jit(lowered.fn)
        ins_key = func.name + str(tuple(b.shape for b in func.inputs))
        ins = _WORKER_INPUT_CACHE.get(ins_key)
        if ins is None:
            ins = {
                k: jax.device_put(x) for k, x in random_inputs(func, 0).items()
            }
            _WORKER_INPUT_CACHE[ins_key] = ins
        build_s = time.perf_counter() - t_start
        # the one shared timing loop (first-call timeout, warmup, median)
        t_run = time.perf_counter()
        res = time_artifact(
            fn, ins, payload["repeats"], payload["warmup"], payload["timeout_s"]
        )
        # full run-stage wall (incl. first call + warmup): the parent's
        # measure.run events and the report's time breakdown consume it
        meta = dict(lowered.meta)
        meta["run_wall_s"] = round(time.perf_counter() - t_run, 6)
        return {
            "latency_s": res.latency_s,
            "error": res.error,
            "build_time_s": build_s,
            "run_time_s": res.run_time_s,
            "compile_s": res.compile_s,
            "timing_s": res.timing_s,
            "meta": meta,
        }
    except Exception as e:
        return {
            "latency_s": float("inf"),
            "error": f"{type(e).__name__}: {e}",
            "build_time_s": time.perf_counter() - t_start,
            "run_time_s": 0.0,
        }


def _pin_worker(envs) -> None:
    """Pool initializer: take one chip's environment before JAX starts."""
    os.environ.update(envs.get())


def _warm_worker(_: int) -> bool:
    """Pre-import the heavy deps so the first real batch finds workers hot."""
    import jax  # noqa: F401

    from ...backends import jnp_backend, registry  # noqa: F401

    return True


class ProcessPoolRunner(Runner):
    """Builds and times candidates across a pool of worker processes."""

    name = "pool"

    def __init__(
        self,
        max_workers: Optional[int] = None,
        timeout_s: float = 30.0,
        repeats: int = 3,
        warmup: int = 1,
        crash_threshold: int = 2,
        grace_s: float = 10.0,
        startup_grace_s: float = 60.0,
        worker_fn: Optional[Callable[[dict], dict]] = None,
        start_method: str = "spawn",
        backend: Optional[str] = None,
    ):
        from ...backends.registry import check_backend_spec
        from ...launch.runtime import host_tpu_chips

        # validate eagerly: a typo'd spec must raise here, not burn the
        # whole tuning budget as per-candidate "failures" inside workers.
        # Only the name: building the backend would claim the device the
        # workers need.
        self.backend = check_backend_spec(backend)
        self.max_workers = max_workers or len(host_tpu_chips()) or min(
            max(os.cpu_count() or 2, 2), 8
        )
        self.timeout_s = timeout_s
        self.repeats = repeats
        self.warmup = warmup
        self.crash_threshold = crash_threshold
        self.grace_s = grace_s
        self.startup_grace_s = startup_grace_s
        self.worker_fn = worker_fn or _measure_worker
        self.start_method = start_method
        self._executor: Optional[cf.ProcessPoolExecutor] = None
        self._cold = True  # fresh pool: charge startup to the first batch
        self.crash_counts: Dict[str, int] = {}
        self.quarantined: Set[str] = set()
        self.n_measured = 0
        self.n_timeouts = 0
        self.n_crashes = 0
        self.n_quarantine_rejects = 0

    # -- pool lifecycle -----------------------------------------------------

    @staticmethod
    def _fix_unspawnable_main() -> None:
        """REPL/stdin parents carry ``__main__.__file__ == '<stdin>'`` (or
        another nonexistent path); spawn's preparation step would then try
        to re-run that file in every worker and kill the whole pool.
        Dropping the bogus attribute makes spawn skip main re-execution —
        our workers only need importable modules, never ``__main__``."""
        main = sys.modules.get("__main__")
        mf = getattr(main, "__file__", None)
        if mf and not os.path.exists(mf):
            try:
                del main.__file__
            except AttributeError:
                pass

    def _executor_or_new(self) -> cf.ProcessPoolExecutor:
        if self._executor is None:
            from ...launch.runtime import chip_child_envs

            envs = chip_child_envs(self.max_workers)
            self._fix_unspawnable_main()
            ctx = mp.get_context(self.start_method)
            init = {}
            if any(envs):
                q = ctx.Queue()
                for e in envs:
                    q.put(e)
                init = {"initializer": _pin_worker, "initargs": (q,)}
            self._executor = cf.ProcessPoolExecutor(
                max_workers=self.max_workers, mp_context=ctx, **init
            )
            self._cold = True
        return self._executor

    def _kill_pool(self) -> None:
        """Tear down the pool, terminating workers that may be hung."""
        ex, self._executor = self._executor, None
        if ex is None:
            return
        for p in list(getattr(ex, "_processes", {}).values()):
            try:
                p.terminate()
            except Exception:
                pass
        ex.shutdown(wait=False, cancel_futures=True)

    def warm(self, wait: bool = False) -> None:
        """Pre-spawn workers and pre-import their deps.  Async by default
        (overlaps with the caller's own work); ``wait=True`` blocks until
        every worker is hot and stops charging startup to the next batch."""
        ex = self._executor_or_new()
        futs = [ex.submit(_warm_worker, i) for i in range(self.max_workers)]
        if wait:
            for f in futs:
                f.result(timeout=self.startup_grace_s)
            self._cold = False

    def close(self) -> None:
        self._kill_pool()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    # -- measurement --------------------------------------------------------

    def _payload(self, mi: MeasureInput) -> dict:
        payload = {
            "workload_key": mi.workload_key,
            "func": mi.func,
            "trace_json": mi.trace.to_json(),
            "timeout_s": self.timeout_s,
            "repeats": self.repeats,
            "warmup": self.warmup,
            "backend": self.backend,
        }
        if mi.schedule is not None:
            # ship the pre-validated schedule (it pickles at ~KBs) so the
            # worker skips the replay+validation the parent already did
            payload["schedule"] = mi.schedule
        return payload

    def run(self, inputs: List[MeasureInput]) -> List[MeasureResult]:
        results: List[Optional[MeasureResult]] = [None] * len(inputs)
        live: List[Tuple[int, str, dict]] = []
        for i, mi in enumerate(inputs):
            h = structural_hash(mi.workload_key, mi.trace)
            if h in self.quarantined:
                self.n_quarantine_rejects += 1
                if trace_enabled():
                    emit(
                        "measure.quarantine_reject",
                        key=mi.workload_key,
                        hash=h,
                        backend=self.backend,
                    )
                results[i] = MeasureResult(
                    float("inf"),
                    "quarantined after repeated worker crashes",
                    source="quarantine",
                )
            else:
                live.append((i, h, self._payload(mi)))
        if live:
            self._run_live(live, results)
        return results  # type: ignore[return-value]

    def _emit_result(self, h: str, payload: dict, out: dict) -> None:
        """Parent-side telemetry for one completed worker measurement
        (build and run happened fused inside the worker)."""
        key = payload.get("workload_key", "")
        meta = out.get("meta") or {}
        ok = not out.get("error")
        build_s = float(out.get("build_time_s", 0.0))
        run_wall = float(meta.get("run_wall_s", out.get("run_time_s", 0.0)))
        if trace_enabled():
            emit(
                "measure.build",
                key=key,
                hash=h,
                ok=ok,
                dur_s=build_s,
                backend=self.backend,
            )
            emit(
                "measure.run",
                key=key,
                hash=h,
                ok=ok,
                latency_s=out["latency_s"] if ok else None,
                compile_s=float(out.get("compile_s", 0.0)),
                timing_s=float(out.get("timing_s", 0.0)),
                dur_s=run_wall,
                backend=self.backend,
                **({"error": out["error"]} if out.get("error") else {}),
            )

    def _emit_timeout(self, h: str, key: str, note: str) -> None:
        if trace_enabled():
            emit(
                "measure.timeout",
                key=key,
                hash=h,
                timeout_s=self.timeout_s,
                note=note,
                backend=self.backend,
            )

    def _run_live(
        self,
        live: List[Tuple[int, str, dict]],
        results: List[Optional[MeasureResult]],
    ) -> None:
        ex = self._executor_or_new()
        futs = {}
        for i, h, payload in live:
            futs[ex.submit(self.worker_fn, payload)] = (i, h, payload)
        waves = math.ceil(len(live) / self.max_workers)
        budget = self.timeout_s * waves + self.grace_s
        if self._cold:
            budget += self.startup_grace_s
        pending = set(futs)
        crashed: List[Tuple[int, str, dict]] = []
        broken = False
        try:
            for fut in cf.as_completed(list(futs), timeout=budget):
                pending.discard(fut)
                self._cold = False  # a worker has answered: pool is hot
                i, h, payload = futs[fut]
                try:
                    out = fut.result()
                    results[i] = MeasureResult(**out)
                    self.n_measured += 1
                    self._emit_result(h, payload, out)
                except Exception:
                    # worker process died; every pending future is now dead
                    # too — retry each in isolation to attribute the crash
                    broken = True
                    crashed.append((i, h, payload))
                    break
        except cf.TimeoutError:
            self.n_timeouts += len(pending)
            for fut in pending:
                i, h, payload = futs[fut]
                self._emit_timeout(
                    h, payload.get("workload_key", ""), "batch budget"
                )
                results[i] = MeasureResult(
                    float("inf"),
                    f"timeout (exceeded {self.timeout_s:.1f}s/candidate batch budget)",
                    source="timeout",
                )
            self._kill_pool()
            return
        if broken:
            crashed.extend(futs[f] for f in pending)
            crashed.sort(key=lambda t: t[0])
            self._kill_pool()
            for i, h, payload in crashed:
                results[i] = self._run_isolated(h, payload)

    def _run_isolated(self, h: str, payload: dict) -> MeasureResult:
        """Re-run one candidate alone in a fresh pool: a crash here is
        definitively attributable to this trace."""
        ex = self._executor_or_new()
        fut = ex.submit(self.worker_fn, payload)
        deadline = self.timeout_s + self.grace_s
        if self._cold:
            deadline += self.startup_grace_s
        try:
            out = fut.result(timeout=deadline)
            self.n_measured += 1
            self._cold = False
            self._emit_result(h, payload, out)
            return MeasureResult(**out)
        except cf.TimeoutError:
            self.n_timeouts += 1
            self._kill_pool()
            self._emit_timeout(
                h, payload.get("workload_key", ""), "isolated retry"
            )
            return MeasureResult(
                float("inf"),
                f"timeout (exceeded {self.timeout_s:.1f}s, isolated retry)",
                source="timeout",
            )
        except Exception as e:
            self.n_crashes += 1
            self._kill_pool()
            n = self.crash_counts.get(h, 0) + 1
            self.crash_counts[h] = n
            key = payload.get("workload_key", "")
            if trace_enabled():
                emit(
                    "measure.crash",
                    key=key,
                    hash=h,
                    crash=n,
                    threshold=self.crash_threshold,
                    error=type(e).__name__,
                    backend=self.backend,
                )
            msg = f"worker crashed ({type(e).__name__}), crash {n}/{self.crash_threshold}"
            if n >= self.crash_threshold:
                self.quarantined.add(h)
                if trace_enabled():
                    emit(
                        "measure.crash_quarantine",
                        key=key,
                        hash=h,
                        crashes=n,
                        backend=self.backend,
                    )
                msg += "; trace quarantined"
            return MeasureResult(float("inf"), msg)

    def stats(self) -> Dict:
        return {
            "measured": self.n_measured,
            "timeouts": self.n_timeouts,
            "crashes": self.n_crashes,
            "quarantined_traces": len(self.quarantined),
            "quarantine_rejects": self.n_quarantine_rejects,
            "workers": self.max_workers,
            "backend": self.backend,
        }
