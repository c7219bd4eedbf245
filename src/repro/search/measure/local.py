"""In-process Builder and Runner (serial reference implementation).

``LocalBuilder`` lowers each candidate through the selected lowering
backend (``backend=`` registry spec, default the ambient
``REPRO_BACKEND``) and wraps it in ``jax.jit``, which is lazy: nothing
compiles at build time.  ``LocalRunner`` times the artifacts; the first
call compiles (or loads from the persistent cache) and is timed apart
from the warm-up and timed repeats.  The
split matters even locally: the builder's output is reusable (e.g. for
correctness checks) and the timing loop is identical for every in-process
runner.  Process-parallel measurement lives in :mod:`pool`.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax
import numpy as np

from ...backends.registry import get_backend, resolve_backend_spec
from ...core.tir import PrimFunc, random_inputs
from ...core.validator import validate_trace
from ...obs import emit, span, trace_enabled
from .hashing import structural_hash
from .protocol import Builder, BuildResult, MeasureInput, MeasureResult, Runner


class LocalBuilder(Builder):
    """Validate, lower and wrap each candidate in ``jax.jit`` in the
    current process; the compile happens at the artifact's first call."""

    name = "local"

    def __init__(self, backend: Optional[str] = None):
        self.backend = resolve_backend_spec(backend)
        get_backend(self.backend)  # fail fast on a typo'd spec

    def build(self, inputs: List[MeasureInput]) -> List[BuildResult]:
        be = get_backend(self.backend)
        out: List[BuildResult] = []
        for mi in inputs:
            t0 = time.perf_counter()
            try:
                sch = mi.schedule
                if sch is None:
                    v = validate_trace(mi.func, mi.trace)
                    if not v.ok:
                        out.append(BuildResult(error=f"invalid trace: {v.reason}"))
                        sch = None
                    else:
                        sch = v.schedule
                if sch is not None:
                    lowered = be.lower(sch, workload_key=mi.workload_key)
                    fn = jax.jit(lowered.fn)
                    out.append(
                        BuildResult(
                            artifact=fn,
                            build_time_s=time.perf_counter() - t0,
                            meta=lowered.meta,
                        )
                    )
            except Exception as e:  # lowering failure -> rejection, not crash
                out.append(
                    BuildResult(
                        error=f"{type(e).__name__}: {e}",
                        build_time_s=time.perf_counter() - t0,
                    )
                )
            br = out[-1]
            if trace_enabled():
                emit(
                    "measure.build",
                    key=mi.workload_key,
                    hash=structural_hash(mi.workload_key, mi.trace),
                    ok=br.ok,
                    dur_s=br.build_time_s,
                    backend=self.backend,
                    **({"error": br.error} if br.error else {}),
                )
        return out


def time_artifact(
    fn,
    ins,
    repeats: int,
    warmup: int,
    timeout_s: float,
) -> MeasureResult:
    """Shared timing loop: the first call (trace, compile or cache load,
    one run) with its timeout check, then ``warmup`` calls, then the
    median of ``repeats`` timed runs.  ``compile_s`` is the first call's
    wall, ``timing_s`` that of the warm-up and timed repeats."""
    t0 = time.perf_counter()
    compile_s = None
    try:
        with span("measure.compile"):
            jax.block_until_ready(fn(ins))
        compile_s = time.perf_counter() - t0
        if compile_s > timeout_s:
            # source stays "measured": this IS a completed measurement (the
            # schedule is too slow) and may be cached; source="timeout" is
            # reserved for pool batch-budget expiry, where the candidate may
            # never have run and must not be cached
            return MeasureResult(
                float("inf"), f"timeout (first call took {compile_s:.2f}s)",
                compile_s=compile_s,
            )
        t1 = time.perf_counter()
        for _ in range(warmup):
            jax.block_until_ready(fn(ins))
        times = []
        for _ in range(repeats):
            t = time.perf_counter()
            jax.block_until_ready(fn(ins))
            times.append(time.perf_counter() - t)
        return MeasureResult(
            float(np.median(times)), run_time_s=float(sum(times)),
            compile_s=compile_s, timing_s=time.perf_counter() - t1,
        )
    except Exception as e:  # runtime failure -> rejection
        took = time.perf_counter() - t0
        return MeasureResult(
            float("inf"), f"{type(e).__name__}: {e}",
            compile_s=took if compile_s is None else compile_s,
            timing_s=0.0 if compile_s is None else took - compile_s,
        )


class LocalRunner(Runner):
    """Serial in-process measurement through a ``LocalBuilder``."""

    name = "local"

    def __init__(
        self,
        repeats: int = 3,
        warmup: int = 1,
        timeout_s: float = 10.0,
        backend: Optional[str] = None,
    ):
        self.repeats = repeats
        self.warmup = warmup
        self.timeout_s = timeout_s
        self.builder = LocalBuilder(backend=backend)
        self.backend = self.builder.backend
        self._inputs_cache: Dict[str, Dict] = {}
        self.n_measured = 0
        self.n_failed = 0

    def _inputs(self, func: PrimFunc):
        key = func.name + str(tuple(b.shape for b in func.inputs))
        if key not in self._inputs_cache:
            self._inputs_cache[key] = {
                k: jax.device_put(v) for k, v in random_inputs(func, 0).items()
            }
        return self._inputs_cache[key]

    def run(self, inputs: List[MeasureInput]) -> List[MeasureResult]:
        built = self.builder.build(inputs)
        out: List[MeasureResult] = []
        for mi, br in zip(inputs, built):
            if not br.ok:
                self.n_failed += 1
                out.append(
                    MeasureResult(float("inf"), br.error, build_time_s=br.build_time_s)
                )
                continue
            ins = self._inputs(mi.func)
            t0 = time.perf_counter()
            res = time_artifact(
                br.artifact, ins, self.repeats, self.warmup, self.timeout_s
            )
            # run-stage wall: compile_s + timing_s and the loop around them
            run_wall = time.perf_counter() - t0
            res.build_time_s = br.build_time_s
            res.meta = br.meta
            self.n_measured += 1
            if not res.ok:
                self.n_failed += 1
            if trace_enabled():
                emit(
                    "measure.run",
                    key=mi.workload_key,
                    hash=structural_hash(mi.workload_key, mi.trace),
                    ok=res.ok,
                    latency_s=res.latency_s if res.ok else None,
                    compile_s=res.compile_s,
                    timing_s=res.timing_s,
                    dur_s=run_wall,
                    backend=self.backend,
                    **({"error": res.error} if res.error else {}),
                )
            out.append(res)
        return out

    def stats(self):
        return {
            "measured": self.n_measured,
            "failed": self.n_failed,
            "backend": self.backend,
        }
