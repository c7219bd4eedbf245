"""Lowering-backend registry + jnp/Pallas parity.

The contract under test: the probabilistic search space is constructed
once and the *backend* carries the sampled decisions to hardware — so for
every workload with a native Pallas lowering, the jnp-lowered and the
Pallas-lowered (interpret mode) executables of the same tuned trace must
agree within dtype tolerance, and the measurement/dispatch stack must
thread a ``backend=`` spec end to end (including recording the *snapped*
Pallas block sizes into provenance instead of losing them).
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.backends.registry import (
    Backend,
    Lowered,
    backend_names,
    default_backend_spec,
    get_backend,
    register_backend,
)
from repro.core.modules import SpaceGenerator, default_modules
from repro.core.tir import random_inputs
from repro.core.validator import validate_trace
from repro.core.workloads import get_workload
from repro.search.database import Database, TuningRecord, workload_key
from repro.search.evolutionary import SearchConfig
from repro.search.measure.local import LocalBuilder, LocalRunner
from repro.search.measure.pool import ProcessPoolRunner
from repro.search.measure.protocol import MeasureInput
from repro.search.tune import apply_best, tune_workload


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_builtins_registered(self):
        names = backend_names()
        assert "jnp" in names and "pallas" in names

    def test_get_backend_memoizes(self):
        assert get_backend("jnp") is get_backend("jnp")

    def test_unknown_backend_raises_with_choices(self):
        with pytest.raises(KeyError, match="jnp"):
            get_backend("warp-drive")

    def test_env_default(self, monkeypatch):
        monkeypatch.delenv("REPRO_BACKEND", raising=False)
        assert default_backend_spec() == "jnp"
        monkeypatch.setenv("REPRO_BACKEND", "pallas-interpret")
        assert default_backend_spec() == "pallas-interpret"
        assert get_backend(None).name == "pallas-interpret"

    def test_pallas_refuses_to_run_off_tpu(self):
        # "pallas" means Mosaic: off a TPU it raises instead of quietly
        # running the interpreter, and no instance is memoized
        assert jax.default_backend() != "tpu"
        with pytest.raises(RuntimeError, match="pallas-interpret"):
            get_backend("pallas")
        from repro.integration.dispatch import DispatchContext

        with pytest.raises(RuntimeError, match="TPU"):
            DispatchContext(Database(None), tasks=[], backend="pallas")
        assert get_backend("pallas-interpret").interpret is True

    def test_register_plugin(self):
        @register_backend("test-dummy")
        def _make():
            class Dummy(Backend):
                name = "test-dummy"

                def lower(self, sch, workload_key=""):
                    return Lowered(lambda ins: ins, {"backend": self.name})

            return Dummy()

        assert get_backend("test-dummy").name == "test-dummy"


# ---------------------------------------------------------------------------
# jnp/Pallas parity on tuned traces from the database
# ---------------------------------------------------------------------------

# every workload with a native Pallas lowering, at test-fast shapes
PARITY_WORKLOADS = [
    ("dense", dict(m=32, n=32, k=32), True),
    ("fused_dense", dict(m=32, n=64, k=32), True),
    ("batch_matmul", dict(b=2, m=16, n=16, k=16), True),
    ("sfm", dict(m=32, n=32), False),
]

TINY = SearchConfig(
    max_trials=4, init_random=4, population=4, measure_per_round=4,
    generations=1,
)


class TestPallasParity:
    @pytest.mark.parametrize("name,kwargs,mxu", PARITY_WORKLOADS)
    def test_tuned_trace_parity(self, name, kwargs, mxu):
        """jnp-backend and pallas-backend outputs agree on the tuned
        database-best trace, within dtype tolerance."""
        db = Database(None)
        res = tune_workload(
            name, kwargs, use_mxu=mxu, config=TINY, database=db,
            runner="local", backend="jnp",
        )
        assert np.isfinite(res.best_latency_s)
        _, low_jnp = apply_best(name, db, kwargs, backend="jnp")
        _, low_pallas = apply_best(name, db, kwargs, backend="pallas-interpret")
        assert low_pallas.meta["backend"] == "pallas-interpret"
        assert low_pallas.meta.get("lowered_with") != "jnp-fallback"
        func = get_workload(name, **kwargs)
        ins = random_inputs(func, 3)
        out_j = jax.jit(low_jnp.fn)(ins)
        out_p = jax.jit(low_pallas.fn)(ins)
        for k in (b.name for b in func.outputs):
            np.testing.assert_allclose(
                np.asarray(out_p[k]), np.asarray(out_j[k]),
                rtol=5e-3, atol=1e-4,
            )

    def test_unsupported_workload_falls_back_to_jnp(self):
        func = get_workload("rmsnorm", tokens=16, d=32)
        gen = SpaceGenerator(default_modules())
        sch = None
        for s in range(8):
            v = validate_trace(func, gen.generate(func, seed=s).trace)
            if v.ok:
                sch = v.schedule
                break
        assert sch is not None
        low = get_backend("pallas-interpret").lower(sch)
        assert low.meta["lowered_with"] == "jnp-fallback"
        ins = random_inputs(func, 0)
        ref = get_backend("jnp").lower(sch).fn(ins)
        got = low.fn(ins)
        np.testing.assert_allclose(
            np.asarray(got["Y"]), np.asarray(ref["Y"]), rtol=1e-5, atol=1e-6
        )


# ---------------------------------------------------------------------------
# backend= threading through the measurement stack
# ---------------------------------------------------------------------------


class TestMeasureThreading:
    def test_local_builder_records_lowering_meta(self):
        func = get_workload("dense", m=32, n=32, k=32)
        gen = SpaceGenerator(default_modules(use_mxu=True))
        v = None
        for s in range(8):
            v = validate_trace(func, gen.generate(func, seed=s).trace)
            if v.ok:
                break
        builder = LocalBuilder(backend="pallas-interpret")
        (br,) = builder.build(
            [MeasureInput("dense/k=32/m=32/n=32", func, v.schedule.trace)]
        )
        assert br.ok
        assert br.meta["backend"] == "pallas-interpret"
        bm, bn, bk = br.meta["pallas_blocks_snapped"]
        assert 32 % bm == 0 and 32 % bn == 0 and 32 % bk == 0

    def test_pool_payload_carries_backend(self):
        func = get_workload("dense", m=8, n=8, k=8)
        gen = SpaceGenerator(default_modules())
        v = validate_trace(func, gen.generate(func, seed=0).trace)
        r = ProcessPoolRunner(backend="pallas")
        try:
            payload = r._payload(MeasureInput("k", func, v.schedule.trace))
            assert payload["backend"] == "pallas"
        finally:
            r.close()

    def test_snapped_blocks_persisted_into_tuning_record(self):
        """Satellite fix: the snapped (bm, bn, bk) lands in
        TuningRecord.meta — measured tiles are never silently lost."""
        db = Database(None)
        res = tune_workload(
            "dense", dict(m=32, n=48, k=32), use_mxu=True, config=TINY,
            database=db, runner="local", backend="pallas-interpret",
        )
        assert np.isfinite(res.best_latency_s)
        rec = db.best(res.workload_key)
        assert rec is not None
        assert rec.meta["backend"] == "pallas-interpret"
        bm, bn, bk = rec.meta["pallas_blocks_snapped"]
        assert 32 % bm == 0 and 48 % bn == 0 and 32 % bk == 0

    def test_runner_backend_defaults_to_env(self, monkeypatch):
        monkeypatch.setenv("REPRO_BACKEND", "pallas-interpret")
        assert LocalRunner().backend == "pallas-interpret"
        monkeypatch.delenv("REPRO_BACKEND")
        assert LocalRunner().backend == "jnp"


# ---------------------------------------------------------------------------
# Dispatch: batched matmul + fused attention through the backend
# ---------------------------------------------------------------------------


def _default_record(db, op, kwargs, use_mxu=True):
    func = get_workload(op, **kwargs)
    key = workload_key(op, **kwargs)
    gen = SpaceGenerator(default_modules(use_mxu=use_mxu))
    for s in range(12):
        v = validate_trace(func, gen.generate(func, seed=s).trace)
        if v.ok:
            db.put(TuningRecord(key, v.schedule.trace.to_json(), 1e-6, time.time()))
            return key, func
    raise AssertionError(f"no valid schedule for {key}")


@pytest.fixture(scope="module")
def attn_qkv():
    B, KVH, G, S, D = 1, 2, 2, 16, 8
    q = jax.random.normal(jax.random.PRNGKey(0), (B, KVH * G, S, D), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, KVH, S, D), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, KVH, S, D), jnp.float32)
    return q, k, v


class TestBatchedDispatch:
    def test_attention_contractions_extract_dispatchable(self, attn_qkv):
        from repro.integration.extract import sites_from_jaxpr
        from repro.models import layers as L

        q, k, v = attn_qkv
        jx = jax.make_jaxpr(
            lambda q, k, v: L.chunked_attention(q, k, v, causal=True, chunk=8)
        )(q, k, v)
        bmm = [s for s in sites_from_jaxpr(jx) if s.op == "batch_matmul"]
        assert len(bmm) == 2  # score + value contraction
        assert all(s.dispatchable for s in bmm)

    def test_transposed_bmm_layout_not_dispatchable(self):
        from repro.integration.extract import sites_from_jaxpr

        a = jax.ShapeDtypeStruct((2, 8, 4), jnp.float32)
        bT = jax.ShapeDtypeStruct((2, 8, 4), jnp.float32)
        sites = sites_from_jaxpr(
            jax.make_jaxpr(lambda a, b: jnp.einsum("bmk,bnk->bmn", a, b))(a, bT)
        )
        assert sites and not sites[0].dispatchable

    @pytest.mark.parametrize("backend", ["jnp", "pallas-interpret"])
    def test_chunked_attention_dispatches_bmm(self, attn_qkv, backend):
        """The attention score/value contractions swap in tuned
        batch_matmul kernels under both backends (traced window — the
        model's scan case — so the fused path declines)."""
        from repro.integration.dispatch import DispatchContext
        from repro.integration.extract import sites_from_jaxpr
        from repro.models import layers as L
        from repro.search.task_scheduler import TuneTask

        q, k, v = attn_qkv
        ref = L.chunked_attention(q, k, v, causal=True, chunk=8)
        jx = jax.make_jaxpr(
            lambda q, k, v: L.chunked_attention(q, k, v, causal=True, chunk=8)
        )(q, k, v)
        db = Database(None)
        tasks = []
        for s in sites_from_jaxpr(jx):
            if s.op != "batch_matmul":
                continue
            key, func = _default_record(db, "batch_matmul", s.kwargs)
            tasks.append(TuneTask(key=key, func=func))
        ctx = DispatchContext(db, tasks=tasks, backend=backend)
        with ctx:
            got = jax.jit(
                lambda q, k, v, w: L.chunked_attention(
                    q, k, v, causal=True, window=w, chunk=8
                )
            )(q, k, v, jnp.int32(0))
        assert ctx.stats["hits"] == 2
        assert ctx.stats["attention_fused"] == 0
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=5e-3, atol=1e-3
        )

    def test_bmm_dispatch_grad_flows(self, attn_qkv):
        from repro.integration.dispatch import DispatchContext
        from repro.integration.extract import sites_from_jaxpr
        from repro.models import layers as L
        from repro.search.task_scheduler import TuneTask

        q, k, v = attn_qkv
        jx = jax.make_jaxpr(
            lambda q, k, v: L.chunked_attention(q, k, v, causal=True, chunk=8)
        )(q, k, v)
        db = Database(None)
        tasks = []
        for s in sites_from_jaxpr(jx):
            if s.op == "batch_matmul":
                key, func = _default_record(db, "batch_matmul", s.kwargs)
                tasks.append(TuneTask(key=key, func=func))
        with DispatchContext(db, tasks=tasks, backend="pallas-interpret"):
            g = jax.grad(
                lambda q: L.chunked_attention(
                    q, k, v, causal=True, window=jnp.int32(0), chunk=8
                ).sum()
            )(q)
        assert np.isfinite(np.asarray(g)).all()

    def test_kernel_meta_surfaces_snapped_blocks(self):
        from repro.integration.dispatch import DispatchContext
        from repro.search.task_scheduler import TuneTask

        db = Database(None)
        key, func = _default_record(db, "batch_matmul", dict(b=2, m=16, n=16, k=16))
        ctx = DispatchContext(
            db, tasks=[TuneTask(key=key, func=func)], backend="pallas-interpret"
        )
        kern = ctx.kernel(key)
        assert kern is not None
        assert "pallas_blocks_snapped" in kern.meta

    def test_search_and_dispatch_kernels_carry_the_same_identity(self):
        from repro.integration.dispatch import DispatchContext
        from repro.search.task_scheduler import TuneTask

        db = Database(None)
        key, func = _default_record(db, "dense", dict(m=32, n=32, k=32))
        trace = db.best(key).trace()
        (built,) = LocalBuilder(backend="pallas-interpret").build(
            [MeasureInput(key, func, trace)]
        )
        served = DispatchContext(
            db, tasks=[TuneTask(key=key, func=func)], backend="pallas-interpret"
        ).kernel(key)
        ins = random_inputs(func, 0)

        def calls(jaxpr):  # pallas_call equations, inside jit too
            for e in jaxpr.eqns:
                if e.primitive.name == "pallas_call":
                    yield e
                    continue
                for v in e.params.values():
                    inner = getattr(v, "jaxpr", v)
                    if hasattr(inner, "eqns"):
                        yield from calls(inner)

        def identity(fn):
            (call,) = calls(jax.make_jaxpr(fn)(ins).jaxpr)
            return call.params["name"], dict(call.params["metadata"])

        name, meta = identity(built.artifact)
        assert (name, meta) == identity(served.fn)
        assert name == "dense" and meta["task"] == key
        assert meta["blocks"] == ",".join(
            map(str, built.meta["pallas_blocks_snapped"]))


class TestFusedAttention:
    def test_pallas_fused_matches_reference(self, attn_qkv):
        from repro.integration.dispatch import DispatchContext
        from repro.kernels import ref as kref

        q, k, v = attn_qkv
        ctx = DispatchContext(Database(None), tasks=[], backend="pallas-interpret")
        out = ctx.attention(q, k, v, causal=True, window=None)
        assert out is not None and ctx.stats["attention_fused"] == 1
        want = kref.flash_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(want), rtol=2e-3, atol=1e-3
        )

    def test_jnp_backend_has_no_fused_path(self, attn_qkv):
        from repro.integration.dispatch import DispatchContext

        q, k, v = attn_qkv
        ctx = DispatchContext(Database(None), tasks=[], backend="jnp")
        assert ctx.attention(q, k, v) is None

    def test_traced_window_falls_back(self, attn_qkv):
        from repro.integration.dispatch import DispatchContext
        from repro.models import layers as L

        q, k, v = attn_qkv
        ref = L.chunked_attention(q, k, v, causal=True, chunk=8)
        ctx = DispatchContext(Database(None), tasks=[], backend="pallas-interpret")
        with ctx:
            got = jax.jit(
                lambda q, k, v, w: L.chunked_attention(
                    q, k, v, causal=True, window=w, chunk=8
                )
            )(q, k, v, jnp.int32(0))
        assert ctx.stats["attention_fused"] == 0  # declined: window traced
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5)

    def test_chunked_attention_swaps_to_fused_kernel(self, attn_qkv):
        from repro.integration.dispatch import DispatchContext
        from repro.models import layers as L

        q, k, v = attn_qkv
        ref = L.chunked_attention(q, k, v, causal=True, chunk=8)
        with DispatchContext(
            Database(None), tasks=[], backend="pallas-interpret"
        ) as ctx:
            got = L.chunked_attention(q, k, v, causal=True, chunk=8)
        assert ctx.stats["attention_fused"] == 1
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=2e-3, atol=1e-3
        )


# ---------------------------------------------------------------------------
# Benchmark regression gate
# ---------------------------------------------------------------------------


class TestRegressionGate:
    def _payload(self, speedup, dispatched=True):
        return {
            "benchmark": "end_to_end",
            "backend": "pallas",
            "models": [{
                "model": "smollm-135m",
                "speedup": speedup,
                "tasks": [{
                    "key": "batch_matmul/b=3/k=64/m=384/n=128",
                    "op": "batch_matmul",
                    "dispatched": dispatched,
                }],
            }],
        }

    def test_gate_passes_and_fails_on_speedup(self, tmp_path):
        import json
        import sys

        sys.path.insert(0, "benchmarks")
        try:
            import check_regression
        finally:
            sys.path.pop(0)
        good = tmp_path / "good.json"
        good.write_text(json.dumps(self._payload(1.2)))
        assert check_regression.check(good) == 0
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps(self._payload(0.7)))
        assert check_regression.check(bad) == 1
        # dispatch-coverage requirement
        miss = tmp_path / "miss.json"
        miss.write_text(json.dumps(self._payload(1.2, dispatched=False)))
        assert check_regression.check(
            miss, require_dispatched_op="batch_matmul"
        ) == 1
        assert check_regression.check(
            good, require_dispatched_op="batch_matmul"
        ) == 0
