"""Tunable fused-attention workload: search -> both backends -> dispatch.

The contract under test (the tentpole of the attention-tuning PR):

* the ``attention`` workload's trace samples the scores-block (i, j)
  tiles, which the Pallas backend turns into the flash kernel's
  ``(block_q, block_kv)`` with divisor snapping + sampled-vs-snapped
  provenance, exactly like the matmul (bm, bn, bk);
* jnp (structural) and Pallas (flash kernel) lowerings of the same tuned
  trace agree for the causal, sliding-window, global, and softcap
  variants;
* extraction emits weighted attention tasks from model traces and
  ``DispatchContext.attention`` serves the db-best blocks by
  ``(b, h, kvh, s, d, causal, window, softcap)`` key;
* the per-layer window metadata reaches the attention hook as a concrete
  Python int under the layer scan (periodic patterns), so fused dispatch
  is possible at trace time.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.backends.pallas_backend import (
    DEFAULT_ATTN_BLOCKS,
    extract_attention_blocks,
    lower_attention,
)
from repro.configs.base import get_config
from repro.core.modules import SpaceGenerator, default_modules
from repro.core.tir import random_inputs
from repro.core.validator import validate_trace
from repro.core.workloads import get_workload
from repro.integration.dispatch import DispatchContext
from repro.integration.extract import (
    AttentionSiteRecorder,
    extract_task_specs,
    model_forward_jaxpr,
)
from repro.kernels.flash_attention import best_divisor, flash_attention
from repro.models.registry import build_model
from repro.models.transformer import layer_windows, window_period
from repro.search.database import (
    Database,
    parse_workload_key,
    workload_key,
)
from repro.search.evolutionary import SearchConfig
from repro.search.tune import apply_best, tune_workload

TINY = SearchConfig(
    max_trials=4, init_random=4, population=4, measure_per_round=4,
    generations=1,
)

# causal / sliding-window / global / softcap variants at test-fast shapes
ATTN_VARIANTS = [
    dict(b=1, h=2, kvh=1, s=16, d=8, causal=1, window=0),
    dict(b=1, h=4, kvh=2, s=16, d=8, causal=1, window=4),
    dict(b=1, h=2, kvh=2, s=16, d=8, causal=0, window=0),
    dict(b=1, h=2, kvh=1, s=16, d=8, causal=1, window=0, softcap=30.0),
]


class TestAttentionParity:
    @pytest.mark.parametrize("kwargs", ATTN_VARIANTS)
    def test_tuned_trace_parity(self, kwargs):
        """jnp and Pallas lowerings of the tuned db-best trace agree."""
        db = Database(None)
        res = tune_workload(
            "attention", kwargs, use_mxu=True, config=TINY, database=db,
            runner="local", backend="jnp",
        )
        assert np.isfinite(res.best_latency_s)
        _, low_jnp = apply_best("attention", db, kwargs, backend="jnp")
        _, low_pal = apply_best(
            "attention", db, kwargs, backend="pallas-interpret"
        )
        assert low_pal.meta["pallas_kernel"] == "flash_attention"
        assert low_pal.meta.get("lowered_with") != "jnp-fallback"
        func = get_workload("attention", **kwargs)
        ins = random_inputs(func, 3)
        out_j = jax.jit(low_jnp.fn)(ins)["O"]
        out_p = jax.jit(low_pal.fn)(ins)["O"]
        np.testing.assert_allclose(
            np.asarray(out_p), np.asarray(out_j), rtol=5e-3, atol=1e-4
        )

    def test_blocks_come_from_the_trace(self):
        """Sampled (i, j) tiles of the scores block become (bq, bkv)."""
        func = get_workload("attention", b=1, h=2, kvh=1, s=32, d=8)
        gen = SpaceGenerator(default_modules(use_mxu=True))
        seen = set()
        for seed in range(6):
            v = validate_trace(func, gen.generate(func, seed=seed).trace)
            if not v.ok:
                continue
            sampled = extract_attention_blocks(v.schedule)
            _, meta = lower_attention(v.schedule, interpret=True)
            bq, bkv = meta["pallas_blocks_snapped"]
            assert 32 % bq == 0 and 32 % bkv == 0
            if sampled is not None:
                assert meta["pallas_blocks_sampled"] == list(sampled)
                seen.add((bq, bkv))
        # the space genuinely varies the blocks (not a fixed default)
        assert len(seen) > 1

    def test_kernel_snaps_non_divisor_blocks(self):
        q = jnp.asarray(np.random.default_rng(0).normal(size=(1, 2, 16, 8)))
        k = jnp.asarray(np.random.default_rng(1).normal(size=(1, 1, 16, 8)))
        v = jnp.asarray(np.random.default_rng(2).normal(size=(1, 1, 16, 8)))
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        ref = flash_attention(q, k, v, block_q=16, block_kv=16, interpret=True)
        got = flash_attention(q, k, v, block_q=13, block_kv=5, interpret=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5
        )
        assert best_divisor(16, 13, 1) == 16 and best_divisor(16, 5, 1) == 4


class TestProvenance:
    def test_snapped_blocks_in_record_and_kernel_meta(self):
        kwargs = dict(b=1, h=2, kvh=1, s=16, d=8, causal=1, window=0)
        db = Database(None)
        res = tune_workload(
            "attention", kwargs, use_mxu=True, config=TINY, database=db,
            runner="local", backend="pallas-interpret",
        )
        assert np.isfinite(res.best_latency_s)
        key = workload_key("attention", **kwargs)
        rec = db.best(key)
        assert rec is not None
        # measurement provenance: what the build actually ran
        assert rec.meta["pallas_kernel"] == "flash_attention"
        bq, bkv = rec.meta["pallas_blocks_snapped"]
        assert 16 % bq == 0 and 16 % bkv == 0
        # dispatch provenance: what the model will be served
        func = get_workload("attention", **kwargs)
        task = type("T", (), {"key": key, "func": func, "use_mxu": True})()
        ctx = DispatchContext(
            db, tasks=[task], mode="best", backend="pallas-interpret"
        )
        kern = ctx.kernel(key)
        assert kern is not None
        assert kern.meta["pallas_blocks_snapped"] == [bq, bkv]


class TestStaticWindows:
    def test_window_period(self):
        assert window_period(np.asarray([0, 0, 0, 0])) == 1
        assert window_period(np.asarray([16, 0, 16, 0])) == 2
        # an aperiodic pattern short enough to unroll is "period L"
        assert window_period(np.asarray([0, 16, 16, 16])) == 4
        # ...but past the unroll cap it must fall back to tracing
        assert window_period(np.asarray([0, 16, 16, 16, 16])) is None
        # hymba's {first, mid, last}-global pattern is aperiodic at depth
        assert window_period(layer_windows(get_config("hymba-1.5b"))) is None
        assert window_period(layer_windows(get_config("gemma2-2b"))) == 2
        assert window_period(layer_windows(get_config("smollm-135m"))) == 1

    def test_hook_sees_concrete_windows_under_scan(self):
        """The attention hook receives Python ints, not tracers, for every
        periodic window pattern — the static-window regression test."""
        cfg = get_config("gemma2-2b", smoke=True)  # alternating 16 / global
        rec = AttentionSiteRecorder()
        with rec:
            model_forward_jaxpr(cfg, batch=1, seq=16)
        windows = sorted(r["window"] for r in rec.sites)
        assert windows == [0, 16]  # both layers, both static
        assert all(isinstance(w, int) for w in windows)

    def test_aperiodic_pattern_traces_windows(self):
        cfg = get_config("hymba-1.5b", smoke=True)
        # hymba-smoke has 2 layers (statically unrollable); synthesize an
        # aperiodic variant deeper than the unroll cap
        from dataclasses import replace

        cfg = replace(cfg, n_layers=5)
        rec = AttentionSiteRecorder()
        with rec:
            model_forward_jaxpr(cfg, batch=1, seq=16)
        assert all(r["window"] == "traced" for r in rec.sites)

    def test_periodic_scan_matches_traced_scan(self):
        """Static-window forward == traced-window forward (numerics)."""
        import repro.models.transformer as T

        cfg = get_config("gemma2-2b", smoke=True)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        toks = jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab, (1, 16)),
            jnp.int32,
        )
        static = model.forward(params, tokens=toks)
        orig = T.window_period
        T.window_period = lambda *a, **kw: None  # force the traced path
        try:
            traced = model.forward(params, tokens=toks)
        finally:
            T.window_period = orig
        # bf16 model: the two scan shapes fuse/round differently at ulp
        # level; a layer-order or mask bug would diverge at O(1)
        np.testing.assert_allclose(
            np.asarray(static, np.float32), np.asarray(traced, np.float32),
            rtol=0.05, atol=0.1,
        )

    def test_prefill_periodic_cache_layout(self):
        """Period-2 prefill collects per-layer caches in layer order."""
        import repro.models.transformer as T

        cfg = get_config("gemma2-2b", smoke=True)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        toks = jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab, (1, 16)),
            jnp.int32,
        )
        cache = model.init_cache(batch=1, max_seq=16)
        logits, new_cache = model.prefill(params, cache, tokens=toks)
        orig = T.window_period
        T.window_period = lambda *a, **kw: None
        try:
            logits_t, cache_t = model.prefill(params, cache, tokens=toks)
        finally:
            T.window_period = orig
        np.testing.assert_allclose(
            np.asarray(logits, np.float32), np.asarray(logits_t, np.float32),
            rtol=0.05, atol=0.1,
        )
        # per-layer cache stacking: a (L/p, p) reshape bug would swap
        # whole layers here, far outside bf16 noise
        np.testing.assert_allclose(
            np.asarray(new_cache["k"], np.float32),
            np.asarray(cache_t["k"], np.float32),
            rtol=0.05, atol=0.1,
        )


class TestExtractionAndDispatch:
    def test_extracted_attention_tasks(self):
        cfg = get_config("gemma2-2b", smoke=True)  # window 16, alternating
        specs = extract_task_specs(cfg, batch=1, seq=32, min_task_elems=16)
        attn = [s for s in specs if s.op == "attention"]
        assert {s.kwargs["window"] for s in attn} == {0, 16}
        for s in attn:
            assert s.dispatchable
            assert s.weight == 1.0  # one local + one global layer
            name, kw = parse_workload_key(s.key)
            assert name == "attention"
            assert get_workload(name, **kw).name.startswith("attention_")

    def test_window_geq_seq_is_global(self):
        """window >= seq canonicalizes to the global task key, so the
        structurally-identical programs share one record."""
        cfg = get_config("gemma2-2b", smoke=True)
        specs = extract_task_specs(cfg, batch=1, seq=16, min_task_elems=16)
        attn = [s for s in specs if s.op == "attention"]
        assert len(attn) == 1
        assert attn[0].kwargs["window"] == 0
        assert attn[0].weight == cfg.n_layers  # both layers share it

    def test_attention_weight_counts_layers(self):
        cfg = get_config("smollm-135m", smoke=True)  # 2 uniform layers
        specs = extract_task_specs(cfg, batch=1, seq=16, min_task_elems=16)
        attn = [s for s in specs if s.op == "attention"]
        assert len(attn) == 1 and attn[0].weight == cfg.n_layers

    def test_dispatch_serves_tuned_blocks(self):
        """Model forward swaps in the db-best attention kernel (tuned
        blocks, not the fixed default) and stays numerically close."""
        cfg = get_config("smollm-135m", smoke=True)
        specs = extract_task_specs(cfg, batch=1, seq=16, min_task_elems=16)
        attn = [s for s in specs if s.op == "attention"]
        tasks = [s.to_tune_task() for s in attn]
        db = Database(None)
        res = tune_workload(
            "attention", attn[0].kwargs, use_mxu=True, config=TINY,
            database=db, runner="local", backend="pallas-interpret",
        )
        assert np.isfinite(res.best_latency_s)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        toks = jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab, (1, 16)),
            jnp.int32,
        )
        ref = model.forward(params, tokens=toks)
        ctx = DispatchContext(
            db, tasks=tasks, mode="best", backend="pallas-interpret"
        )
        with ctx:
            got = jax.jit(lambda p, t: model.forward(p, tokens=t))(
                params, toks
            )
        assert ctx.stats["attention_tuned"] > 0
        assert ctx.hits_by_key.get(tasks[0].key, 0) > 0
        err = float(
            jnp.max(jnp.abs(got.astype(jnp.float32) - ref.astype(jnp.float32)))
        )
        scale = float(jnp.max(jnp.abs(ref.astype(jnp.float32)))) or 1.0
        assert err / scale < 5e-2  # bf16 model, f32 kernel

    def test_dispatch_key_mismatch_falls_back(self):
        """No record for the shape -> the backend-default fused path (or
        the chunked path) serves, never a crash."""
        cfg = get_config("smollm-135m", smoke=True)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        toks = jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab, (1, 16)),
            jnp.int32,
        )
        ctx = DispatchContext(
            Database(None), tasks=[], mode="best", backend="pallas-interpret"
        )
        with ctx:
            out = model.forward(params, tokens=toks)
        assert ctx.stats["attention_tuned"] == 0
        assert ctx.stats["attention_fused"] > 0
        assert np.isfinite(np.asarray(out, np.float32)).all()

    def test_default_blocks_constant(self):
        # the pre-tuning fixed default the gate guards against regressing to
        assert DEFAULT_ATTN_BLOCKS == (128, 128)


class TestTransposedUnembed:
    def test_dense_transpose_at_load(self):
        """``bsd,vd->bsv`` serves through a tuned dense (m, n, k) record
        via transpose-at-load, forward and backward."""
        m, n, k = 8, 12, 16
        key = workload_key("dense", m=m, n=n, k=k)
        func = get_workload("dense", m=m, n=n, k=k)
        task = type("T", (), {"key": key, "func": func, "use_mxu": False})()
        ctx = DispatchContext(None, tasks=[task], mode="default")
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(2, 4, k)), jnp.float32)
        wT = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)
        out = ctx.dense(x, wT, transpose_w=True)
        assert out is not None
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(jnp.einsum("bsd,vd->bsv", x, wT)),
            rtol=1e-5, atol=1e-5,
        )
        # backward: reference VJP flows through the transpose
        def loss(w2):
            return ctx.dense(x, w2, transpose_w=True).sum()

        g = jax.grad(loss)(wT)
        g_ref = jax.grad(lambda w2: jnp.einsum("bsd,vd->bsv", x, w2).sum())(wT)
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(g_ref), rtol=1e-5, atol=1e-5
        )

    def test_unembed_hook_dispatches(self):
        from repro.models import layers as L

        m, n, k = 4, 12, 16
        key = workload_key("dense", m=m, n=n, k=k)
        func = get_workload("dense", m=m, n=n, k=k)
        task = type("T", (), {"key": key, "func": func, "use_mxu": False})()
        ctx = DispatchContext(None, tasks=[task], mode="default")
        x = jnp.asarray(
            np.random.default_rng(0).normal(size=(1, m, k)), jnp.float32
        )
        table = jnp.asarray(
            np.random.default_rng(1).normal(size=(n, k)), jnp.float32
        )
        ref = L.unembed(x, table)
        with ctx:
            got = L.unembed(x, table)
        assert ctx.hits_by_key.get(key, 0) > 0
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-4
        )


class TestRegressionGate:
    def test_require_dispatched_attention(self, tmp_path):
        import json

        from benchmarks.check_regression import check

        payload = {
            "models": [
                {
                    "model": "m",
                    "speedup": 1.2,
                    "tasks": [
                        {"op": "batch_matmul", "dispatched": True},
                        {"op": "attention", "dispatched": False},
                    ],
                }
            ]
        }
        p = tmp_path / "bench.json"
        p.write_text(json.dumps(payload))
        assert check(p, require_dispatched_op=["batch_matmul"]) == 0
        assert (
            check(p, require_dispatched_op=["batch_matmul", "attention"]) == 1
        )
        payload["models"][0]["tasks"][1]["dispatched"] = True
        p.write_text(json.dumps(payload))
        assert (
            check(p, require_dispatched_op=["batch_matmul", "attention"]) == 0
        )
