"""End-to-end integration: task extraction, tuned-kernel dispatch,
scheduler cold-start/plateau fixes, database robustness."""

import glob
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core.modules import SpaceGenerator, default_modules
from repro.core.validator import validate_trace
from repro.core.workloads import dense, get_workload
from repro.integration.dispatch import DispatchContext, current
from repro.integration.extract import (
    extract_task_specs,
    extract_tasks,
    sites_from_jaxpr,
)
from repro.models.registry import build_model
from repro.search.database import (
    Database,
    TuningRecord,
    parse_workload_key,
    workload_key,
)
from repro.search.evolutionary import SearchConfig
from repro.search.measure.hashing import primfunc_structural_hash
from repro.search.measure.protocol import MeasureResult, Runner
from repro.search.task_scheduler import TaskScheduler, TuneTask

SEQ = 8


# ---------------------------------------------------------------------------
# Task extraction
# ---------------------------------------------------------------------------


class TestExtraction:
    @pytest.mark.parametrize(
        "arch", ["smollm-135m", "gemma2-2b", "olmoe-1b-7b"]
    )
    def test_generic_across_configs(self, arch):
        """No per-model shape tables: extraction works off any config."""
        cfg = get_config(arch, smoke=True)
        specs = extract_task_specs(cfg, batch=1, seq=SEQ, min_task_elems=16)
        assert specs, arch
        ops = {s.op for s in specs}
        assert "dense" in ops
        keys = [s.key for s in specs]
        assert len(keys) == len(set(keys))  # deduped

    def test_repeated_layer_shapes_merge_weighted(self):
        cfg = get_config("smollm-135m", smoke=True)
        specs = extract_task_specs(cfg, batch=1, seq=SEQ, min_task_elems=16)
        hashes = [s.struct_hash for s in specs]
        assert len(hashes) == len(set(hashes))
        # per-layer ops occur once per scanned layer: weight >= n_layers
        assert any(s.weight >= cfg.n_layers for s in specs if s.op == "dense")
        # rmsnorm: >= 2 per layer + final norm
        rms = [s for s in specs if s.op == "rmsnorm"]
        assert rms and rms[0].weight >= 2 * cfg.n_layers + 1

    def test_unknown_ops_skipped(self):
        j = jax.make_jaxpr(lambda x: jnp.sort(jnp.tanh(x), axis=-1))(
            jax.ShapeDtypeStruct((8, 8), jnp.float32)
        )
        assert sites_from_jaxpr(j, d_model=8) == []

    def test_dispatchable_layout(self):
        spec = jax.ShapeDtypeStruct((16, 8), jnp.float32)
        wkn = jax.ShapeDtypeStruct((8, 12), jnp.float32)
        wnk = jax.ShapeDtypeStruct((12, 8), jnp.float32)
        ok = sites_from_jaxpr(
            jax.make_jaxpr(lambda x, w: jnp.einsum("mk,kn->mn", x, w))(spec, wkn)
        )
        assert ok[0].dispatchable
        # transposed weight (unembed layout): served via transpose-at-load
        # in DispatchContext.dense, same (m, n, k) workload key
        t = sites_from_jaxpr(
            jax.make_jaxpr(lambda x, w: jnp.einsum("mk,nk->mn", x, w))(spec, wnk)
        )
        assert t[0].dispatchable
        assert t[0].kwargs == ok[0].kwargs
        # a contraction the hook cannot serve (3-D rhs) stays non-dispatchable
        w3 = jax.ShapeDtypeStruct((2, 8, 6), jnp.float32)
        nd = sites_from_jaxpr(
            jax.make_jaxpr(lambda x, w: jnp.einsum("mk,bkn->bmn", x, w))(spec, w3)
        )
        assert not any(s.dispatchable for s in nd if s.op == "dense")

    def test_min_elems_filter_and_cap(self):
        cfg = get_config("smollm-135m", smoke=True)
        none = extract_task_specs(cfg, batch=1, seq=SEQ, min_task_elems=1 << 30)
        assert none == []
        capped = extract_task_specs(
            cfg, batch=1, seq=SEQ, min_task_elems=16, max_tasks=2
        )
        assert len(capped) == 2

    def test_tune_task_conversion(self):
        cfg = get_config("smollm-135m", smoke=True)
        tasks = extract_tasks(cfg, batch=1, seq=SEQ, min_task_elems=16)
        for t in tasks:
            assert t.func.total_flops() > 0
            name, kw = parse_workload_key(t.key)
            assert get_workload(name, **kw).name == t.func.name


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def smoke_cfg():
    return get_config("smollm-135m", smoke=True)


@pytest.fixture(scope="module")
def smoke_setup(smoke_cfg):
    """(model, params, tokens, tasks, db-with-default-records)."""
    cfg = smoke_cfg
    tasks = extract_tasks(
        cfg, batch=1, seq=SEQ, min_task_elems=16, dispatchable_only=True
    )
    assert tasks
    db = Database(None)
    for t in tasks:
        gen = SpaceGenerator(default_modules(use_mxu=t.use_mxu))
        for s in range(8):
            v = validate_trace(t.func, gen.generate(t.func, seed=s).trace)
            if v.ok:
                db.put(
                    TuningRecord(t.key, v.schedule.trace.to_json(), 1e-6, time.time())
                )
                break
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab, (1, SEQ)), jnp.int32
    )
    return model, params, toks, tasks, db


class TestDispatch:
    def test_hit_swaps_kernel_and_matches_reference(self, smoke_setup):
        model, params, toks, tasks, db = smoke_setup
        ref = jax.jit(lambda p, t: model.forward(p, tokens=t))(params, toks)
        ctx = DispatchContext(db, tasks=tasks)
        with ctx:
            assert current() is ctx
            got = jax.jit(lambda p, t: model.forward(p, tokens=t))(params, toks)
        assert current() is None
        assert ctx.stats["hits"] > 0  # database hit swapped a kernel in
        r = np.asarray(ref.astype(jnp.float32))
        g = np.asarray(got.astype(jnp.float32))
        scale = max(np.abs(r).max(), 1e-6)
        assert np.abs(g - r).max() / scale < 2e-2

    def test_miss_falls_back_to_reference(self, smoke_setup):
        model, params, toks, tasks, _ = smoke_setup
        ref = jax.jit(lambda p, t: model.forward(p, tokens=t))(params, toks)
        ctx = DispatchContext(Database(None), tasks=tasks)  # empty db
        with ctx:
            got = jax.jit(lambda p, t: model.forward(p, tokens=t))(params, toks)
        assert ctx.stats["hits"] == 0
        assert ctx.stats["misses"] > 0
        # fallback is the identical jnp path, bit-for-bit
        assert np.array_equal(np.asarray(got), np.asarray(ref))

    def test_default_mode_needs_no_database(self, smoke_setup):
        model, params, toks, tasks, _ = smoke_setup
        ref = jax.jit(lambda p, t: model.forward(p, tokens=t))(params, toks)
        ctx = DispatchContext(None, tasks=tasks, mode="default")
        with ctx:
            got = jax.jit(lambda p, t: model.forward(p, tokens=t))(params, toks)
        assert ctx.stats["hits"] > 0
        r = np.asarray(ref.astype(jnp.float32))
        g = np.asarray(got.astype(jnp.float32))
        assert np.abs(g - r).max() / max(np.abs(r).max(), 1e-6) < 2e-2

    def test_rmsnorm_dispatches_under_extracted_key(self, smoke_setup):
        """Extraction keys and dispatch lookup keys must agree, eps included."""
        model, params, _, tasks, db = smoke_setup
        cfg = model.cfg
        rms = [t for t in tasks if t.key.startswith("rmsnorm/")]
        assert rms
        ctx = DispatchContext(db, tasks=tasks)
        x = jnp.ones((1, SEQ, cfg.d_model), jnp.float32)
        w = jnp.ones((cfg.d_model,), jnp.float32)
        out = ctx.rmsnorm(x, w, cfg.norm_eps)
        assert out is not None and ctx.stats["hits"] == 1
        ref = x * jax.lax.rsqrt(
            jnp.mean(x * x, -1, keepdims=True) + cfg.norm_eps
        ) * w
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=5e-3, atol=1e-3
        )

    def test_shape_mismatch_returns_none(self, smoke_setup):
        _, _, _, tasks, db = smoke_setup
        ctx = DispatchContext(db, tasks=tasks)
        x = jnp.ones((4, 3), jnp.float32)  # shape in no task
        w = jnp.ones((3, 5), jnp.float32)
        assert ctx.dense(x, w) is None
        assert ctx.dense(jnp.ones((4, 4)), jnp.ones((3, 5))) is None  # k mismatch

    def test_grad_flows_through_dispatched_kernels(self, smoke_setup):
        from repro.training.optimizer import OptConfig, adamw_init
        from repro.training.train_loop import make_train_step

        model, params, toks, tasks, db = smoke_setup
        step = make_train_step(
            model, OptConfig(), dispatch=DispatchContext(db, tasks=tasks)
        )
        opt = adamw_init(params)
        batch = {
            "tokens": jnp.asarray(
                np.random.default_rng(1).integers(
                    0, model.cfg.vocab, (1, SEQ + 1)
                ),
                jnp.int32,
            )
        }
        _, _, metrics = jax.jit(step)(params, opt, batch)
        assert np.isfinite(float(metrics["loss"]))

    def test_serving_engine_accepts_context(self, smoke_setup):
        from repro.serving.engine import ServingEngine

        model, params, _, tasks, db = smoke_setup
        eng = ServingEngine(
            model.cfg, params, max_batch=2, max_seq=16,
            dispatch=DispatchContext(db, tasks=tasks),
        )
        r = eng.submit(np.arange(SEQ) % model.cfg.vocab, max_new_tokens=3)
        eng.run()
        assert r.done and len(r.generated) == 3


# ---------------------------------------------------------------------------
# Scheduler cold-start / plateau fixes
# ---------------------------------------------------------------------------


class FakeRunner(Runner):
    """Constant-latency runner: no search signal, instant measurements."""

    name = "fake"

    def __init__(self, latency_s: float = 1e-3):
        self.latency_s = latency_s
        self.calls = 0

    def run(self, inputs):
        self.calls += len(inputs)
        return [MeasureResult(self.latency_s) for _ in inputs]


def _tiny_tasks(n):
    out = []
    for i in range(n):
        m = 8 * (i + 1)
        out.append(
            TuneTask(workload_key("dense", m=m, n=8, k=8), dense(m=m, n=8, k=8))
        )
    return out


SMALL = SearchConfig(
    max_trials=8, init_random=2, population=4, measure_per_round=2, generations=1
)


class TestTaskScheduler:
    def test_warmup_initializes_every_task_first(self):
        sched = TaskScheduler(_tiny_tasks(3), runner=FakeRunner(), config=SMALL)
        sched.tune(total_rounds=3)
        assert all(sched._initialized)
        assert all(s.measured for s in sched.searches)  # nobody starved

    def test_early_stop_when_all_tasks_plateau(self):
        sched = TaskScheduler(
            _tiny_tasks(2), runner=FakeRunner(), config=SMALL, patience=1
        )
        sched.tune(total_rounds=50)
        assert sched.rounds_run < 50

    def test_gradient_tie_break_randomized(self):
        sched = TaskScheduler(
            _tiny_tasks(4), runner=FakeRunner(), config=SMALL, seed=7
        )
        sched._initialized = [True] * 4
        sched._gradient = lambda i: 1.0  # exact four-way tie
        picks = {sched._pick_task() for _ in range(40)}
        assert len(picks) > 1  # not always argmax index 0

    def test_task_with_nothing_measured_waits(self):
        # a task whose rounds found nothing to measure gets rounds only
        # after the tasks that measure plateau
        sched = TaskScheduler(
            _tiny_tasks(2), runner=FakeRunner(), config=SMALL, patience=2
        )
        sched._initialized = [True, True]
        sched.searches[0].best_latency = 1e-3
        sched.searches[0].history = [(1, 1e-3)]
        assert sched._pick_task() == 0
        sched._stale_rounds[0] = 2
        assert sched._pick_task() == 1

    def test_plateaued_task_stops_receiving_trials(self):
        sched = TaskScheduler(
            _tiny_tasks(2), runner=FakeRunner(), config=SMALL, patience=1
        )
        sched.tune(total_rounds=50)
        assert all(s >= 1 for s in sched._stale_rounds)
        assert sched._pick_task() is None


# ---------------------------------------------------------------------------
# Database robustness + key round-trip
# ---------------------------------------------------------------------------


class TestDatabase:
    def _record(self, key="dense/k=8/m=8/n=8"):
        f = dense(m=8, n=8, k=8)
        gen = SpaceGenerator(default_modules())
        v = validate_trace(f, gen.generate(f, seed=0).trace)
        assert v.ok
        return TuningRecord(key, v.schedule.trace.to_json(), 1e-4, time.time())

    def test_crashed_save_leaves_database_intact(self, tmp_path):
        path = str(tmp_path / "db.json")
        db = Database(path)
        db.put(self._record())
        before = open(path).read()
        # poison: a record whose meta cannot serialize -> dump raises midway
        db.records["dense/k=8/m=8/n=8"][0].meta = {"bad": object()}
        with pytest.raises(TypeError):
            db.save()
        assert open(path).read() == before  # last complete db preserved
        assert glob.glob(str(tmp_path / "*.tmp")) == []  # no temp junk
        db2 = Database(path)  # still loadable
        assert db2.best("dense/k=8/m=8/n=8") is not None

    def test_workload_key_roundtrip(self):
        key = workload_key("dense", m=8, n=16, k=32, epilogue="bias_gelu")
        name, kw = parse_workload_key(key)
        assert name == "dense"
        assert kw == {"m": 8, "n": 16, "k": 32, "epilogue": "bias_gelu"}
        assert workload_key(name, **kw) == key
        name, kw = parse_workload_key(
            workload_key("rmsnorm", tokens=128, d=576, eps=1e-6)
        )
        assert kw["eps"] == pytest.approx(1e-6)
        with pytest.raises(ValueError):
            parse_workload_key("dense/notakv")


class TestPrimFuncHash:
    def test_stable_and_shape_sensitive(self):
        a = primfunc_structural_hash(dense(m=8, n=8, k=8))
        b = primfunc_structural_hash(dense(m=8, n=8, k=8))
        c = primfunc_structural_hash(dense(m=8, n=16, k=8))
        d = primfunc_structural_hash(dense(m=8, n=8, k=8, epilogue="bias_relu"))
        assert a == b
        assert len({a, c, d}) == 3
