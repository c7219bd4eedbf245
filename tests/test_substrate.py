"""Substrate: data pipeline, optimizer, checkpoint, fault tolerance,
sharding resolution, serving engine."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis", reason="property-based tests need hypothesis")
from hypothesis import given, settings, strategies as st

from repro.configs.base import ShapeConfig, get_config
from repro.data.pipeline import SyntheticTokenPipeline
from repro.distributed import sharding as shd
from repro.launch.mesh import make_mesh
from repro.models.registry import build_model
from repro.training import checkpoint as ckpt
from repro.training.fault_tolerance import StepFailure, StragglerDetector, retry
from repro.training.optimizer import (
    OptConfig,
    adamw_init,
    adamw_update,
    compress_gradients,
    global_norm,
)


class TestDataPipeline:
    def test_deterministic_across_instances(self):
        cfg = get_config("smollm-135m", smoke=True)
        p1 = SyntheticTokenPipeline(cfg, 16, 4, seed=7)
        p2 = SyntheticTokenPipeline(cfg, 16, 4, seed=7)
        np.testing.assert_array_equal(
            p1.batch_at(13)["tokens"], p2.batch_at(13)["tokens"]
        )

    def test_resume_equals_continuous(self):
        cfg = get_config("smollm-135m", smoke=True)
        p = SyntheticTokenPipeline(cfg, 8, 2, seed=1)
        cont = [b["tokens"] for _, b in zip(range(6), iter(p))]
        resumed = [b["tokens"] for _, b in zip(range(3), p.iter_from(3))]
        for a, b in zip(cont[3:], resumed):
            np.testing.assert_array_equal(a, b)

    def test_shards_are_disjoint_streams(self):
        cfg = get_config("smollm-135m", smoke=True)
        a = SyntheticTokenPipeline(cfg, 8, 4, num_shards=2, shard_id=0)
        b = SyntheticTokenPipeline(cfg, 8, 4, num_shards=2, shard_id=1)
        assert not np.array_equal(a.batch_at(0)["tokens"], b.batch_at(0)["tokens"])
        assert a.local_batch == b.local_batch == 2


class TestOptimizer:
    def test_adamw_converges_on_quadratic(self):
        params = {"w": jnp.asarray([5.0, -3.0])}
        opt = adamw_init(params)
        cfg = OptConfig(lr=0.2, warmup_steps=1, total_steps=100, weight_decay=0.0)

        def loss(p):
            return jnp.sum(p["w"] ** 2)

        for _ in range(60):
            g = jax.grad(loss)(params)
            params, opt = adamw_update(cfg, g, opt, params)
        assert float(loss(params)) < 0.1

    def test_clip_caps_update_norm(self):
        params = {"w": jnp.zeros(4)}
        opt = adamw_init(params)
        cfg = OptConfig(lr=1.0, clip_norm=1e-3, warmup_steps=1, total_steps=10,
                        weight_decay=0.0)
        g = {"w": jnp.full((4,), 1e6)}
        p2, _ = adamw_update(cfg, g, opt, params)
        assert float(global_norm(p2)) < 2.0

    @given(st.integers(2, 8))
    @settings(max_examples=10, deadline=None)
    def test_compression_bounded_error(self, bits):
        g = {"w": jnp.asarray(np.random.default_rng(0).standard_normal(256))}
        gq = compress_gradients(g, bits, jax.random.PRNGKey(0))
        scale = float(jnp.max(jnp.abs(g["w"])))
        err = float(jnp.max(jnp.abs(gq["w"] - g["w"])))
        assert err <= scale / (2 ** (bits - 1) - 1) * 1.01


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        tree = {"a": jnp.arange(6).reshape(2, 3), "b": {"c": jnp.ones(4)}}
        ckpt.save(str(tmp_path), 7, tree, extra={"note": "x"})
        step, back, extra = ckpt.restore(str(tmp_path))
        assert step == 7 and extra["note"] == "x"
        np.testing.assert_array_equal(np.asarray(back["a"]), np.arange(6).reshape(2, 3))

    def test_latest_pointer_and_gc(self, tmp_path):
        tree = {"w": jnp.zeros(2)}
        for s in (1, 2, 3, 4):
            ckpt.save(str(tmp_path), s, tree)
        assert ckpt.latest_step(str(tmp_path)) == 4
        ckpt.gc_old(str(tmp_path), keep_last=2)
        steps = {n for n in os.listdir(tmp_path) if n.startswith("step_")}
        assert steps == {"step_3", "step_4"}

    def test_elastic_reshard_restore(self, tmp_path):
        """Checkpoint written once restores onto a different mesh layout."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        tree = {"w": jnp.arange(8.0)}
        ckpt.save(str(tmp_path), 1, tree)
        mesh = make_mesh((1,), ("model",))
        sh = {"w": NamedSharding(mesh, P("model"))}
        _, back, _ = ckpt.restore(str(tmp_path), mesh=mesh, shardings=sh)
        np.testing.assert_array_equal(np.asarray(back["w"]), np.arange(8.0))
        assert back["w"].sharding == sh["w"]

    def test_training_resume_matches_uninterrupted(self, tmp_path):
        """Fault-tolerance contract: crash + resume == continuous run."""
        from repro.training.train_loop import make_train_step
        from repro.models.registry import make_train_batch

        cfg = get_config("smollm-135m", smoke=True)
        m = build_model(cfg)
        step_fn = jax.jit(make_train_step(m, OptConfig(lr=1e-3)))
        pipe = SyntheticTokenPipeline(cfg, 16, 2, seed=3)

        def run(n_steps, params, opt, start=0):
            for s, batch in zip(range(start, n_steps), pipe.iter_from(start)):
                batch = {k: jnp.asarray(v) for k, v in batch.items()}
                params, opt, _ = step_fn(params, opt, batch)
            return params, opt

        p0 = m.init(jax.random.PRNGKey(0))
        o0 = adamw_init(p0)
        # continuous 6 steps
        pc, _ = run(6, p0, o0)
        # interrupted: 3 steps, checkpoint, restore, 3 more
        p1, o1 = run(3, p0, adamw_init(p0))
        ckpt.save(str(tmp_path), 3, {"p": p1, "o": o1})
        _, state, _ = ckpt.restore(str(tmp_path))
        pr, _ = run(6, state["p"], state["o"], start=3)
        for a, b in zip(jax.tree.leaves(pc), jax.tree.leaves(pr)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=1e-5, atol=1e-5,
            )


class TestFaultTolerance:
    def test_retry_recovers(self):
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise RuntimeError("transient")
            return "ok"

        assert retry(flaky, max_attempts=3, backoff_s=0.01) == "ok"

    def test_retry_exhausts(self):
        with pytest.raises(StepFailure):
            retry(lambda: 1 / 0, max_attempts=2, backoff_s=0.01)

    def test_straggler_detection(self):
        d = StragglerDetector(threshold=2.0)
        for s in range(10):
            d.record(s, 0.1)
        assert d.record(10, 0.5) is True
        assert 10 in d.flagged


class TestShardingRules:
    def _mesh(self):
        return make_mesh((1, 1), ("data", "model"))

    def test_param_divisibility_fallback(self):
        """smollm's 9 heads can't shard 16-way -> falls back, never errors."""
        mesh = make_mesh((1, 1), ("data", "model"))
        spec = shd.spec_for_param(mesh, (576, 576), ("embed", "heads"))
        assert len(spec) == 2

    def test_activation_spec_resolution(self):
        mesh = self._mesh()
        s = shd.spec_for_activation(mesh, "residual", (2, 32, 64))
        assert len(s) == 3

    def test_model_param_tree_shardings(self):
        mesh = self._mesh()
        cfg = get_config("smollm-135m", smoke=True)
        m = build_model(cfg)
        specs = m.param_specs()
        sh = shd.param_shardings(mesh, specs)
        assert jax.tree.structure(sh, is_leaf=lambda x: hasattr(x, "spec")) \
            .num_leaves == jax.tree.structure(specs).num_leaves

    def test_sharded_train_step_runs_under_mesh(self):
        """jit with in_shardings on a 1x1 mesh actually executes."""
        from repro.models.registry import make_train_batch
        from repro.training.train_loop import make_train_step

        mesh = self._mesh()
        cfg = get_config("smollm-135m", smoke=True)
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        opt = adamw_init(params)
        with shd.use_mesh(mesh):
            p_sh = shd.param_shardings(mesh, params)
            o_sh = shd.opt_state_shardings(mesh, params)
            batch = make_train_batch(cfg, ShapeConfig("s", 16, 2, "train"))
            b_sh = shd.batch_shardings(mesh, batch)
            fn = jax.jit(
                make_train_step(m, OptConfig()),
                in_shardings=(p_sh, o_sh, b_sh),
            )
            _, _, metrics = fn(params, opt, batch)
        assert np.isfinite(float(metrics["loss"]))


class TestServing:
    def test_engine_batched_requests(self):
        from repro.serving.engine import ServingEngine

        cfg = get_config("smollm-135m", smoke=True)
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        eng = ServingEngine(cfg, params, max_batch=2, max_seq=64)
        rng = np.random.default_rng(0)
        for _ in range(4):
            eng.submit(rng.integers(0, cfg.vocab, 8), max_new_tokens=4)
        reqs = eng.run()
        assert all(r.done and len(r.generated) == 4 for r in reqs)

    def test_greedy_decode_is_deterministic(self):
        from repro.serving.engine import ServingEngine

        cfg = get_config("smollm-135m", smoke=True)
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        prompt = np.arange(8) % cfg.vocab
        outs = []
        for _ in range(2):
            eng = ServingEngine(cfg, params, max_batch=1, max_seq=64)
            eng.submit(prompt, max_new_tokens=5)
            outs.append(eng.run()[0].generated)
        assert outs[0] == outs[1]
