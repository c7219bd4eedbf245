"""Transformation modules, space generation, validator, mutators, search."""

import numpy as np
import pytest

from repro.backends import jnp_backend as J
from repro.core import workloads as W
from repro.core.modules import (
    AutoInline,
    MultiLevelTiling,
    ParallelizeVectorizeUnroll,
    SpaceGenerator,
    UseMXU,
    default_modules,
)
from repro.core.mutators import mutate
from repro.core.tir import evaluate_primfunc, random_inputs
from repro.core.validator import validate_trace
from repro.search.cost_model import GBDTCostModel
from repro.search.database import Database, TuningRecord
from repro.search.evolutionary import SearchConfig
from repro.search.features import extract_features
from repro.search.tune import apply_best, tune_workload

SPACE_WORKLOADS = ["gmm", "sfm", "c2d", "dense", "dep", "relu"]


class TestSpaceGeneration:
    @pytest.mark.parametrize("name", SPACE_WORKLOADS)
    def test_generated_schedules_preserve_semantics(self, name):
        f = W.get_workload(name, **W.REDUCED_KWARGS.get(name, {}))
        ins = random_inputs(f, 11)
        ref = evaluate_primfunc(f, ins)
        gen = SpaceGenerator(default_modules(use_mxu=name in ("gmm", "dense")))
        checked = 0
        for s in range(8):
            sch = gen.generate(f, seed=100 + s)
            res = validate_trace(f, sch.trace)
            if not res.ok:
                continue
            got = J.build(res.schedule).jit()(ins)
            for k in ref:
                np.testing.assert_allclose(
                    np.asarray(got[k]), ref[k], rtol=3e-4, atol=1e-4
                )
            checked += 1
        assert checked >= 3, f"space for {name} produced too few valid samples"

    def test_spaces_are_diverse(self):
        f = W.gmm(n=32, m=32, k=32)
        gen = SpaceGenerator(default_modules())
        scripts = {gen.generate(f, seed=s).script() for s in range(8)}
        assert len(scripts) >= 4

    def test_use_mxu_composes(self):
        """Fig 5: the hardware module composes with generic ones."""
        f = W.dense(m=32, n=32, k=32, epilogue="bias_relu")
        gen = SpaceGenerator(
            [AutoInline(), UseMXU(), MultiLevelTiling(),
             ParallelizeVectorizeUnroll()]
        )
        found_mxu = False
        for s in range(6):
            sch = gen.generate(f, seed=s)
            if any(i.name == "tensorize_mxu" for i in sch.trace.insts):
                found_mxu = True
        assert found_mxu


class TestMutation:
    def test_mutations_stay_semantic_or_rejected(self):
        f = W.gmm(n=32, m=32, k=32)
        ins = random_inputs(f, 0)
        gen = SpaceGenerator(default_modules())
        rng = np.random.default_rng(0)
        sch = gen.generate(f, seed=5)
        base = validate_trace(f, sch.trace)
        assert base.ok
        n_valid = 0
        for _ in range(10):
            t = mutate(f, sch.trace, rng)
            if t is None:
                continue
            res = validate_trace(f, t)
            if res.ok:
                got = J.build(res.schedule).jit()(ins)
                np.testing.assert_allclose(
                    np.asarray(got["C"]), ins["A"] @ ins["B"], rtol=3e-4,
                    atol=1e-4,
                )
                n_valid += 1
        assert n_valid >= 3


class TestCostModel:
    def test_gbdt_fits_monotone_signal(self):
        rng = np.random.default_rng(0)
        X = rng.standard_normal((200, 8)).astype(np.float32)
        y = (X[:, 0] * 2 + np.sin(X[:, 1])) + 0.01 * rng.standard_normal(200)
        m = GBDTCostModel(n_trees=40)
        m.update(X[:150], y[:150])
        pred = m.predict(X[150:])
        corr = np.corrcoef(pred, y[150:])[0, 1]
        assert corr > 0.8

    def test_features_shape_stable(self):
        f = W.gmm(n=32, m=32, k=32)
        gen = SpaceGenerator(default_modules())
        dims = {
            extract_features(gen.generate(f, seed=s)).shape for s in range(3)
        }
        assert len(dims) == 1


class TestSearch:
    def test_search_improves_over_first_sample(self, tmp_path):
        db = Database(str(tmp_path / "db.json"))
        res = tune_workload(
            "gmm",
            dict(n=64, m=64, k=64),
            use_mxu=True,
            config=SearchConfig(
                max_trials=16, init_random=6, population=8,
                measure_per_round=5, generations=2,
            ),
            database=db,
        )
        assert np.isfinite(res.best_latency_s)
        first_measured = res.history[0][1]
        assert res.best_latency_s <= first_measured
        # database roundtrip -> executable
        sch, low = apply_best("gmm", db, dict(n=64, m=64, k=64))
        import jax

        ins = random_inputs(low.func, 0)
        out = jax.jit(low.fn)(ins)
        np.testing.assert_allclose(
            np.asarray(out["C"]), ins["A"] @ ins["B"], rtol=1e-3, atol=1e-3
        )

    def test_database_topk_and_persistence(self, tmp_path):
        p = str(tmp_path / "db.json")
        db = Database(p, top_k=2)
        # distinct traces: records for an identical trace are deduplicated
        for i, lat in enumerate([3.0, 1.0, 2.0]):
            db.put(TuningRecord("k1", f'[{{"t": {i}}}]', lat))
        assert [r.latency_s for r in db.top("k1", 5)] == [1.0, 2.0]
        db2 = Database(p)
        assert db2.best("k1").latency_s == 1.0
        # re-measuring the same trace keeps one (best) record
        db.put(TuningRecord("k1", '[{"t": 1}]', 1.5))
        assert [r.latency_s for r in db.top("k1", 5)] == [1.0, 2.0]


# ---------------------------------------------------------------------------
# The matmul kernel's block space (UseMXU on dense_* / batch_matmul)
# ---------------------------------------------------------------------------

from repro.backends.pallas_backend import (  # noqa: E402
    MAX_GRID_STEPS,
    MatmulShape,
    lower_to_pallas,
    matmul_shape,
)
from repro.core.trace import Trace  # noqa: E402
from repro.kernels.matmul import (  # noqa: E402
    VMEM_LIMIT_BYTES,
    block_vmem_bytes,
    vmem_budget,
)
from repro.search.distributions import (  # noqa: E402
    DecisionDistributions,
    decision_site_key,
)

# (m, k, n) of x (m, k) @ w (k, n): stablelm-3b at 4 x 2048 tokens
# (up/gate, q/k/v/o, down, unembed) and smollm-135m's q/o at 16384 tokens
MXU_SHAPES = {
    "stablelm_up": (8192, 2560, 6912),
    "stablelm_qkvo": (8192, 2560, 2560),
    "stablelm_down": (8192, 6912, 2560),
    "stablelm_unembed": (8192, 2560, 50304),
    "smollm_qo": (16384, 576, 576),
}


def _block_of(trace):
    (inst,) = [i for i in trace.insts if i.name == "sample_mxu_block"]
    return tuple(inst.decision)


def _assert_legal(func, trace):
    shape = matmul_shape(func)
    bm, bn, bk = block = _block_of(trace)
    m, n, k = shape.dims
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    assert bm % 8 == 0 or bm == m  # float32 sublane tile
    assert bn % 128 == 0 or bn == n
    assert bk % 128 == 0 or bk == k
    assert block_vmem_bytes(bm, bn, bk, 4) <= vmem_budget(4)
    assert shape.grid_steps(block) <= MAX_GRID_STEPS
    replayed = Trace.from_json(trace.to_json())
    res = validate_trace(func, replayed)
    assert res.ok, res.reason
    assert _block_of(res.schedule.trace) == block
    _, meta = lower_to_pallas(res.schedule, interpret=True)
    assert tuple(meta["pallas_blocks_snapped"]) == block
    assert tuple(meta["pallas_blocks_sampled"]) == block
    assert "pallas_blocks_adjusted" not in meta


class TestMXUBlockSpace:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    @pytest.mark.parametrize("name", sorted(MXU_SHAPES))
    def test_sampled_and_mutated_blocks_are_legal(self, name, seed):
        m, k, n = MXU_SHAPES[name]
        f = W.dense(m=m, n=n, k=k)
        gen = SpaceGenerator(default_modules(use_mxu=True))
        rng = np.random.default_rng(seed)
        for s in range(4):
            trace = gen.generate(f, seed=100 * seed + s).trace
            _assert_legal(f, trace)
            mutated = 0
            for _ in range(8):
                t = mutate(f, trace, rng)
                if t is None or not any(
                    i.name == "sample_mxu_block" for i in t.insts
                ):
                    continue
                v = validate_trace(f, t)
                assert v.ok, v.reason
                _assert_legal(f, v.schedule.trace)
                mutated += 1
            assert mutated

    def test_smollm_blocks_fit_scoped_vmem(self):
        # the record that asked for 50 MB of scoped VMEM on the chip had
        # no bound on its block: every block the space holds now fits
        shape = matmul_shape(W.dense(m=16384, n=576, k=576))
        legal = shape.legal_blocks()
        assert legal
        assert {(bn, bk) for _, bn, bk in legal} == {(576, 576)}
        assert max(shape.vmem_bytes(b) for b in legal) <= VMEM_LIMIT_BYTES
        assert (4096, 576, 576) not in legal

    def test_legal_set_follows_operand_width(self):
        f32 = MatmulShape(1, 64, 256, 256, 4)
        bf16 = MatmulShape(1, 64, 256, 256, 2)
        assert {b[0] for b in f32.legal_blocks()} == {8, 16, 32, 64}
        assert {b[0] for b in bf16.legal_blocks()} == {16, 32, 64}
        # the bias row is part of the footprint
        assert MatmulShape(1, 64, 256, 256, 4, bias=True).vmem_bytes(
            (64, 256, 256)
        ) > f32.vmem_bytes((64, 256, 256))

    def test_batch_matmul_samples_a_block_per_batch(self):
        f = W.batch_matmul(b=4, m=64, n=256, k=128)
        gen = SpaceGenerator(default_modules(use_mxu=True))
        for s in range(4):
            res = validate_trace(f, gen.generate(f, seed=s).trace)
            assert res.ok, res.reason
            block = _block_of(res.schedule.trace)
            assert block in matmul_shape(f).legal_blocks()
            _, meta = lower_to_pallas(res.schedule, interpret=True)
            assert tuple(meta["pallas_blocks_snapped"]) == block
            assert "pallas_blocks_adjusted" not in meta

    def test_learned_draws_stay_legal(self):
        f = W.dense(m=8192, n=6912, k=2560)
        gen = SpaceGenerator(default_modules(use_mxu=True))
        dists = DecisionDistributions(explore=0.0)
        for s in range(6):
            dists.observe_trace(gen.generate(f, seed=s).trace)
        dists.fit()
        trace = gen.generate(f, seed=99).trace
        (inst,) = [i for i in trace.insts if i.name == "sample_mxu_block"]
        assert decision_site_key(inst) == (
            "mxu/shape=1,8192,6912,2560/itemsize=4/bias=0"
        )
        rng = np.random.default_rng(0)
        for _ in range(6):
            decs = dists.decisions_for(trace, rng)
            res = validate_trace(f, trace.with_decisions(decs))
            assert res.ok, res.reason
            _assert_legal(f, res.schedule.trace)

    def test_out_of_set_block_fails_replay(self):
        f = W.dense(m=8192, n=6912, k=2560)
        trace = SpaceGenerator(default_modules(use_mxu=True)).generate(
            f, seed=0
        ).trace
        idx = next(
            i for i, it in enumerate(trace.insts)
            if it.name == "sample_mxu_block"
        )
        for block in ([4096, 6912, 2560], [100, 128, 128]):
            res = validate_trace(f, trace.with_decision(idx, block))
            assert not res.ok and res.kind == "replay"


class TestKernelValidation:
    def _mxu_perfect_tile_trace(self, f):
        """A tensorized dense block tiled by plain splits, as traces
        recorded before the block was sampled are."""
        from repro.core.schedule import Schedule

        sch = Schedule(f, seed=0)
        block = sch.get_block("dense")
        sch.tensorize_mxu(block)
        return sch, block, sch.get_loops(block)

    @pytest.mark.parametrize(
        "tiles,kind",
        [((4096, 6912, 2560), "vmem"), ((8, 128, 8), "iteration")],
        ids=["vmem", "grid"],
    )
    def test_kernel_limits_reject_old_traces(self, tiles, kind):
        f = W.dense(m=8192, n=6912, k=2560)
        sch, block, loops = self._mxu_perfect_tile_trace(f)
        splits = [
            sch.split(loop, [loop_extent(sch, loop) // t, t])
            for loop, t in zip(loops, tiles)
        ]
        sch.reorder(*[o for o, _ in splits], *[inner for _, inner in splits])
        for _, inner in splits:
            sch.unroll(inner)
        res = validate_trace(f, sch.trace)
        assert not res.ok and res.kind == kind

    def test_other_blocks_keep_the_jnp_caps(self):
        # gmm has no matmul-kernel lowering: its tiles are capped as before
        f = W.gmm(n=1024, m=1024, k=1024)
        from repro.core.schedule import Schedule

        sch = Schedule(f, seed=0)
        block = sch.get_block("C")
        for loop in sch.get_loops(block):
            sch.unroll(loop)
        res = validate_trace(f, sch.trace)
        assert not res.ok and res.kind == "tile"


def loop_extent(sch, loop):
    return sch.loop_info(loop).extent
