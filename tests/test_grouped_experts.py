"""The dropless expert layer and the layer stack of a sparse-expert model
with leading dense layers and a 3:1 window/full attention pattern
(trinity-mini): the grouped matmul kernel, the sigmoid router, the
grouped workload's search space, extraction and dispatch."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.backends.pallas_backend import MatmulShape
from repro.backends.registry import get_backend
from repro.configs.base import get_config
from repro.core.modules import SpaceGenerator, default_modules
from repro.core.mutators import MutateTileSize
from repro.core.validator import validate_trace
from repro.core.workloads import get_workload, group_sizes, workload_inputs
from repro.integration.dispatch import DispatchContext
from repro.integration.extract import extract_task_specs
from repro.kernels.grouped_matmul import grouped_matmul
from repro.models import layers as L
from repro.models import transformer as T
from repro.models.registry import build_model
from repro.obs import RingBufferSink, configure_tracing, disable_tracing
from repro.search.database import Database, TuningRecord

RNG = np.random.default_rng(0)
SMOKE = get_config("trinity-mini", smoke=True)


def _ragged(x, w, sizes):
    return jax.lax.ragged_dot(
        x, w, jnp.asarray(sizes, jnp.int32), preferred_element_type=jnp.float32
    )


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sizes, blocks", [
    ([40, 0, 100, 16, 100, 0], (64, 128, 64)),   # empty groups
    ([0, 0, 256, 0, 0, 0], (64, 128, 64)),       # one group holds every row
    ([1, 7, 9, 100, 130, 9], (64, 128, 64)),     # many boundaries in a tile
    ([100, 156, 0, 0, 0, 0], (256, 256, 128)),   # one tile, two groups
    ([64, 64, 64, 64, 0, 0], (64, 128, 128)),    # boundaries on tile edges
], ids=["empty", "one-group", "boundaries", "one-tile", "aligned"])
def test_grouped_kernel_matches_ragged_dot(sizes, blocks):
    M, K, N, G = 256, 128, 256, 6
    x = jnp.asarray(RNG.standard_normal((M, K)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((G, K, N)), jnp.float32)
    got = grouped_matmul(
        x, w, jnp.asarray(sizes, jnp.int32), block_sizes=blocks, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_ragged(x, w, sizes)), rtol=2e-4, atol=2e-3
    )


def test_grouped_kernel_in_bf16_accumulates_in_f32():
    sizes = [30, 0, 70, 100, 56]
    x = jnp.asarray(RNG.standard_normal((256, 256)), jnp.bfloat16)
    w = jnp.asarray(RNG.standard_normal((5, 256, 128)), jnp.bfloat16)
    got = grouped_matmul(x, w, jnp.asarray(sizes, jnp.int32),
                         block_sizes=(32, 128, 128), interpret=True)
    want = _ragged(x, w, sizes)
    assert got.dtype == jnp.bfloat16
    # float32 sums in another order, each rounded once to bf16: at most
    # one bf16 step (2**-8 of the value) apart
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), rtol=2**-8, atol=1e-6)


# ---------------------------------------------------------------------------
# the workload and its space
# ---------------------------------------------------------------------------


def test_grouped_workload_is_the_grouped_matmul_of_its_mask():
    from repro.core.tir import evaluate_primfunc

    f = get_workload("grouped_dense", m=64, g=4, k=32, n=32, dtype="float32")
    ins = workload_inputs(f, 5)
    sizes = ins["MASK"].sum(0).astype(np.int32)
    assert sizes.sum() == 64 and np.all(ins["MASK"].sum(1) == 1)
    np.testing.assert_allclose(
        evaluate_primfunc(f, ins)["Y"],
        np.asarray(_ragged(ins["X"], ins["W"], sizes)), rtol=1e-5, atol=1e-5,
    )


def test_measured_group_sizes_are_seeded_and_uneven():
    a, b = group_sizes(131072, 128, 0), group_sizes(131072, 128, 0)
    np.testing.assert_array_equal(a, b)
    assert a.sum() == 131072 and a.max() > 2 * a.min()
    assert not np.array_equal(a, group_sizes(131072, 128, 1))


def test_grouped_space_samples_the_kernels_legal_block():
    f = get_workload("grouped_dense", m=256, g=4, k=256, n=256)
    legal = MatmulShape(1, 256, 256, 256, 2, groups=4).legal_blocks()
    space = SpaceGenerator(default_modules(use_mxu=True))
    rng = np.random.default_rng(0)
    for seed in range(4):
        sch = space.generate(f, seed=seed)
        (inst,) = [i for i in sch.trace.insts if i.name == "sample_mxu_block"]
        assert inst.attrs["groups"] == 4 and tuple(inst.decision) in legal
        v = validate_trace(f, sch.trace)
        meta = get_backend("pallas-interpret").lower(v.schedule, "k").meta
        assert meta["pallas_kernel"] == "grouped_matmul"
        assert meta["pallas_blocks_sampled"] == meta["pallas_blocks_snapped"]
        mutated = MutateTileSize().apply(f, sch.trace, rng)
        (m_inst,) = [i for i in mutated.insts if i.name == "sample_mxu_block"]
        assert tuple(m_inst.decision) in legal
        assert validate_trace(f, mutated).ok


def test_grouped_grid_counts_a_visit_per_group_boundary():
    shape = MatmulShape(1, 1024, 256, 256, 2, groups=8)
    assert shape.grid_steps((256, 128, 128)) == (4 + 7) * 2 * 2


# ---------------------------------------------------------------------------
# the router and the expert layer
# ---------------------------------------------------------------------------


def test_sigmoid_router_bias_changes_selection_not_weight():
    # one token, 4 experts: scores sigmoid([2, 1, 0, -1]); the bias lifts
    # expert 3 above expert 1 for the choice, and the weights stay its
    # own scores over the chosen ones' sum, times the scale
    p = {"router": jnp.eye(4, dtype=jnp.float32),
         "expert_bias": jnp.asarray([0.0, 0.0, 0.0, 0.6], jnp.float32)}
    x = jnp.asarray([[2.0, 1.0, 0.0, -1.0]])
    idx, w = L.moe_route(p, x, 2, "sigmoid", 2.5)
    s = 1 / (1 + np.exp(-np.asarray([2.0, 1.0, 0.0, -1.0])))
    assert idx.tolist() == [[0, 3]]  # 0.88, 0.27+0.6 beat 0.73
    np.testing.assert_allclose(
        np.asarray(w)[0], 2.5 * s[[0, 3]] / s[[0, 3]].sum(), rtol=1e-6)
    p0 = dict(p, expert_bias=jnp.zeros(4, jnp.float32))
    idx0, w0 = L.moe_route(p0, x, 2, "sigmoid", 2.5)
    assert idx0.tolist() == [[0, 1]]
    np.testing.assert_allclose(
        np.asarray(w0)[0], 2.5 * s[[0, 1]] / s[[0, 1]].sum(), rtol=1e-6)


def test_dropless_experts_match_a_per_token_loop():
    cfg = dataclasses.replace(SMOKE, dtype="float32")
    p = L.moe_init(jax.random.PRNGKey(3), cfg, "moe")
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    p["expert_bias"] = jnp.asarray(RNG.uniform(-0.1, 0.1, 8), jnp.float32)
    x = jnp.asarray(RNG.standard_normal((2, 5, cfg.d_model)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(L.moe(p, x, cfg))
        idx, w = L.moe_route(p, x.reshape(10, -1), 2, "sigmoid", 2.826)
        want = np.array(L.mlp(p["shared"], x, "silu")).reshape(10, -1)
        for t in range(10):
            for s in range(2):
                e = int(idx[t, s])
                pe = {k: p[k][e] for k in ("wi", "wg", "wo")}
                want[t] += float(w[t, s]) * np.asarray(L.mlp(pe, x.reshape(10, -1)[t], "silu"))
    np.testing.assert_allclose(got.reshape(10, -1), want, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# the layer stack
# ---------------------------------------------------------------------------


def test_three_to_one_windows_and_two_stacks():
    cfg = get_config("trinity-mini")
    w = T.layer_windows(cfg)
    assert w[:8].tolist() == [2048, 2048, 2048, 0, 2048, 2048, 2048, 0]
    assert T.layer_stacks(cfg) == [("dense_layers", 0, 2), ("layers", 2, 32)]
    six = dataclasses.replace(cfg, n_layers=6)
    assert T.window_period(w[:2]) == 1 and T.window_period(T.layer_windows(six)[2:]) == 4


def test_decode_and_serve_are_refused_for_leading_dense_layers():
    model = build_model(SMOKE)
    with pytest.raises(NotImplementedError, match="leading dense"):
        model.init_cache(2, 32)
    with pytest.raises(NotImplementedError, match="leading dense"):
        jax.eval_shape(
            lambda: T.decode_step(SMOKE, T.param_specs(SMOKE), {}, jnp.zeros((2, 1), jnp.int32))
        )


def test_global_layers_carry_no_rotary():
    """Shifting every position by one changes nothing on a NoPE layer: the
    global layer's output at the last position depends on positions only
    through the causal mask."""
    cfg = dataclasses.replace(SMOKE, dtype="float32")
    p = L.attention_init(jax.random.PRNGKey(0), cfg, "attn")
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    x = jnp.asarray(RNG.standard_normal((1, 8, cfg.d_model)), jnp.float32)
    pos = jnp.arange(8)[None]
    glob = [T._attn_full(cfg, p, x, pos + s, None)[0] for s in (0, 5)]
    local = [T._attn_full(cfg, p, x, pos + s, 4)[0] for s in (0, 5)]
    np.testing.assert_allclose(np.asarray(glob[0]), np.asarray(glob[1]), atol=1e-5)
    assert float(jnp.max(jnp.abs(local[0] - local[1]))) < 1e-4  # rotary is relative
    nope_off = dataclasses.replace(cfg, nope_global=False)
    roped = [T._attn_full(nope_off, p, x, pos * (s + 1), None)[0] for s in (0, 1)]
    assert float(jnp.max(jnp.abs(roped[0] - roped[1]))) > 1e-3


# ---------------------------------------------------------------------------
# extraction and dispatch
# ---------------------------------------------------------------------------


def test_six_layer_stack_extracts_both_attention_and_grouped_keys():
    cfg = dataclasses.replace(get_config("trinity-mini"), n_layers=6, vocab=25088)
    sink = RingBufferSink()
    configure_tracing(sink=sink)
    try:
        specs = extract_task_specs(
            cfg, batch=2, seq=8192, dispatchable_only=True, mesh=None
        )
    finally:
        disable_tracing()
    events = sink.events
    keys = {s.key: s for s in specs}
    attn = sorted(k for k in keys if k.startswith("attention/"))
    assert attn == [
        "attention/b=2/causal=1/d=128/h=32/kvh=4/s=8192/softcap=0.0/window=0",
        "attention/b=2/causal=1/d=128/h=32/kvh=4/s=8192/softcap=0.0/window=2048",
    ]
    assert keys[attn[0]].weight == 1 and keys[attn[1]].weight == 5
    grouped = sorted(k for k in keys if k.startswith("grouped_dense/"))
    assert grouped == [
        "grouped_dense/dtype=bfloat16/g=128/k=1024/m=131072/n=2048",
        "grouped_dense/dtype=bfloat16/g=128/k=2048/m=131072/n=1024",
    ]
    assert keys[grouped[0]].weight == 4 and keys[grouped[1]].weight == 8
    skips = [e for e in events if e["ev"] == "extract.skip"]
    assert not any(e.get("reason") == "traced_window" for e in skips)
    seen = [e for e in events if e["ev"] == "extract.grouped"]
    assert sorted((e["k"], e["n"], e["layers"]) for e in seen) == [
        (1024, 2048, 4.0), (2048, 1024, 8.0)]
    assert all(e["rows"] == 131072 and e["groups"] == 128 for e in seen)


def test_dispatch_hits_the_grouped_keys():
    specs = extract_task_specs(
        SMOKE, batch=2, seq=16, dispatchable_only=True, mesh=None,
        min_task_elems=16,
    )
    tasks = [s.to_tune_task(use_mxu=True) for s in specs]
    db = Database()
    space = SpaceGenerator(default_modules(use_mxu=True))
    for t in tasks:
        if t.key.startswith("grouped_dense/"):
            sch = space.generate(t.func, seed=0)
            db.put(TuningRecord(t.key, sch.trace.to_json(), 1e-3))
    model = build_model(SMOKE)
    params = model.init(jax.random.PRNGKey(1))
    toks = jnp.asarray(RNG.integers(0, SMOKE.vocab, (2, 16)))
    fwd = jax.jit(lambda p, t: model.forward(p, tokens=t))
    want = fwd(params, toks)
    ctx = DispatchContext(db, tasks=tasks, backend="pallas-interpret")
    with ctx:
        got = jax.jit(lambda p, t: model.forward(p, tokens=t))(params, toks)
    rows = {k: r for k, r in ctx.stats_by_key().items() if k.startswith("grouped_dense/")}
    assert sorted(r["hits"] for r in rows.values()) == [4, 8]
    assert all(r["misses"] == 0 for r in rows.values())
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
