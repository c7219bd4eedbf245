"""Pallas kernel allclose sweeps (interpret mode) vs ref.py oracles."""

import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention, visit_schedule
from repro.kernels.matmul import matmul
from repro.kernels.ssd import ssd

RNG = np.random.default_rng(0)


class TestMatmulKernel:
    @pytest.mark.parametrize(
        "M,N,K,bm,bn,bk",
        [
            (128, 128, 128, 64, 64, 64),
            (256, 128, 64, 128, 128, 64),
            (64, 256, 128, 32, 128, 32),
            (128, 128, 128, 128, 128, 128),
            (32, 32, 32, 8, 8, 8),
        ],
    )
    def test_block_shape_sweep(self, M, N, K, bm, bn, bk):
        x = RNG.standard_normal((M, K), dtype=np.float32)
        w = RNG.standard_normal((K, N), dtype=np.float32)
        got = matmul(x, w, block_sizes=(bm, bn, bk), interpret=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref.matmul(x, w)), rtol=2e-4, atol=2e-4
        )

    @pytest.mark.parametrize(
        "ep", ["none", "bias", "bias_relu", "bias_gelu", "bias_silu", "softcap"]
    )
    def test_epilogue_sweep(self, ep):
        x = RNG.standard_normal((64, 32), dtype=np.float32)
        w = RNG.standard_normal((32, 64), dtype=np.float32)
        b = RNG.standard_normal((64,), dtype=np.float32) if "bias" in ep else None
        got = matmul(
            x, w, b, epilogue=ep, block_sizes=(32, 32, 32), interpret=True
        )
        want = ref.matmul(x, w, b, ep)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
        )

    @pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
    def test_dtype_sweep(self, dtype):
        import jax.numpy as jnp

        dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
        x = jnp.asarray(RNG.standard_normal((64, 64)), dtype=dt)
        w = jnp.asarray(RNG.standard_normal((64, 64)), dtype=dt)
        got = matmul(x, w, block_sizes=(32, 32, 32), interpret=True)
        want = ref.matmul(x, w)
        np.testing.assert_allclose(
            np.asarray(got, np.float32),
            np.asarray(want, np.float32),
            rtol=2e-2,
            atol=2e-2,
        )


class TestFlashAttentionKernel:
    @pytest.mark.parametrize(
        "B,H,KVH,S,D,causal,win,cap,bq,bkv",
        [
            (1, 4, 4, 256, 64, True, None, None, 64, 64),
            (2, 4, 2, 128, 32, True, 64, None, 64, 32),
            (1, 8, 2, 128, 64, True, None, 30.0, 32, 64),
            (1, 2, 1, 256, 64, False, None, None, 128, 128),
            (2, 6, 3, 64, 16, True, 16, 20.0, 32, 32),
            # folded causal grid, odd nq: 5 q blocks in 3 rows
            (1, 2, 2, 320, 32, True, None, None, 64, 64),
            # window band whose first two rows run past their diagonal
            (1, 2, 1, 512, 32, True, 128, None, 64, 64),
            # GQA with bq != bkv, causal and banded
            (2, 4, 2, 256, 32, True, None, None, 32, 64),
            (1, 6, 2, 384, 32, True, 96, None, 128, 64),
        ],
    )
    def test_variant_sweep(self, B, H, KVH, S, D, causal, win, cap, bq, bkv):
        q = RNG.standard_normal((B, H, S, D), dtype=np.float32) * 0.3
        k = RNG.standard_normal((B, KVH, S, D), dtype=np.float32) * 0.3
        v = RNG.standard_normal((B, KVH, S, D), dtype=np.float32)
        got = flash_attention(
            q, k, v, causal=causal, window=win, softcap=cap,
            block_q=bq, block_kv=bkv, interpret=True,
        )
        want = ref.flash_attention(q, k, v, causal=causal, window=win, softcap=cap)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3
        )


def _admitted(S, bq, bkv, causal, window):
    """The (q block, kv block) pairs holding a (query, key) pair the mask
    admits, from the elementwise mask as ``ref.flash_attention`` builds it."""
    d = np.arange(S)[:, None] - np.arange(S)[None, :]  # query - key
    mask = np.ones((S, S), bool)
    if causal:
        mask &= d >= 0
    if window is not None:
        mask &= d < window
    blocks = mask.reshape(S // bq, bq, S // bkv, bkv).any(axis=(1, 3))
    return set(zip(*map(list, np.nonzero(blocks))))


def _visits(sched):
    """(qi, ki, valid, first, last) of every grid step, rows in order and
    each row's steps in order."""
    r, j = np.meshgrid(np.arange(sched.rows), np.arange(sched.row_len),
                       indexing="ij")
    cols = sched.at(r, j, np)
    return list(zip(*(np.broadcast_to(c, r.shape).ravel().tolist()
                      for c in cols)))


class TestFlashSchedule:
    """The prefill kernel's visit schedule, without running the kernel."""

    @pytest.mark.parametrize(
        "S,bq,bkv,causal,window",
        [
            (512, 64, 64, True, None),  # even nq, folded
            (448, 64, 64, True, None),  # odd nq: the middle row runs alone
            (512, 128, 64, True, None),  # bq = 2 bkv
            (384, 128, 64, True, None),
            (512, 64, 128, True, None),  # bkv = 2 bq
            (768, 128, 256, True, None),
            (512, 64, 64, True, 40),  # window under one block
            (448, 64, 64, True, 100),  # window over one block
            (512, 64, 64, True, 65),  # window one past a block edge
            (512, 128, 64, True, 200),
            (768, 128, 256, True, 300),
            (384, 64, 128, True, 130),
            (512, 64, 64, True, 512),  # window as long as S
            (256, 64, 64, False, None),  # the full square
            (256, 64, 32, False, 100),
        ],
    )
    def test_visits_each_admitted_pair_once_in_order(
        self, S, bq, bkv, causal, window
    ):
        sched = visit_schedule(S, bq, bkv, causal, window)
        steps = _visits(sched)
        assert len(steps) == sched.steps <= sched.nq * sched.nkv
        computed = [(qi, ki) for qi, ki, valid, _, _ in steps if valid]
        assert len(computed) == len(set(computed))
        assert set(computed) == _admitted(S, bq, bkv, causal, window)
        # each q block's steps are one contiguous run of one row, its
        # computed kv blocks rising, flagged first and last at its ends
        at = {}
        for n, (qi, *_) in enumerate(steps):
            at.setdefault(qi, []).append(n)
        assert sorted(at) == list(range(sched.nq))
        for qi, ns in at.items():
            assert ns == list(range(ns[0], ns[-1] + 1))
            assert len({n // sched.row_len for n in ns}) == 1
            run = [steps[n] for n in ns]
            kis = [ki for _, ki, valid, _, _ in run if valid]
            assert kis == sorted(set(kis))
            assert [f for *_, f, _ in run] == [True] + [False] * (len(run) - 1)
            assert [l for *_, l in run] == [False] * (len(run) - 1) + [True]

    @pytest.mark.parametrize(
        "S,window,visited,computed",
        [
            (2048, None, "136/256", 136),  # stablelm-3b, causal
            (8192, None, "2080/4096", 2080),  # trinity-mini, global layer
            (8192, 2048, "1088/4096", 952),  # trinity-mini, sliding layers
        ],
    )
    def test_benchmark_step_counts(self, S, window, visited, computed):
        sched = visit_schedule(S, 128, 128, True, window)
        assert sched.visited == visited
        assert sum(valid for _, _, valid, _, _ in _visits(sched)) == computed


class TestSSDKernel:
    @pytest.mark.parametrize(
        "B,S,H,P,N,chunk",
        [
            (2, 128, 4, 32, 16, 32),
            (1, 64, 2, 16, 8, 16),
            (1, 256, 1, 64, 32, 64),
            (3, 32, 8, 8, 4, 8),
        ],
    )
    def test_shape_sweep_vs_recurrence(self, B, S, H, P, N, chunk):
        x = RNG.standard_normal((B, S, H, P), dtype=np.float32)
        la = -np.abs(RNG.standard_normal((B, S, H), dtype=np.float32)) * 0.3
        Bm = RNG.standard_normal((B, S, N), dtype=np.float32) * 0.3
        Cm = RNG.standard_normal((B, S, N), dtype=np.float32) * 0.3
        want = ref.ssd_scan(x, la, Bm, Cm)
        got = ssd(x, la, Bm, Cm, chunk=chunk, interpret=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=3e-3, atol=3e-3
        )

    def test_kernel_carries_its_identity(self):
        # Mosaic cannot lower ssd (cumsum), so the identity is checked on
        # the pallas_call itself rather than on a compiled TPU op
        import jax

        args = (np.ones((1, 64, 2, 8), np.float32), np.ones((1, 64, 2), np.float32),
                np.ones((1, 64, 4), np.float32), np.ones((1, 64, 4), np.float32))
        jaxpr = jax.make_jaxpr(
            lambda *a: ssd(*a, chunk=16, interpret=True, task="ssd/of=a_record")
        )(*args)
        (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        assert call.params["name"] == "ssd"
        assert dict(call.params["metadata"]) == {
            "task": "ssd/of=a_record", "blocks": "16", "dtype": "float32"}

    def test_chunked_ref_equals_scan(self):
        B, S, H, P, N = 2, 64, 2, 8, 4
        x = RNG.standard_normal((B, S, H, P), dtype=np.float32)
        la = -np.abs(RNG.standard_normal((B, S, H), dtype=np.float32)) * 0.2
        Bm = RNG.standard_normal((B, S, N), dtype=np.float32) * 0.3
        Cm = RNG.standard_normal((B, S, N), dtype=np.float32) * 0.3
        want = ref.ssd_scan(x, la, Bm, Cm)
        for chunk in (8, 16, 32):
            got = ref.ssd_chunked(x, la, Bm, Cm, chunk=chunk)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3
            )

    def test_final_state_matches_recurrence(self):
        import jax.numpy as jnp

        B, S, H, P, N = 1, 32, 2, 8, 4
        x = RNG.standard_normal((B, S, H, P), dtype=np.float32)
        la = -np.abs(RNG.standard_normal((B, S, H), dtype=np.float32)) * 0.2
        Bm = RNG.standard_normal((B, S, N), dtype=np.float32) * 0.3
        Cm = RNG.standard_normal((B, S, N), dtype=np.float32) * 0.3
        _, h = ref.ssd_chunked(x, la, Bm, Cm, chunk=8, return_state=True)
        # recurrence state
        hr = np.zeros((B, H, N, P), np.float32)
        for t in range(S):
            a = np.exp(la[:, t])  # (B,H)
            hr = a[:, :, None, None] * hr + np.einsum(
                "bn,bhp->bhnp", Bm[:, t], x[:, t]
            )
        np.testing.assert_allclose(np.asarray(h), hr, rtol=2e-3, atol=2e-3)


class TestTraceToPallas:
    def test_tuned_trace_lowers_to_pallas_kernel(self):
        """MetaSchedule trace -> BlockSpec extraction -> Pallas matmul."""
        from repro.backends.pallas_backend import lower_dense
        from repro.core.modules import SpaceGenerator, default_modules
        from repro.core.tir import random_inputs
        from repro.core.validator import validate_trace
        from repro.core.workloads import dense

        f = dense(m=128, n=128, k=64, epilogue="bias_relu")
        gen = SpaceGenerator(default_modules(use_mxu=True))
        done = 0
        for s in range(20):
            sch = gen.generate(f, seed=s)
            res = validate_trace(f, sch.trace)
            if not res.ok:
                continue
            fn, _ = lower_dense(res.schedule, interpret=True)
            ins = random_inputs(f, 1)
            out = fn(ins)
            want = ref.matmul(ins["X"], ins["W"], ins["bias"], "bias_relu")
            np.testing.assert_allclose(
                np.asarray(out["R"]), np.asarray(want), rtol=2e-3, atol=2e-3
            )
            done += 1
            if done >= 2:
                break
        assert done >= 2
