"""Pallas kernel allclose sweeps (interpret mode) vs ref.py oracles."""

import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
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
