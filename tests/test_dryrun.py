"""Dry-run machinery: collective parser, mesh factory, and a multi-device
lowering in a subprocess (device count locks at first jax init, so the
512-device production path cannot run inside this pytest process)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.launch.dryrun import parse_collective_bytes

SAMPLE_HLO = """
  %ag = bf16[16,1024]{1,0} all-gather(%p0), replica_groups={{0,1}}
  %ar = f32[256]{0} all-reduce(%x), to_apply=%add
  %rs = f32[8,8]{1,0} reduce-scatter(%y), dimensions={0}
  %a2a = bf16[4,4,4]{2,1,0} all-to-all(%z), dimensions={0}
  %cp = u8[128]{0} collective-permute(%w), source_target_pairs={{0,1}}
  %notacoll = f32[2]{0} add(%a, %b)
"""


class TestCollectiveParser:
    def test_counts_and_bytes(self):
        out = parse_collective_bytes(SAMPLE_HLO)
        assert out["count"] == 5
        assert out["all-gather"] == 16 * 1024 * 2
        assert out["all-reduce"] == 256 * 4
        assert out["reduce-scatter"] == 64 * 4
        assert out["all-to-all"] == 64 * 2
        assert out["collective-permute"] == 128

    def test_ignores_non_collectives(self):
        out = parse_collective_bytes("%x = f32[4]{0} add(%a, %b)")
        assert out["count"] == 0


class TestMeshFactory:
    def test_shapes(self):
        # importing must not touch devices; constructing uses this process's
        # CPU (1 device) so just validate the arithmetic via the docstring
        from repro.launch import mesh as M

        assert M.make_production_mesh.__doc__
        # host mesh works on 1 device
        m = M.make_host_mesh()
        assert m.axis_names == ("data", "model")


@pytest.mark.slow
class TestMultiDeviceLowering:
    def test_smoke_cell_lowers_on_8_fake_devices(self):
        """End-to-end mini dry-run: 2x4 mesh, smoke config, train+decode."""
        code = textwrap.dedent(
            """
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import jax, json
            import jax.numpy as jnp
            from repro.configs.base import get_config, ShapeConfig
            from repro.distributed import sharding as shd
            from repro.launch.mesh import make_mesh
            from repro.models.registry import (
                build_model, train_batch_specs, decode_input_specs)
            from repro.training.optimizer import OptConfig, adamw_init
            from repro.training.train_loop import make_train_step

            mesh = make_mesh((2, 4), ("data", "model"))
            cfg = get_config("smollm-135m", smoke=True)
            model = build_model(cfg)
            pspecs = model.param_specs()
            shape = ShapeConfig("t", 64, 8, "train")
            with shd.use_mesh(mesh):
                p_sh = shd.param_shardings(mesh, pspecs)
                o_sh = shd.opt_state_shardings(mesh, pspecs)
                b = train_batch_specs(cfg, shape)
                b_sh = shd.batch_shardings(mesh, b)
                fn = jax.jit(make_train_step(model, OptConfig()),
                             in_shardings=(p_sh, o_sh, b_sh))
                lowered = fn.lower(pspecs, jax.eval_shape(adamw_init, pspecs), b)
                compiled = lowered.compile()
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            print(json.dumps({
                "ok": True,
                "flops": cost.get("flops", 0.0),
                "devices": len(jax.devices()),
            }))
            """
        )
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
        r = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=420, env=env,
        )
        assert r.returncode == 0, r.stderr[-3000:]
        out = json.loads(r.stdout.strip().splitlines()[-1])
        assert out["ok"] and out["devices"] == 8 and out["flops"] > 0


class TestDryrunResults:
    """Validate whatever cells the background sweep has produced so far."""

    def test_completed_cells_are_coherent(self):
        d = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun")
        if not os.path.isdir(d):
            pytest.skip("no dry-run results yet")
        files = [f for f in os.listdir(d) if f.endswith(".json")]
        if not files:
            pytest.skip("no dry-run results yet")
        n_ok = 0
        for f in files:
            rec = json.load(open(os.path.join(d, f)))
            assert rec["status"] in ("ok", "skipped", "error"), f
            if rec["status"] == "ok":
                n_ok += 1
                assert rec["n_devices"] in (256, 512)
                assert rec["cost"]["flops"] is None or rec["cost"]["flops"] > 0
        assert n_ok >= 1
