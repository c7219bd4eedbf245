"""Process-level device set-up: compile-cache placement and the
one-process-per-chip guard of every path that starts JAX workers."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import runtime


@pytest.fixture
def restore_cache_config():
    from jax.experimental.compilation_cache import compilation_cache

    keys = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
    )
    prev = {k: getattr(jax.config, k) for k in keys}
    compilation_cache.reset_cache()
    yield
    for k, v in prev.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def _entries(path) -> set:
    return set(os.listdir(path)) if os.path.isdir(path) else set()


def _compile_something_new():
    # a constant no other test uses: a fresh program, so a fresh entry
    c = float(np.random.default_rng().integers(1, 1 << 30))
    jax.block_until_ready(jax.jit(lambda x: x * c + 1.0)(jnp.ones(3)))


class TestCompileCache:
    def test_env_dir_is_used(self, monkeypatch, tmp_path, restore_cache_config):
        want = str(tmp_path / "cache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
        assert runtime.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        _compile_something_new()
        assert _entries(want)

    def test_fixed_checkout_dir_otherwise(
        self, monkeypatch, restore_cache_config
    ):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = runtime.enable_compile_cache()
        assert path == str(runtime.DEFAULT_COMPILE_CACHE_DIR)
        assert (runtime.REPO_ROOT / "pyproject.toml").is_file()
        before = _entries(path)
        _compile_something_new()
        assert _entries(path) - before


@pytest.fixture
def fake_host(monkeypatch, tmp_path):
    """A host described on disk: a PCI bus with four v5e chips (and a
    Google NIC) in IOMMU groups 10-13, of which the container may open
    the groups it is given.  Returns that function."""
    pci, vfio = tmp_path / "pci", tmp_path / "vfio"
    pci.mkdir()
    nic = pci / "0000:00:04.0"
    nic.mkdir()
    (nic / "vendor").write_text("0x1ae0\n")
    (nic / "device").write_text("0x0042\n")
    for i, addr in enumerate(("0000:00:05.0", "0000:00:06.0",
                              "0000:00:07.0", "0000:00:08.0")):
        d = pci / addr
        d.mkdir()
        (d / "vendor").write_text("0x1ae0\n")
        (d / "device").write_text("0x0063\n")
        (d / "iommu_group").symlink_to(tmp_path / "iommu_groups" / str(10 + i))
    monkeypatch.setattr(runtime, "SYSFS_PCI", str(pci))
    monkeypatch.setattr(runtime, "DEV_VFIO", str(vfio))
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")

    def give(*groups):
        vfio.mkdir()
        (vfio / "vfio").touch()
        for g in groups:
            (vfio / str(g)).touch()

    return give


class TestChipGuard:
    def test_cpu_host_pins_nothing(self):
        assert runtime.host_tpu_chips() == []  # JAX_PLATFORMS keeps JAX on CPU
        assert runtime.chip_child_envs(3) == [{}, {}, {}]

    def test_one_chip_each(self, fake_host, monkeypatch):
        fake_host(10, 11, 12, 13)
        monkeypatch.setattr(runtime, "_holds_chips", lambda: False)
        assert runtime.host_tpu_chips() == [0, 1, 2, 3]
        envs = runtime.chip_child_envs(4)
        assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
        assert all(e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1" for e in envs)
        assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4

    def test_partial_host_pins_the_chips_it_was_given(
        self, fake_host, monkeypatch
    ):
        fake_host(11, 13)  # the 2nd and 4th chip on the bus
        monkeypatch.setattr(runtime, "_holds_chips", lambda: False)
        assert runtime.host_tpu_chips() == [1, 3]
        envs = runtime.chip_child_envs(2)
        assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["1", "3"]
        with pytest.raises(RuntimeError, match="one process per chip"):
            runtime.chip_child_envs(3)

    def test_more_workers_than_chips_refused(self, monkeypatch):
        monkeypatch.setattr(runtime, "host_tpu_chips", lambda: [0])
        monkeypatch.setattr(runtime, "_holds_chips", lambda: False)
        with pytest.raises(RuntimeError, match="one process per chip"):
            runtime.chip_child_envs(2)

    def test_parent_holding_the_chip_refused(self, fake_host, tmp_path):
        fake_host(10)
        assert not runtime._holds_chips()
        # an open chip node, as the TPU runtime keeps one once JAX starts
        with open(tmp_path / "vfio" / "10"):
            assert runtime._holds_chips()
            with pytest.raises(RuntimeError, match="holds a TPU chip"):
                runtime.chip_child_envs(1)
        assert runtime.chip_child_envs(1)[0]["TPU_VISIBLE_CHIPS"] == "0"

    def test_pool_defaults_to_one_worker_per_chip_and_refuses_more(
        self, monkeypatch
    ):
        from repro.search.measure.pool import ProcessPoolRunner

        monkeypatch.setattr(runtime, "host_tpu_chips", lambda: [0])
        assert ProcessPoolRunner(backend="jnp").max_workers == 1
        r = ProcessPoolRunner(max_workers=2, backend="jnp")
        with pytest.raises(RuntimeError, match="one process per chip"):
            r.warm()
        assert r._executor is None  # nothing was spawned

    def test_rpc_workers_refused(self, monkeypatch):
        from repro.search.measure.rpc import spawn_local_workers

        monkeypatch.setattr(runtime, "host_tpu_chips", lambda: [0])
        with pytest.raises(RuntimeError, match="one process per chip"):
            spawn_local_workers(2, backend="jnp")

    def test_router_workers_refused(self, monkeypatch):
        from repro.serving.router import spawn_serving_workers

        monkeypatch.setattr(runtime, "host_tpu_chips", lambda: [0])
        with pytest.raises(RuntimeError, match="one process per chip"):
            spawn_serving_workers(2)
