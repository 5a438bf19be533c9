"""Process set-up helpers: the mesh helper, the compile cache, and the
host-device flag that stands in for chips only on the CPU."""
from __future__ import annotations

import os

import jax
import pytest
from jax.sharding import AxisType

from repro.core.context import make_mesh
from repro.launch import env as E


def test_make_mesh_gives_auto_axes():
    mesh = make_mesh((1, 1), ("data", "model"))
    assert mesh.axis_names == ("data", "model")
    assert tuple(mesh.axis_types) == (AxisType.Auto, AxisType.Auto)


def test_make_mesh_refuses_more_devices_than_exist():
    n = len(jax.devices())
    with pytest.raises(ValueError, match=f"needs {n + 1} devices"):
        make_mesh((n + 1,), ("data",))


def test_compile_cache_keeps_env_dir(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert E.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_repo_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = E.enable_compile_cache()
        assert got == os.path.join(E.REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        assert os.path.isfile(os.path.join(E.REPO, "BENCHMARK.json"))
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_host_device_flag_appends_and_replaces():
    flags = f"--xla_cpu_enable_fast_math=false {E.COUNT_FLAG}=1"
    assert E.with_host_devices(flags, 4).split() == [
        "--xla_cpu_enable_fast_math=false", f"{E.COUNT_FLAG}=4"]


def test_host_devices_only_on_cpu(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=x")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert E.child_env(4)["XLA_FLAGS"] == f"--xla_dump_to=x {E.COUNT_FLAG}=4"
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    assert E.child_env(4)["XLA_FLAGS"] == "--xla_dump_to=x"
    E.ensure_host_devices(4, ["never-run.py"])     # on a chip: no re-exec
    monkeypatch.delenv("JAX_PLATFORMS")
    assert E.child_env(4)["XLA_FLAGS"] == "--xla_dump_to=x"
