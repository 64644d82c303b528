"""The device the planner prices against, the compile cache location, and
meshes that never run on fewer devices than they were sized for."""
from types import SimpleNamespace

import jax
import pytest

from repro import compile_cache
from repro.launch import machine
from repro.launch.mesh import make_host_mesh


def _attach(monkeypatch, kind):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [SimpleNamespace(device_kind=kind)])


def test_v5e_is_keyed_by_device_kind(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    machine.invalidate_cache()
    _attach(monkeypatch, "TPU v5 lite")
    assert machine.for_backend() is machine.V5E
    assert machine.builtin("tpu") is machine.V5E


@pytest.mark.parametrize("prefer_calibrated", [True, False])
def test_unknown_tpu_kind_raises(monkeypatch, prefer_calibrated):
    _attach(monkeypatch, "TPU v99")
    with pytest.raises(ValueError, match="TPU v99"):
        machine.for_backend(prefer_calibrated=prefer_calibrated)


def test_cpu_keeps_the_v5e_reference_pricing(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    machine.invalidate_cache()
    assert machine.for_backend("cpu") is machine.V5E
    assert machine.builtin("cpu") is machine.CPU


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    old = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable()
        assert path == compile_cache.CHECKOUT_CACHE
        assert path.name == ".jax_cache" and (path.parent / "src").is_dir()
        assert jax.config.jax_compilation_cache_dir == str(path)
    finally:
        jax.config.update("jax_compilation_cache_dir", old)


def test_compile_cache_env_wins(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    old = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == tmp_path
    assert jax.config.jax_compilation_cache_dir == old   # nothing set


def test_host_mesh_never_shrinks():
    with pytest.raises(ValueError, match="needs"):
        make_host_mesh(data=len(jax.devices()) + 1)
