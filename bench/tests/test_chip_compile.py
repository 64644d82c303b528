"""Each cell's kernels compile for a described TPU v5e at the cell's own
shapes, read from the configuration files; nothing runs.

The shapes are those of bench/configs: the dense fused gradient of the
solve cell, and the Gram and U = A·V of Gram-mode SVD at the traffic's
k.  The ops wrappers pick their Pallas kernels on TPU only, so each test
steers them there."""
import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune, ops

BENCH = Path(__file__).resolve().parents[1]
F32 = jnp.float32


def config(name):
    with open(BENCH / "configs" / f"{name}.json") as f:
        return json.load(f)


def traffic(name):
    with open(BENCH / "traffic" / f"{name}.json") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:         # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch, tmp_path):
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    autotune.reset()
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", cache_on)
    autotune.reset()


def compile_text(f, *args) -> str:
    return jax.jit(f).lower(*args).compile().as_text()


def spec(shape, one_chip, dtype=F32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def test_dense_fused_grad(one_chip, on_tpu):
    c = config("fig1-dense")
    m, n = c["rows"], c["cols"]
    assert "repro_fused_grad" in compile_text(
        lambda a, x, t: ops.fused_grad(a, x, t, t, loss="logistic"),
        spec((m, n), one_chip), spec((n,), one_chip), spec((m,), one_chip))


def test_dense_gram_and_u(one_chip, on_tpu):
    c, t = config("fig1-dense"), traffic("svd-auto-k10")
    m, n = c["rows"], c["cols"]
    a = spec((m, n), one_chip)
    assert "repro_tsgram" in compile_text(
        lambda a: ops.tsgram(a, out_dtype=F32), a)
    assert "repro_gemm" in compile_text(
        ops.gemm, a, spec((n, t["k"]), one_chip))
