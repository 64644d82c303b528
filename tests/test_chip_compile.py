"""The main-path Pallas kernels compile for a TPU v5e at the widths users
run (the chip_smoke.py phases), with the block configs the autotuner picks.

Nothing runs: each case lowers the ops wrapper for one described v5e chip
and compiles it with the TPU compiler, which refuses what interpret mode
accepts — VMEM overflow, block shapes off the (8 × 128) tiling, SMEM
overflow, ops Mosaic cannot lower.  The ops wrappers pick the kernel on
TPU only, so each test steers them there by patching `ops._on_tpu`.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune, bsr, ops

F32 = jnp.float32
DENSE = (1 << 20, 1024)          # dense solve / serving / Gram-mode SVD
WIDE = (1 << 18, 4096)           # the wide Gram-mode SVD
SPARSE = (1 << 20, 1 << 16)      # the sparse phase, 128 x 128 tiles
BS, ELL = 128, 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:         # noqa: BLE001 — any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch, tmp_path):
    """Dispatch the ops wrappers to their Pallas kernels, with block
    configs ranked from scratch (no user cache or calibration)."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    autotune.reset()
    # The persistent cache cannot hold programs for a described chip.
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", cache_on)
    autotune.reset()


def compile_text(f, *args) -> str:
    return jax.jit(f).lower(*args).compile().as_text()


@pytest.mark.parametrize("shape", [DENSE, WIDE], ids=["n1024", "n4096"])
def test_tsgram(shape, one_chip, on_tpu):
    a = jax.ShapeDtypeStruct(shape, F32, sharding=one_chip)
    assert "repro_tsgram" in compile_text(
        lambda a: ops.tsgram(a, out_dtype=F32), a)


def test_randsketch(one_chip, on_tpu):
    a = jax.ShapeDtypeStruct(WIDE, F32, sharding=one_chip)
    q = jax.ShapeDtypeStruct((WIDE[0], 128), F32, sharding=one_chip)
    assert "repro_randsketch" in compile_text(
        lambda a, q: ops.randsketch(a, q, out_dtype=F32), a, q)


@pytest.mark.parametrize("shape", [DENSE, WIDE], ids=["n1024", "n4096"])
def test_gemm(shape, one_chip, on_tpu):
    """Gram-mode SVD's U = A·(V Σ⁻¹) at k = 10."""
    a = jax.ShapeDtypeStruct(shape, F32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((shape[1], 10), F32, sharding=one_chip)
    assert "repro_gemm" in compile_text(lambda a, b: ops.gemm(a, b), a, b)


@pytest.mark.parametrize("shape", [DENSE, WIDE, (1 << 16, 1 << 14)],
                         ids=["n1024", "n4096", "n16384"])
def test_fused_grad(shape, one_chip, on_tpu):
    m, n = shape
    a = jax.ShapeDtypeStruct(shape, F32, sharding=one_chip)
    x = jax.ShapeDtypeStruct((n,), F32, sharding=one_chip)
    t = jax.ShapeDtypeStruct((m,), F32, sharding=one_chip)
    assert "repro_fused_grad" in compile_text(
        lambda a, x, t: ops.fused_grad(a, x, t, t, loss="logistic"),
        a, x, t)


def test_fused_grad_multi(one_chip, on_tpu):
    m, n = DENSE
    a = jax.ShapeDtypeStruct(DENSE, F32, sharding=one_chip)
    x = jax.ShapeDtypeStruct((8, n), F32, sharding=one_chip)
    t = jax.ShapeDtypeStruct((8, m), F32, sharding=one_chip)
    assert "repro_fused_grad_multi" in compile_text(
        lambda a, x, t: ops.fused_grad_multi(a, x, t, t, loss="quad"),
        a, x, t)


@pytest.fixture
def block_ell(one_chip):
    m, n = SPARSE
    return bsr.BlockELL(
        jax.ShapeDtypeStruct((m // BS, ELL, BS, BS), F32, sharding=one_chip),
        jax.ShapeDtypeStruct((m // BS, ELL), jnp.int32, sharding=one_chip),
        SPARSE)


def test_bsr_matvec(block_ell, one_chip, on_tpu):
    x = jax.ShapeDtypeStruct((SPARSE[1],), F32, sharding=one_chip)
    assert "repro_bsr_matvec" in compile_text(ops.bsr_matvec, block_ell, x)


def test_bsr_matmul(block_ell, one_chip, on_tpu):
    """The sparse SVD's U = A·(V Σ⁻¹) at k = 5."""
    v = jax.ShapeDtypeStruct((SPARSE[1], 5), F32, sharding=one_chip)
    assert "repro_bsr_matmul" in compile_text(ops.bsr_matmul, block_ell, v)


def test_bsr_rmatmul(block_ell, one_chip, on_tpu):
    u = jax.ShapeDtypeStruct((SPARSE[0], 1), F32, sharding=one_chip)
    assert "repro_bsr_rmatmul" in compile_text(ops.bsr_rmatmul, block_ell, u)


def test_fused_grad_bsr(block_ell, one_chip, on_tpu):
    m, n = SPARSE
    x = jax.ShapeDtypeStruct((n,), F32, sharding=one_chip)
    t = jax.ShapeDtypeStruct((m,), F32, sharding=one_chip)
    assert "repro_fused_grad_bsr" in compile_text(
        lambda a, x, t: ops.fused_grad_bsr(a, x, t, t, loss="logistic"),
        block_ell, x, t)
