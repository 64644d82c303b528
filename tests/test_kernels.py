"""Pallas kernels in interpret mode vs the pure-jnp oracles,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.bsr import BlockELL

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("m,k,n", [(16, 128, 128), (40, 70, 50),
                                   (128, 256, 128), (8, 130, 129)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
@pytest.mark.parametrize("bm,bn,bk", [(16, 128, 128), (32, 256, 256)])
def test_gemm_sweep(m, k, n, dtype, bm, bn, bk):
    a = jnp.asarray(RNG.normal(size=(m, k)), dtype)
    b = jnp.asarray(RNG.normal(size=(k, n)), dtype)
    got = ops.gemm(a, b, bm=bm, bn=bn, bk=bk, force_pallas=True,
                   out_dtype=jnp.float32)
    want = ref.gemm_ref(a, b, jnp.float32)
    tol = 1e-4 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * k)


def test_gemm_autotuned_default_parity():
    """tune="auto" (no explicit tiles) matches the oracle too."""
    a = jnp.asarray(RNG.normal(size=(72, 130)), jnp.float32)
    b = jnp.asarray(RNG.normal(size=(130, 66)), jnp.float32)
    got = ops.gemm(a, b, force_pallas=True, out_dtype=jnp.float32)
    np.testing.assert_allclose(got, ref.gemm_ref(a, b, jnp.float32),
                               rtol=1e-4, atol=1e-2)


@pytest.mark.parametrize("m,n", [(64, 16), (100, 20), (256, 32), (33, 7)])
@pytest.mark.parametrize("bm", [16, 64])
def test_tsgram_sweep(m, n, bm):
    a = jnp.asarray(RNG.normal(size=(m, n)), jnp.float32)
    got = ops.tsgram(a, bm=bm, force_pallas=True)
    np.testing.assert_allclose(got, ref.tsgram_ref(a), rtol=1e-4,
                               atol=1e-3)


@given(st.integers(10, 200), st.integers(2, 40), st.integers(1, 24))
@settings(max_examples=8, deadline=None)
def test_randsketch_property(m, n, r):
    rng = np.random.default_rng(m * 1000 + n * 10 + r)
    a = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(m, r)), jnp.float32)
    got = ops.randsketch(a, q, bm=16, force_pallas=True)
    np.testing.assert_allclose(got, ref.randsketch_ref(a, q), rtol=1e-4,
                               atol=1e-3)


@pytest.mark.parametrize("bm,bn", [(16, 128), (40, 256)])
def test_randsketch_tile_configs(bm, bn):
    rng = np.random.default_rng(42)
    a = jnp.asarray(rng.normal(size=(120, 150)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(120, 11)), jnp.float32)
    got = ops.randsketch(a, q, bm=bm, bn=bn, force_pallas=True)
    np.testing.assert_allclose(got, ref.randsketch_ref(a, q), rtol=1e-4,
                               atol=1e-3)


@given(st.integers(1, 6), st.integers(1, 6), st.floats(0.1, 0.9))
@settings(max_examples=8, deadline=None)
def test_bsr_property(bm, bn, density):
    rng = np.random.default_rng(int(bm * 100 + bn * 10 + density * 7))
    mask = rng.random((bm, bn)) < density
    dense = (np.kron(mask, np.ones((8, 8)))
             * rng.normal(size=(bm * 8, bn * 8))).astype(np.float32)
    bell = BlockELL.from_dense(dense, bs=8)
    np.testing.assert_allclose(bell.to_dense(), dense, atol=1e-6)
    x = rng.normal(size=(bn * 8, 16)).astype(np.float32)
    got = ops.bsr_matmul(bell, jnp.asarray(x), force_pallas=True)
    np.testing.assert_allclose(got, dense @ x, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("quantize", ["none", "int8"])
@pytest.mark.parametrize("rows", [2, 3])
def test_bsr_matmul_prefetch_chunks(monkeypatch, quantize, rows):
    """With the scalar-prefetched column list capped at `rows` block-rows,
    bsr_matmul runs several chunks into one aliased output, the last one
    ragged (7 block-rows), for exact and int8 blocks alike."""
    from repro.kernels import bsr
    rng = np.random.default_rng(31 + rows)
    mask = rng.random((7, 5)) < 0.5
    mask[:, 0] = True
    dense = (np.kron(mask, np.ones((8, 8)))
             * rng.normal(size=(56, 40))).astype(np.float32)
    bell = BlockELL.from_dense(dense, bs=8, quantize=quantize)
    assert (bell.scales is not None) == (quantize == "int8")
    monkeypatch.setattr(bsr, "PREFETCH_WORDS", rows * bell.ell)
    assert 7 % rows and -(-7 // rows) > 1    # several chunks, ragged tail
    x = jnp.asarray(rng.normal(size=(40, 16)), jnp.float32)
    bsr.bsr_matmul.clear_cache()             # the cap is read at trace time
    try:
        got = ops.bsr_matmul(bell, x, force_pallas=True)
    finally:
        bsr.bsr_matmul.clear_cache()
    np.testing.assert_allclose(got, ref.bsr_matmul_ref(bell, x), rtol=1e-4,
                               atol=1e-3)


@given(st.integers(1, 6), st.integers(1, 6), st.floats(0.1, 0.9))
@settings(max_examples=8, deadline=None)
def test_bsr_matvec_property(bm, bn, density):
    rng = np.random.default_rng(int(bm * 90 + bn * 9 + density * 11))
    mask = rng.random((bm, bn)) < density
    dense = (np.kron(mask, np.ones((8, 8)))
             * rng.normal(size=(bm * 8, bn * 8))).astype(np.float32)
    bell = BlockELL.from_dense(dense, bs=8)
    x = rng.normal(size=(bn * 8,)).astype(np.float32)
    got = ops.bsr_matvec(bell, jnp.asarray(x), force_pallas=True)
    np.testing.assert_allclose(got, dense @ x, rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(ops.bsr_matvec(bell, jnp.asarray(x)),
                               dense @ x, rtol=1e-4, atol=1e-3)


@given(st.integers(1, 6), st.integers(1, 6), st.integers(1, 20))
@settings(max_examples=8, deadline=None)
def test_bsr_rmatmul_property(bm, bn, nx):
    rng = np.random.default_rng(bm * 77 + bn * 7 + nx)
    mask = rng.random((bm, bn)) < 0.5
    dense = (np.kron(mask, np.ones((8, 8)))
             * rng.normal(size=(bm * 8, bn * 8))).astype(np.float32)
    bell = BlockELL.from_dense(dense, bs=8)
    x = rng.normal(size=(bm * 8, nx)).astype(np.float32)
    got = ops.bsr_rmatmul(bell, jnp.asarray(x), force_pallas=True)
    np.testing.assert_allclose(got, dense.T @ x, rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(ops.bsr_rmatmul(bell, jnp.asarray(x)),
                               dense.T @ x, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("B,hq,hkv,S,D", [
    (1, 2, 2, 64, 16),        # MHA
    (2, 4, 2, 64, 16),        # GQA 2:1
    (1, 8, 2, 128, 32),       # GQA 4:1
])
@pytest.mark.parametrize("bq,bk", [(16, 128), (32, 256)])
def test_flash_attention_sweep(B, hq, hkv, S, D, bq, bk):
    q = jnp.asarray(RNG.normal(size=(B, hq, S, D)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, hkv, S, D)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, hkv, S, D)), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=True, bq=bq, bk=bk,
                              force_pallas=True)
    want = ref.flash_attention_ref(
        q.reshape(B * hq, S, D), k.reshape(B * hkv, S, D),
        v.reshape(B * hkv, S, D), causal=True,
        q_heads_per_kv=hq // hkv).reshape(B, hq, S, D)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=3e-4)


def test_flash_attention_uneven_seq():
    B, H, S, D = 1, 2, 50, 16
    q, k, v = (jnp.asarray(RNG.normal(size=(B, H, S, D)), jnp.float32)
               for _ in range(3))
    got = ops.flash_attention(q, k, v, causal=True, bq=16, bk=128,
                              force_pallas=True)
    want = ref.flash_attention_ref(q[0], k[0], v[0], causal=True)[None]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=3e-4)


def test_flash_attention_bf16():
    B, H, S, D = 1, 2, 64, 32
    q, k, v = (jnp.asarray(RNG.normal(size=(B, H, S, D)), jnp.bfloat16)
               for _ in range(3))
    got = ops.flash_attention(q, k, v, causal=True, bq=16, bk=128,
                              force_pallas=True)
    want = ref.flash_attention_ref(q[0], k[0], v[0], causal=True)[None]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=5e-2)


def test_cpu_dispatch_no_force():
    """Without force_pallas on CPU the wrappers route to the reference."""
    a = jnp.asarray(RNG.normal(size=(12, 9)), jnp.float32)
    b = jnp.asarray(RNG.normal(size=(9, 5)), jnp.float32)
    np.testing.assert_allclose(ops.gemm(a, b), ref.gemm_ref(a, b),
                               rtol=1e-6)


@pytest.mark.parametrize("Bt,S,d,N,q", [(1, 32, 128, 16, 16),
                                        (2, 64, 96, 16, 16),
                                        (2, 64, 96, 16, 32),
                                        (1, 50, 70, 8, 16)])
def test_selective_scan_sweep(Bt, S, d, N, q):
    """Fused Mamba1 scan kernel (the §Perf-A kernel) vs sequential oracle."""
    rng = np.random.default_rng(Bt * 100 + S)
    x = jnp.asarray(rng.normal(size=(Bt, S, d)), jnp.float32)
    dt = jnp.asarray(np.abs(rng.normal(size=(Bt, S, d))) * 0.1, jnp.float32)
    A = jnp.asarray(-np.abs(rng.normal(size=(d, N))) - 0.1, jnp.float32)
    B = jnp.asarray(rng.normal(size=(Bt, S, N)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(Bt, S, N)), jnp.float32)
    D = jnp.asarray(rng.normal(size=(d,)), jnp.float32)
    got = ops.selective_scan(x, dt, A, B, C, D, q=q, force_pallas=True)
    want = ref.selective_scan_ref(x, dt, A, B, C, D)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_selective_scan_matches_mamba1_inner():
    """The kernel computes the same recurrence as the production
    (chunked associative-scan) path in models/ssm.py."""
    from repro.models import ssm as SSM
    rng = np.random.default_rng(7)
    Bt, S, di, N, dt_rank = 2, 32, 64, 16, 8
    x = jnp.asarray(rng.normal(size=(Bt, S, di)), jnp.float32)
    p = {
        "x_proj": jnp.asarray(rng.normal(size=(di, dt_rank + 2 * N)) * 0.1,
                              jnp.float32),
        "dt_proj": jnp.asarray(rng.normal(size=(dt_rank, di)) * 0.1,
                               jnp.float32),
        "dt_bias": jnp.zeros((di,), jnp.float32),
        "A_log": jnp.asarray(np.log(np.tile(np.arange(1, N + 1), (di, 1))),
                             jnp.float32),
        "D": jnp.ones((di,), jnp.float32),
    }
    h0 = jnp.zeros((Bt, di, N), jnp.float32)
    y_prod, _ = SSM._mamba1_inner(p, x, dt_rank, N, h0, chunk=8)
    # reconstruct the kernel inputs exactly as _mamba1_inner does
    dtBC = x @ p["x_proj"]
    dtr, Bm, Cm = jnp.split(dtBC, [dt_rank, dt_rank + N], -1)
    dt = jax.nn.softplus(dtr @ p["dt_proj"] + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y_kern = ops.selective_scan(x, dt, A, Bm, Cm, p["D"], q=16,
                                force_pallas=True)
    np.testing.assert_allclose(y_kern, y_prod, rtol=1e-3, atol=1e-3)
