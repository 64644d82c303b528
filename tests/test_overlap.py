"""Compute/collective overlap: the chunked (double-buffered) gram and
fused_grad bodies must agree with the eager path while issuing the
segmented psum structure the planner schedules.  The chunks split
columns: `dot(aᵀ, a[:, seg])` is the same sum as the matching columns of
`dot(aᵀ, a)`, but a separate contraction that XLA may block (and so round)
differently.  Segmented products are therefore held to the f32 forward
error bound of a length-m dot product, |Δ| ≤ 2·m·eps·(|a|ᵀ|b|), for the
two summation orders; values computed by the same contraction on both
paths (f, z) stay bit-identical.  Parity is pinned in-process on one
device and in a subprocess on a real 8-device host mesh."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.distmat import RowMatrix, SparseRowMatrix
from repro.core.distmat.rowmatrix import chunk_bounds
from repro.core.tfocs import SmoothQuad, LinopMatrix, row_separable


def assert_f32_dot_close(got, want, lhs, rhs):
    """|got − want| within the two-order f32 forward error bound of the
    contraction lhsᵀ·rhs over its m rows (see the module docstring)."""
    lhs, rhs = np.abs(np.asarray(lhs, np.float64)), np.abs(
        np.asarray(rhs, np.float64))
    bound = 2 * lhs.shape[0] * np.finfo(np.float32).eps * (lhs.T @ rhs)
    err = np.abs(np.asarray(got, np.float64) - np.asarray(want, np.float64))
    assert np.all(err <= bound), float((err - bound).max())


def _problem(m=96, n=24, seed=3):
    rng = np.random.default_rng(seed)
    A = rng.normal(size=(m, n)).astype(np.float32)
    b = rng.normal(size=m).astype(np.float32)
    rm = RowMatrix.create(jnp.asarray(A))
    linop = LinopMatrix(rm)
    sep = row_separable(SmoothQuad(b=linop.pad_data(jnp.asarray(b)),
                                   weights=linop.row_weights()))
    x = jnp.asarray(rng.normal(size=n), jnp.float32)
    return A, rm, sep, x


class TestChunkBounds:
    def test_covers_exactly_once(self):
        for n, c in [(24, 4), (24, 1), (7, 3), (5, 8), (1, 1)]:
            bounds = chunk_bounds(n, c)
            assert bounds[0][0] == 0 and bounds[-1][1] == n
            for (a0, a1), (b0, b1) in zip(bounds, bounds[1:]):
                assert a1 == b0
            assert len(bounds) <= max(min(c, n), 1)


class TestBitParity:
    def test_gram_chunked_matches_eager(self):
        A, rm, _, _ = _problem()
        eager = np.asarray(rm.gram(chunks=1))
        for c in (2, 4, 8):
            assert_f32_dot_close(rm.gram(chunks=c), eager, A, A)
        np.testing.assert_allclose(eager, A.T @ A, rtol=1e-4, atol=1e-4)

    def test_fused_grad_chunked_matches_eager(self):
        A, rm, sep, x = _problem()
        f1, g1, z1 = rm.fused_grad(x, sep, chunks=1)
        r = np.asarray(z1)[:A.shape[0]] - np.asarray(sep.target)[:A.shape[0]]
        for c in (2, 4):
            fc, gc, zc = rm.fused_grad(x, sep, chunks=c)
            assert np.array_equal(np.asarray(fc), np.asarray(f1))
            assert_f32_dot_close(gc, g1, r[:, None], A)
            assert np.array_equal(np.asarray(zc), np.asarray(z1))

    def test_auto_resolves_to_eager_on_one_device(self):
        """One device → no psum payload worth hiding; the planner must keep
        chunks=1 and auto must equal the explicit eager call bitwise."""
        _, rm, sep, x = _problem()
        assert np.array_equal(np.asarray(rm.gram()),
                              np.asarray(rm.gram(chunks=1)))
        fa, ga, _ = rm.fused_grad(x, sep)
        f1, g1, _ = rm.fused_grad(x, sep, chunks=1)
        assert np.array_equal(np.asarray(ga), np.asarray(g1))
        assert np.array_equal(np.asarray(fa), np.asarray(f1))

    @pytest.mark.parametrize("dispatch", ["bsr", "dense"])
    def test_sparse_fused_grad_chunked_matches_eager(self, dispatch):
        rng = np.random.default_rng(12)
        mask = rng.random((8, 6)) < 0.4
        A = (np.kron(mask, np.ones((8, 8)))
             * rng.normal(size=(64, 48))).astype(np.float32)
        srm = SparseRowMatrix.from_dense(A, bs=8)
        linop = LinopMatrix(srm)
        b = rng.normal(size=64).astype(np.float32)
        sep = row_separable(SmoothQuad(b=linop.pad_data(jnp.asarray(b)),
                                       weights=linop.row_weights()))
        x = jnp.asarray(rng.normal(size=48), jnp.float32)
        f1, g1, z1 = srm.fused_grad(x, sep, dispatch=dispatch, chunks=1)
        fc, gc, zc = srm.fused_grad(x, sep, dispatch=dispatch, chunks=4)
        assert np.array_equal(np.asarray(fc), np.asarray(f1))
        assert np.array_equal(np.asarray(gc), np.asarray(g1))
        assert np.array_equal(np.asarray(zc), np.asarray(z1))


class TestPsumStructure:
    """The overlap is real, not cosmetic: the traced program must contain
    one psum per scheduled segment (each a pipelineable partial reduction)
    instead of the eager path's single full-width psum."""

    def test_gram_psum_count(self):
        _, rm, _, _ = _problem()
        eager = str(jax.make_jaxpr(lambda: rm.gram(chunks=1))())
        chunked = str(jax.make_jaxpr(lambda: rm.gram(chunks=4))())
        assert eager.count("psum") == 1
        assert chunked.count("psum") == 4

    def test_fused_grad_psum_count(self):
        _, rm, sep, x = _problem()
        eager = str(jax.make_jaxpr(
            lambda v: rm.fused_grad(v, sep, chunks=1))(x))
        chunked = str(jax.make_jaxpr(
            lambda v: rm.fused_grad(v, sep, chunks=4))(x))
        # eager: one psum for f, one for g; chunked: f + one per segment
        assert eager.count("psum") == 2
        assert chunked.count("psum") == 5


EIGHT_DEV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np, jax.numpy as jnp
    assert len(jax.devices()) == 8
    from repro.core.distmat import RowMatrix, SparseRowMatrix
    from repro.core.distmat.types import make_mesh
    from repro.core.tfocs import SmoothQuad, LinopMatrix, row_separable

    mesh = make_mesh((4, 2), ("data", "model"))
    rng = np.random.default_rng(0)
    A = rng.normal(size=(96, 24)).astype(np.float32)
    b = rng.normal(size=96).astype(np.float32)
    rm = RowMatrix.create(jnp.asarray(A), mesh)
    linop = LinopMatrix(rm)
    sep = row_separable(SmoothQuad(b=linop.pad_data(jnp.asarray(b)),
                                   weights=linop.row_weights()))
    x = jnp.asarray(rng.normal(size=24), jnp.float32)

    def close(got, want, lhs, rhs):     # the f32 two-order dot bound
        lhs, rhs = np.abs(np.float64(lhs)), np.abs(np.float64(rhs))
        bound = 2 * lhs.shape[0] * np.finfo(np.float32).eps * (lhs.T @ rhs)
        assert np.all(np.abs(np.float64(got) - np.float64(want)) <= bound)

    eager = np.asarray(rm.gram(chunks=1))
    for c in (2, 4):
        close(rm.gram(chunks=c), eager, A, A)
    np.testing.assert_allclose(eager, A.T @ A, rtol=1e-3, atol=1e-3)

    f1, g1, z1 = rm.fused_grad(x, sep, chunks=1)
    fc, gc, zc = rm.fused_grad(x, sep, chunks=4)
    assert np.array_equal(np.asarray(fc), np.asarray(f1))
    close(gc, g1, (np.asarray(z1)[:96] - b)[:, None], A)
    assert np.array_equal(np.asarray(zc), np.asarray(z1))

    mask = rng.random((8, 6)) < 0.4
    S = (np.kron(mask, np.ones((8, 8)))
         * rng.normal(size=(64, 48))).astype(np.float32)
    srm = SparseRowMatrix.from_dense(S, bs=8, mesh=mesh)
    sl = LinopMatrix(srm)
    bs = rng.normal(size=64).astype(np.float32)
    seps = row_separable(SmoothQuad(b=sl.pad_data(jnp.asarray(bs)),
                                    weights=sl.row_weights()))
    xs = jnp.asarray(rng.normal(size=48), jnp.float32)
    for dispatch in ("bsr", "dense"):
        f1, g1, z1 = srm.fused_grad(xs, seps, dispatch=dispatch, chunks=1)
        fc, gc, zc = srm.fused_grad(xs, seps, dispatch=dispatch, chunks=4)
        assert np.array_equal(np.asarray(fc), np.asarray(f1)), dispatch
        close(gc, g1, (np.asarray(z1)[:64] - bs)[:, None], S)
        assert np.array_equal(np.asarray(zc), np.asarray(z1)), dispatch
    print("OVERLAP_8DEV_OK")
""")


def test_overlap_parity_on_8_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", EIGHT_DEV_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OVERLAP_8DEV_OK" in out.stdout
