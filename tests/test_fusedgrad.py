"""Single-pass fused gradient: kernel parity, distmat wiring, solver
structure (exactly one A-pass per backtracking attempt), and the
fused-vs-unfused solution parity the acceptance bar demands."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.distmat import RowMatrix, SparseRowMatrix
from repro.core.distmat import types as T
from repro.core.optim import make_problem, minimize, composite_value
from repro.core.tfocs import (CountingLinop, LinopMatrix, ProxZero,
                              SmoothHuber, SmoothHuberL1, SmoothLogLoss,
                              SmoothPoisson, SmoothQuad, TfocsOptions,
                              fused_gradient_enabled, row_separable, tfocs)
from repro.kernels import ops, ref
from repro.kernels.bsr import BlockELL
from repro.launch import planner


def _data(m, n, dtype, seed=0, weighted=True):
    rng = np.random.default_rng(seed)
    a = rng.normal(size=(m, n)).astype(dtype)
    x = rng.normal(size=n).astype(dtype)
    t = rng.normal(size=m).astype(np.float32)
    w = (rng.random(m).astype(np.float32) if weighted
         else np.ones(m, np.float32))
    return (jnp.asarray(a), jnp.asarray(x), jnp.asarray(t), jnp.asarray(w))


# bf16 tolerance is wide: the kernel (like the unfused adjoint) feeds the
# MXU bf16 operands, so the residual is quantized before the second product
# and cancellation amplifies the quantization on small gradient entries.
TOL = {np.float32: dict(rtol=1e-5, atol=1e-5),
       jnp.bfloat16: dict(rtol=8e-2, atol=8e-2)}


class TestKernelParity:
    @pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
    @pytest.mark.parametrize("loss", ["quad", "logistic"])
    @pytest.mark.parametrize("m,n", [(96, 48), (130, 70)])  # multi-tile+pad
    def test_dense_kernel_matches_oracle(self, dtype, loss, m, n):
        a, x, t, w = _data(m, n, dtype, seed=m + n)
        if loss == "logistic":
            t = jnp.sign(t) + (t == 0)
        got = ops.fused_grad(a, x, t, w, loss=loss, force_pallas=True)
        want = ref.fused_grad_ref(a, x, t, w, loss=loss)
        tol = TOL[dtype]
        np.testing.assert_allclose(got[0], want[0], **tol)
        np.testing.assert_allclose(np.asarray(got[1], np.float32),
                                   np.asarray(want[1], np.float32), **tol)
        np.testing.assert_allclose(got[2], want[2], **tol)

    @pytest.mark.parametrize("loss", ["quad", "logistic"])
    @pytest.mark.parametrize("bs", [8, 16])
    def test_bsr_kernel_matches_oracle(self, loss, bs):
        rng = np.random.default_rng(3)
        nbr, nbc = 5, 7
        mask = rng.random((nbr, nbc)) < 0.4
        dense = (np.kron(mask, np.ones((bs, bs)))
                 * rng.normal(size=(nbr * bs, nbc * bs))).astype(np.float32)
        bell = BlockELL.from_dense(dense, bs=bs)
        m, n = dense.shape
        x = jnp.asarray(rng.normal(size=n), jnp.float32)
        t = jnp.asarray(np.sign(rng.normal(size=m)) + 0.0, jnp.float32) \
            if loss == "logistic" else jnp.asarray(
                rng.normal(size=m), jnp.float32)
        w = jnp.asarray(rng.random(m), jnp.float32)
        got = ops.fused_grad_bsr(bell, x, t, w, loss=loss, force_pallas=True)
        want = ref.fused_grad_ref(bell, x, t, w, loss=loss)
        np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(got[1], want[1], rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(got[2], want[2], rtol=1e-4, atol=1e-4)

    def test_bsr_fused_grad_vmem_fallback_parity(self, monkeypatch):
        """When the fused BSR kernel's resident working set would overflow
        VMEM, ops.fused_grad_bsr composes the VMEM-safe two-pass BSR
        kernels instead — same results, one extra block read."""
        from repro.kernels import autotune as at
        from repro.kernels import fusedgrad as fg
        rng = np.random.default_rng(23)
        mask = rng.random((4, 11)) < 0.5
        dense = (np.kron(mask, np.ones((8, 8)))
                 * rng.normal(size=(32, 88))).astype(np.float32)
        bell = BlockELL.from_dense(dense, bs=8)
        assert fg.fused_grad_bsr_vmem(bell.bs, bell.ell, bell.shape[1],
                                       bell.data.dtype) > 2048
        monkeypatch.setattr(at, "VMEM_BUDGET", 2048)
        x = jnp.asarray(rng.normal(size=88), jnp.float32)
        t = jnp.asarray(rng.normal(size=32), jnp.float32)
        w = jnp.asarray(rng.random(32), jnp.float32)
        got = ops.fused_grad_bsr(bell, x, t, w, loss="quad",
                                 force_pallas=True)
        want = ref.fused_grad_ref(bell, x, t, w, loss="quad")
        np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(got[1], want[1], rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(got[2], want[2], rtol=1e-4, atol=1e-4)

    def test_bsr_rmatmul_wide_fallback_parity(self, monkeypatch):
        """When the fused-scatter accumulator would overflow VMEM,
        bsr_rmatmul falls back to the partials + segment_sum scheme — force
        that branch with a tiny budget and check parity."""
        from repro.kernels import autotune as at
        from repro.kernels import bsr as bsr_mod
        rng = np.random.default_rng(17)
        mask = rng.random((3, 9)) < 0.5
        dense = (np.kron(mask, np.ones((8, 8)))
                 * rng.normal(size=(24, 72))).astype(np.float32)
        bell = BlockELL.from_dense(dense, bs=8)
        x = jnp.asarray(rng.normal(size=(24, 16)), jnp.float32)
        assert bsr_mod._rmm_fused_vmem(9, 8, 16, 4) > 1024
        monkeypatch.setattr(at, "VMEM_BUDGET", 1024)
        got = ops.bsr_rmatmul(bell, x, force_pallas=True)
        np.testing.assert_allclose(got, dense.T @ x, rtol=1e-4, atol=1e-4)

    def test_jnp_paths_match_oracle(self):
        """The off-TPU dispatch target (structured jnp) is itself correct."""
        a, x, t, w = _data(100, 40, np.float32, seed=9)
        got = ops.fused_grad(a, x, t, w, loss="quad")
        want = ref.fused_grad_ref(a, x, t, w, loss="quad")
        np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
        np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-5)

    def test_bad_loss_rejected(self):
        a, x, t, w = _data(16, 8, np.float32)
        with pytest.raises(ValueError):
            ops.fused_grad(a, x, t, w, loss="hinge")


class TestDistmatFusedGrad:
    def _meshes(self):
        yield None                                     # single-device
        if jax.device_count() > 1:                     # CI forces 8 hosts
            yield T.make_mesh((jax.device_count(), 1), ("data", "model"))

    @pytest.mark.parametrize("loss", ["quad", "logistic"])
    def test_rowmatrix_matches_apply_adjoint(self, loss):
        rng = np.random.default_rng(11)
        m, n = 203, 24                                 # ragged: padding rows
        A = rng.normal(size=(m, n)).astype(np.float32)
        for mesh in self._meshes():
            rm = RowMatrix.create(jnp.asarray(A), mesh)
            linop = LinopMatrix(rm)
            t = np.sign(rng.normal(size=m)).astype(np.float32) \
                if loss == "logistic" else rng.normal(size=m).astype(
                    np.float32)
            smooth = (SmoothLogLoss(y=linop.pad_data(jnp.asarray(t)),
                                    weights=linop.row_weights())
                      if loss == "logistic" else
                      SmoothQuad(b=linop.pad_data(jnp.asarray(t)),
                                 weights=linop.row_weights()))
            x = jnp.asarray(rng.normal(size=n), jnp.float32)
            f, g, z = linop.fused_grad(x, row_separable(smooth))
            zu = linop.apply(x)
            fu = smooth.value(zu)
            gu = linop.adjoint(smooth.grad(zu))
            np.testing.assert_allclose(f, fu, rtol=1e-5)
            np.testing.assert_allclose(g, gu, rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(z, zu, rtol=1e-5, atol=1e-5)

    def test_sparserowmatrix_matches_apply_adjoint(self):
        rng = np.random.default_rng(12)
        mask = rng.random((8, 6)) < 0.4
        A = (np.kron(mask, np.ones((8, 8)))
             * rng.normal(size=(64, 48))).astype(np.float32)
        for mesh in self._meshes():
            srm = SparseRowMatrix.from_dense(A, bs=8, mesh=mesh)
            linop = LinopMatrix(srm)
            b = rng.normal(size=64).astype(np.float32)
            smooth = SmoothQuad(b=linop.pad_data(jnp.asarray(b)),
                                weights=linop.row_weights())
            x = jnp.asarray(rng.normal(size=48), jnp.float32)
            for dispatch in ("bsr", "dense"):
                f, g, z = srm.fused_grad(x, row_separable(smooth),
                                         dispatch=dispatch)
                zu = linop.apply(x)
                fu = smooth.value(zu)
                gu = linop.adjoint(smooth.grad(zu))
                np.testing.assert_allclose(f, fu, rtol=1e-5)
                np.testing.assert_allclose(g, gu, rtol=1e-4, atol=1e-4)
                np.testing.assert_allclose(z, zu, rtol=1e-4, atol=1e-4)

    def test_non_separable_smooth_rejected(self):
        rm = RowMatrix.create(jnp.ones((16, 4), jnp.float32))
        with pytest.raises(ValueError):
            rm.fused_grad(jnp.ones(4), row_separable(SmoothHuberL1(0.1)))


class TestSolverStructure:
    def _composite(self, m=120, n=16, seed=5):
        rng = np.random.default_rng(seed)
        A = rng.normal(size=(m, n)).astype(np.float32)
        b = rng.normal(size=m).astype(np.float32)
        rm = RowMatrix.create(jnp.asarray(A))
        linop = LinopMatrix(rm)
        smooth = SmoothQuad(b=linop.pad_data(jnp.asarray(b)),
                            weights=linop.row_weights())
        return smooth, linop

    def test_fused_path_one_pass_per_backtracking_attempt(self):
        """The structural acceptance bar: with the fused path on, every
        traced A-contact is a fused_grad (one pass) — the seed evaluation
        plus one per traced attempt site (first attempt + backtracking
        body), and zero apply/adjoint calls."""
        smooth, linop = self._composite()
        counting = CountingLinop(linop)
        tfocs(smooth, counting, ProxZero(), jnp.zeros(16),
              TfocsOptions(max_iters=3, accel=False, backtracking=True,
                           fused=True))
        assert counting.counts == {"apply": 0, "adjoint": 0,
                                   "fused_grad": 3,
                                   "fused_grad_multi": 0}, counting.counts

    def test_unfused_path_two_passes_per_attempt(self):
        smooth, linop = self._composite()
        counting = CountingLinop(linop)
        tfocs(smooth, counting, ProxZero(), jnp.zeros(16),
              TfocsOptions(max_iters=3, accel=False, backtracking=True,
                           fused=False))
        # init apply + (adjoint + apply) per traced attempt site (2 sites)
        assert counting.counts == {"apply": 3, "adjoint": 2,
                                   "fused_grad": 0,
                                   "fused_grad_multi": 0}, counting.counts

    def test_accelerated_quad_takes_affine_fused_path(self):
        """acc* over a quadratic smooth rides the affine-u engine: every
        traced A-contact is a fused_grad — two seeds (u_b, x0) plus one per
        traced attempt site (first attempt + backtracking body), and zero
        apply/adjoint calls."""
        smooth, linop = self._composite()
        counting = CountingLinop(linop)
        _, info = tfocs(smooth, counting, ProxZero(), jnp.zeros(16),
                        TfocsOptions(max_iters=3, accel=True,
                                     backtracking=True, fused="auto"))
        assert counting.counts == {"apply": 0, "adjoint": 0,
                                   "fused_grad": 4,
                                   "fused_grad_multi": 0}, counting.counts
        assert info["plan"] == "fused_affine"
        assert bool(np.asarray(info["fused"]))

    def test_accelerated_non_quad_keeps_cached_path(self):
        """The affine decomposition needs ∇f linear in the image — logistic
        acc* must stay on the cached apply+adjoint engine."""
        _, linop = self._composite()
        rng = np.random.default_rng(5)
        y = (rng.random(120) > 0.5).astype(np.float32) * 2 - 1
        smooth = SmoothLogLoss(y=linop.pad_data(jnp.asarray(y)),
                               weights=linop.row_weights())
        counting = CountingLinop(linop)
        _, info = tfocs(smooth, counting, ProxZero(), jnp.zeros(16),
                        TfocsOptions(max_iters=3, accel=True,
                                     backtracking=True, fused="auto"))
        assert counting.counts["fused_grad"] == 0
        assert info["plan"] == "cached"
        assert not bool(np.asarray(info["fused"]))

    def test_fused_true_on_non_separable_raises(self):
        _, linop = self._composite()
        with pytest.raises(ValueError):
            fused_gradient_enabled(SmoothHuberL1(0.1), linop, True)

    def test_counting_wrapper_on_non_fused_base_falls_back(self):
        """CountingLinop's delegating methods exist unconditionally; the
        capability check must unwrap to the base so a non-fused-capable
        operator keeps the apply+adjoint path instead of crashing."""
        from repro.core.tfocs import LinopIdentity
        wrapped = CountingLinop(LinopIdentity(8))
        smooth = SmoothQuad(b=jnp.zeros(8))
        assert not fused_gradient_enabled(smooth, wrapped, "auto")
        x, _ = tfocs(smooth, wrapped, ProxZero(), jnp.ones(8),
                     TfocsOptions(max_iters=5, accel=False))
        assert wrapped.counts["fused_grad"] == 0
        assert wrapped.counts["apply"] > 0
        assert np.all(np.isfinite(np.asarray(x)))

    def test_sparse_operand_fuses_past_the_dense_width(self):
        """At n where no dense fused row block fits VMEM the planner picks
        the two passes for a dense shard; a block-sparse operand is priced
        on its stored blocks and takes its one-read BSR kernel — but not
        once quantized to int8, which has no fused kernel."""
        m, n = 256, 1 << 17
        rng = np.random.default_rng(7)
        ri = rng.integers(0, m, 64)
        ci = rng.integers(0, n, 64)
        S = SparseRowMatrix.from_entries(ri, ci, np.ones(64, np.float32),
                                         (m, n), bs=128)
        linop = LinopMatrix(S)
        smooth = SmoothQuad(b=linop.pad_data(jnp.zeros(m)),
                            weights=linop.row_weights())
        assert planner.plan("grad", {"m": m, "n": n}).choice == "unfused"
        assert linop.block_layout() == (S.bs, S.ell)
        assert fused_gradient_enabled(smooth, linop, "auto")
        assert fused_gradient_enabled(smooth, CountingLinop(linop), "auto")
        q = LinopMatrix(S.astype_store("int8"))
        assert not fused_gradient_enabled(smooth, q, "auto")
        assert not fused_gradient_enabled(smooth, CountingLinop(q), "auto")
        # The solver reports the engine that ran: two passes per attempt.
        _, info = tfocs(smooth, q, ProxZero(), jnp.zeros(n),
                        TfocsOptions(max_iters=3, accel=False))
        assert info["plan"] != "fused" and not bool(info["fused"])
        assert int(info["a_passes"]) == 1 + 2 * (
            int(info["iterations"]) + int(info["n_backtracks"]))

    @pytest.mark.parametrize("dtype,n,choice", [
        ("float32", 1 << 16, "fused"),
        ("int8", 1 << 16, "unfused"),        # no scale-aware fused kernel
        ("float32", 1 << 23, "unfused"),     # x + accumulator outgrow VMEM
    ])
    def test_sparse_grad_plan(self, dtype, n, choice):
        """plan("grad", kind sparse) prices one read of the stored blocks
        against two, and offers the fused side only where the BSR fused
        kernel runs (kernels/ops.fused_grad_bsr composes the two passes
        elsewhere)."""
        from repro.kernels import autotune as at
        from repro.kernels import fusedgrad as fg
        fits = fg.fused_grad_bsr_vmem(128, 5, n, dtype) <= at.VMEM_BUDGET
        assert fits == (n == 1 << 16 or dtype == "int8")
        p = planner.plan("grad", {"m": 1 << 20, "n": n, "bs": 128, "ell": 5},
                         dtype, context={"kind": "sparse"})
        assert p.choice == choice
        names = {lb for lb, _ in p.alternatives}
        assert names == ({"fused", "unfused"} if choice == "fused"
                         else {"unfused"})
        if choice == "fused":
            costs = dict(p.alternatives)
            assert costs["unfused"] > 1.9 * costs["fused"]

    def test_opt_out_flag(self):
        smooth, linop = self._composite()
        assert fused_gradient_enabled(smooth, linop, "auto")
        assert not fused_gradient_enabled(smooth, linop, False)


class TestSolverParity:
    """Fused and unfused paths run identical math — the iterates must agree
    to float tolerance on every Figure-1 problem (acceptance: ≤1e-5 rel in
    f32), dense and sparse."""

    @pytest.mark.parametrize("pname", ["linear", "linear_l1", "logistic",
                                       "logistic_l2"])
    @pytest.mark.parametrize("method", ["gra", "lbfgs"])
    def test_figure1_parity(self, pname, method):
        p = make_problem(pname, m=300, n=48)
        xf, info_f = minimize(p, method, max_iters=60, fused=True)
        xu, _ = minimize(p, method, max_iters=60, fused=False)
        ff, fu = (float(composite_value(p, xf)),
                  float(composite_value(p, xu)))
        assert abs(ff - fu) <= 1e-5 * (abs(fu) + 1.0), (ff, fu)
        np.testing.assert_allclose(np.asarray(xf), np.asarray(xu),
                                   rtol=1e-4, atol=1e-5)

    def test_sparse_composite_parity(self):
        rng = np.random.default_rng(21)
        mask = rng.random((10, 4)) < 0.4
        A = (np.kron(mask, np.ones((8, 8)))
             * rng.normal(size=(80, 32))).astype(np.float32)
        srm = SparseRowMatrix.from_dense(A, bs=8)
        linop = LinopMatrix(srm)
        b = rng.normal(size=80).astype(np.float32)
        smooth = SmoothQuad(b=linop.pad_data(jnp.asarray(b)),
                            weights=linop.row_weights())
        # Backtracking here accepts steps 1/L with L as low as λmax(AᵀA)/5
        # (about half of 80 steps have L < λmax/2), so any difference
        # between the two engines' f32 roundings grows along the top
        # eigenvector by |1 − λmax/L| per such step: the backtracking
        # iterates agree to 1e-7 after 40 iterations and by 3e-3 after 80,
        # while the objective and every accept/reject decision still agree.
        # Both engines are held to all three, and the iterates to the
        # tolerance also after 80 steps at the fixed step 1/λmax.
        L = float(np.linalg.norm(A, 2)) ** 2
        run = {}
        for fused in (True, False):
            for name, opts in (
                    ("fixed80", dict(max_iters=80, Lexact=L)),
                    ("bt40", dict(max_iters=40, backtracking=True)),
                    ("bt80", dict(max_iters=80, backtracking=True))):
                x, info = tfocs(smooth, linop, ProxZero(), jnp.zeros(32),
                                TfocsOptions(accel=False, fused=fused,
                                             **opts))
                assert bool(info["fused"]) == fused
                run[name, fused] = (np.asarray(x), info)
        for name in ("fixed80", "bt40"):
            np.testing.assert_allclose(run[name, True][0],
                                       run[name, False][0],
                                       rtol=1e-4, atol=1e-5)
        for name in ("bt40", "bt80"):
            fi, ui = run[name, True][1], run[name, False][1]
            assert int(fi["n_backtracks"]) == int(ui["n_backtracks"])
            np.testing.assert_allclose(np.asarray(fi["history"]),
                                       np.asarray(ui["history"]), rtol=1e-4)

    def test_counting_linop_is_transparent(self):
        p = make_problem("linear", m=200, n=32)
        pw = dataclasses.replace(p, linop=CountingLinop(p.linop))
        xw, _ = minimize(pw, "gra", max_iters=30)
        x, _ = minimize(p, "gra", max_iters=30)
        np.testing.assert_allclose(np.asarray(xw), np.asarray(x), rtol=1e-6)


class TestNewLossParity:
    """huber + poisson separable losses (ROADMAP fused-grad follow-on):
    kernel parity on the dense AND the BSR paths, smooth-object consistency,
    and a fused-vs-unfused solver run over SmoothHuber."""

    @pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
    @pytest.mark.parametrize("loss,param", [("huber", 0.6), ("huber", 2.0),
                                            ("poisson", 1.0)])
    @pytest.mark.parametrize("m,n", [(96, 48), (130, 70)])  # multi-tile+pad
    def test_dense_kernel_matches_oracle(self, dtype, loss, param, m, n):
        a, x, t, w = _data(m, n, dtype, seed=m + n)
        x = x * 0.1                        # keep e^z in float32 range
        if loss == "poisson":
            t = jnp.abs(t)                 # counts-like targets
        got = ops.fused_grad(a, x, t, w, loss=loss, param=param,
                             force_pallas=True)
        want = ref.fused_grad_ref(a, x, t, w, loss=loss, param=param)
        tol = TOL[dtype]
        np.testing.assert_allclose(got[0], want[0], **tol)
        np.testing.assert_allclose(np.asarray(got[1], np.float32),
                                   np.asarray(want[1], np.float32), **tol)
        np.testing.assert_allclose(got[2], want[2], **tol)

    @pytest.mark.parametrize("loss,param", [("huber", 0.5), ("poisson", 1.0)])
    @pytest.mark.parametrize("bs", [8, 16])
    def test_bsr_kernel_matches_oracle(self, loss, param, bs):
        rng = np.random.default_rng(11)
        nbr, nbc = 5, 7
        mask = rng.random((nbr, nbc)) < 0.4
        dense = (np.kron(mask, np.ones((bs, bs)))
                 * rng.normal(size=(nbr * bs, nbc * bs))).astype(np.float32)
        bell = BlockELL.from_dense(dense, bs=bs)
        m, n = dense.shape
        x = jnp.asarray(rng.normal(size=n) * 0.1, jnp.float32)
        t = jnp.asarray(rng.poisson(2.0, m), jnp.float32) \
            if loss == "poisson" else jnp.asarray(
                rng.normal(size=m), jnp.float32)
        w = jnp.asarray(rng.random(m), jnp.float32)
        got = ops.fused_grad_bsr(bell, x, t, w, loss=loss, param=param,
                                 force_pallas=True)
        want = ref.fused_grad_ref(bell, x, t, w, loss=loss, param=param)
        np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(got[1], want[1], rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(got[2], want[2], rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("cls,loss", [(SmoothHuber, "huber"),
                                          (SmoothPoisson, "poisson")])
    def test_smooth_advertises_row_separable(self, cls, loss):
        rng = np.random.default_rng(5)
        t = jnp.asarray(np.abs(rng.normal(size=64)), jnp.float32)
        sm = cls(t, weights=None) if loss == "poisson" \
            else cls(t, delta=0.7, weights=None)
        sep = row_separable(sm)
        assert sep is not None and sep.kind == loss
        # the kernel's row-local math IS the smooth's value/grad
        from repro.kernels.fusedgrad import row_loss_grad
        z = jnp.asarray(rng.normal(size=64) * 0.3, jnp.float32)
        f, r = row_loss_grad(z, sep.target, jnp.ones(64, jnp.float32),
                             loss, sep.param)
        np.testing.assert_allclose(float(f), float(sm.value(z)), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(r), np.asarray(sm.grad(z)),
                                   rtol=1e-5, atol=1e-6)

    def test_distmat_parity_dense_and_bsr(self):
        """RowMatrix.fused_grad and SparseRowMatrix.fused_grad (both
        dispatch arms) agree with apply + value/grad + adjoint for the new
        losses."""
        rng = np.random.default_rng(7)
        mask = rng.random((4, 6)) < 0.3
        dense = (np.kron(mask, np.ones((16, 16)))
                 * rng.normal(size=(64, 96))).astype(np.float32)
        x = jnp.asarray(rng.normal(size=96) * 0.1, jnp.float32)
        for A in (RowMatrix.create(dense),
                  SparseRowMatrix.from_dense(dense, bs=16)):
            linop = LinopMatrix(A)
            t = linop.pad_data(jnp.asarray(
                np.abs(rng.normal(size=64)), jnp.float32))
            for sm in (SmoothHuber(t, delta=0.8,
                                   weights=linop.row_weights()),
                       SmoothPoisson(t, weights=linop.row_weights())):
                f, g, z = linop.fused_grad(x, row_separable(sm))
                z2 = linop.apply(x)
                f2, g2 = sm.value(z2), linop.adjoint(sm.grad(z2))
                np.testing.assert_allclose(float(f), float(f2), rtol=1e-5)
                np.testing.assert_allclose(np.asarray(g), np.asarray(g2),
                                           rtol=1e-4, atol=1e-4)
                np.testing.assert_allclose(np.asarray(z)[:64],
                                           np.asarray(z2)[:64],
                                           rtol=1e-5, atol=1e-5)

    def test_huber_solver_fused_matches_unfused(self):
        """gra over SmoothHuber: the fused engine (one A-pass per attempt)
        reaches the same solution as the unfused baseline."""
        rng = np.random.default_rng(9)
        A = RowMatrix.create(rng.normal(size=(160, 24)).astype(np.float32))
        linop = LinopMatrix(A)
        b = jnp.asarray(rng.normal(size=160), jnp.float32)
        sm = SmoothHuber(linop.pad_data(b), delta=0.5,
                         weights=linop.row_weights())
        outs = {}
        for fused in (True, False):
            outs[fused], info = tfocs(
                sm, linop, ProxZero(), jnp.zeros(24),
                TfocsOptions(max_iters=60, accel=False, backtracking=True,
                             fused=fused))
            assert bool(info["fused"]) == fused
        np.testing.assert_allclose(np.asarray(outs[True]),
                                   np.asarray(outs[False]),
                                   rtol=1e-4, atol=1e-5)
