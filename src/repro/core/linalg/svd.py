"""computeSVD / computePCA — paper §3.1, plus a randomized third path.

Dispatch mirrors MLlib's RowMatrix.computeSVD: the *user does not choose* —
`mode="auto"` asks the execution planner (launch/planner.plan("svd", ...),
the same calibrated-machine-model path every other dispatch decision takes)
to pick among three paths by (n, k):

  * ``gram``        — n ≤ GRAM_THRESHOLD (=8192): the n×n Gram fits "on the
    driver" (replicated per chip); one all-reduce, then a local eigh
    (§3.1.2 tall-and-skinny).
  * ``randomized``  — n > GRAM_THRESHOLD and k ≤ RANDOMIZED_K_THRESHOLD
    (=128), RowMatrix only: blocked Gaussian range finder with TSQR
    re-orthonormalization and 2+2q passes over A (Li–Kluger–Tygert; see
    randsvd.py).  Wins when A is too wide for Gram but dense enough that
    Lanczos' one-direction-per-matvec iteration is the bottleneck.
  * ``lanczos``     — everything else: ARPACK-analogue matrix-free
    thick-restart Lanczos (§3.1.1); the right tool for very sparse
    operators and for k too large for a sketch to be cheap.

Wide-and-short inputs (m < n) route through the transpose, exactly as the
paper describes: SVD(Aᵀ) = U'ΣV'ᵀ gives A = V'ΣU'ᵀ, so the factors swap.
CoordinateMatrix transposes for free (index swap); RowMatrix and
SparseRowMatrix transpose at driver scale (the paper's format-conversion
shuffle warning applies).  The transposed problem then picks among the same
three modes on its own (n', k) — in particular Lanczos now iterates on the
small AAᵀ instead of the large AᵀA.

SparseRowMatrix inputs drive Lanczos through the block-sparse
matvec/rmatvec (auto mode; the Gram path is available explicitly when n is
small), and U is recovered by the same broadcast-V multiply — the product
of a sparse matrix with the dense small factor is a dense RowMatrix.

All modes report their convergence evidence in ``SVDResult.info`` (gram:
exact; randomized: ``tail_ratio``; lanczos: restarts/residuals).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distmat.coordinatematrix import CoordinateMatrix
from repro.core.distmat.rowmatrix import RowMatrix
from repro.core.distmat.sparserow import SparseRowMatrix
from repro.launch import telemetry as _telemetry
from . import lanczos as _lanczos
from . import randsvd as _randsvd

Array = jax.Array

# n at which an n×n float32 Gram stops being a comfortable "driver" object.
# 16 GB HBM chip → reserve ≲ 1 GB for the replicated Gram → n ≈ 16384.
GRAM_THRESHOLD = 8192

# Largest k for which the (k+p)-wide sketch beats Lanczos' k sequential
# directions: past this, sketch passes stop amortizing the extra flops and
# the (n × k+p) projections crowd VMEM in the streaming kernel.
RANDOMIZED_K_THRESHOLD = 128


@dataclass(frozen=True)
class SVDResult:
    U: RowMatrix | None     # (m, k) distributed left singular vectors
    s: Array                # (k,) singular values, descending (replicated)
    V: Array                # (n, k) right singular vectors (replicated)
    info: dict | None = None


def driver_eigh(G: Array, k: int | None = None) -> tuple[Array, Array]:
    """Eigenpairs of the replicated n × n Gram, largest first (top k), on
    the driver: LAPACK in float64 on the host, as the paper runs it.  (The
    TPU's own eigh compiles for minutes and tens of GB of host memory at
    n in the thousands.)  Spans: ``svd.fetch`` waits for the Gram and
    copies it to the host, ``svd.eigh`` is the host LAPACK and the copy
    back."""
    tel = _telemetry.current()
    with tel.span("svd.fetch"):
        G = jax.device_get(G)
    with tel.span("svd.eigh"):
        w, V = np.linalg.eigh(np.asarray(G, np.float64))
        w, V = w[::-1][:k], V[:, ::-1][:, :k]
        return jnp.asarray(w, jnp.float32), jnp.asarray(V, jnp.float32)


def _recover_u(A, s: Array, V: Array, rcond: float) -> RowMatrix:
    """U = A (V Σ⁻¹): broadcast the small factor (paper: "embarrassingly
    parallel"), one local GEMM (or BSR SpMM) per row shard, no collectives
    at all.  Works for any row-sharded type with multiply_local."""
    inv = jnp.where(s > rcond * jnp.max(s), 1.0 / jnp.maximum(s, 1e-30), 0.0)
    return A.multiply_local(V * inv[None, :])


def _transpose(A):
    """Type-specific Aᵀ for the wide-and-short dispatch; None when the type
    has no transpose (those inputs keep the direct Lanczos-on-AᵀA path)."""
    if isinstance(A, (CoordinateMatrix, SparseRowMatrix)):
        return A.transpose()
    if isinstance(A, RowMatrix):
        return RowMatrix.create(jnp.asarray(A.to_local()).T, A.mesh,
                                A.row_axes)
    return None


def _swap_transposed(A, At, res: "SVDResult", compute_u: bool,
                     rcond: float) -> "SVDResult":
    """Map SVD(Aᵀ) = U'ΣV'ᵀ back to A = V'ΣU'ᵀ: V of A is the distributed
    U' (replicated on the way out — it is the paper's driver-side factor),
    U of A is the small V', re-wrapped row-sharded."""
    s = res.s
    if res.U is not None:
        V = jnp.asarray(res.U.to_local())
    else:
        # Generic U' = AᵀV'Σ⁻¹ via k driver-looped matvecs (CoordinateMatrix
        # returns replicated vectors, so this is vector-scale work).
        inv = jnp.where(s > rcond * jnp.max(s),
                        1.0 / jnp.maximum(s, 1e-30), 0.0)
        V = jnp.stack([At.matvec(res.V[:, i]) * inv[i]
                       for i in range(res.V.shape[1])], axis=1)
    U = None
    if compute_u:
        U = RowMatrix.create(res.V, getattr(A, "mesh", None),
                             getattr(A, "row_axes", None))
    return SVDResult(U=U, s=s, V=V,
                     info=dict(res.info or {}, transposed=True))


def compute_svd(A, k: int, *, compute_u: bool = True,
                mode: Literal["auto", "gram", "lanczos",
                              "randomized"] = "auto",
                gram_threshold: int = GRAM_THRESHOLD,
                randomized_k_threshold: int = RANDOMIZED_K_THRESHOLD,
                oversampling: int = _randsvd.OVERSAMPLING,
                power_iters: int = _randsvd.POWER_ITERS,
                rcond: float = 1e-9, seed: int = 0,
                **lanczos_kw) -> SVDResult:
    m, n = A.shape
    k = min(k, min(m, n))
    if mode not in ("auto", "gram", "lanczos", "randomized"):
        raise ValueError(f"unknown mode {mode!r}; expected auto | gram | "
                         "lanczos | randomized")
    if m < n and (At := _transpose(A)) is not None:
        # Paper: wide-and-short inputs go through the transpose, which is
        # tall-and-skinny and picks among the same three modes on (n', k);
        # SVD(Aᵀ) = U'ΣV'ᵀ ⇒ A = V'ΣU'ᵀ, so the factors swap on the way out.
        # Types without a transpose (BlockMatrix, IndexedRowMatrix) keep the
        # direct matrix-free path below.
        res = compute_svd(At, k, compute_u=True, mode=mode,
                          gram_threshold=gram_threshold,
                          randomized_k_threshold=randomized_k_threshold,
                          oversampling=oversampling, power_iters=power_iters,
                          rcond=rcond, seed=seed, **lanczos_kw)
        return _swap_transposed(A, At, res, compute_u, rcond)
    if mode == "auto":
        # §3.1 mode dispatch now lives in the execution planner (one
        # calibrated machine model behind every decision): sparse operators
        # take the matrix-free iteration (matvec ∝ nnz, no dense Gram),
        # RowMatrix picks gram / randomized / lanczos by (n, k).
        # plan(...).explain() shows the modeled A-pass cost of each mode.
        from repro.launch import planner as _planner
        kind = ("sparse" if isinstance(A, SparseRowMatrix)
                else "row" if isinstance(A, RowMatrix) else "other")
        ctx = {"kind": kind, "gram_threshold": gram_threshold,
               "randomized_k_threshold": randomized_k_threshold,
               "oversampling": oversampling, "power_iters": power_iters}
        if isinstance(A, SparseRowMatrix):
            ctx["nnz"] = A.nnz
        mode = _planner.plan("svd", {"m": m, "n": n, "k": k},
                             context=ctx).choice

    # All branches report the standardized info keys (iterations / a_passes
    # / converged / plan) alongside their native diagnostics; the native
    # mode-specific keys ("mode", "restarts", "passes_over_A", ...) are
    # deprecated aliases kept for one release.
    if mode == "gram":
        # §3.1.2 tall-and-skinny: one all-reduce builds AᵀA, the
        # eigendecomposition runs on the driver.
        w, V = driver_eigh(A.gram(), k)
        s = jnp.sqrt(jnp.maximum(w, 0.0))
        info = {"mode": "gram", "plan": "gram", "iterations": 0,
                "a_passes": 1, "converged": True}
    elif mode == "randomized":
        # Few-pass sketch path: U falls out of the range basis for free, so
        # recover it there instead of paying _recover_u's extra pass.
        if not isinstance(A, RowMatrix):
            raise ValueError("mode='randomized' needs a RowMatrix "
                             "(row-sharded sketch/project primitives)")
        U, s, V, info = _randsvd.randomized_svd(
            A, k, oversampling=oversampling, power_iters=power_iters,
            seed=seed, compute_u=compute_u)
        info = dict(info, plan="randomized", iterations=power_iters,
                    a_passes=info["passes_over_A"], converged=True)
        return SVDResult(U=U, s=s, V=V, info=info)
    else:
        # §3.1.1 square/sparse: ARPACK-analogue matrix-free Lanczos.
        s, V, info = _lanczos.svd_via_lanczos(A, k, seed=seed, **lanczos_kw)
        # Each normal-equations op call is a matvec + rmatvec = 2 A-passes.
        info = dict(info, mode="lanczos", plan="lanczos",
                    iterations=info["restarts"],
                    a_passes=2 * info["op_calls"])

    U = None
    if compute_u and isinstance(A, (RowMatrix, SparseRowMatrix)):
        with _telemetry.current().span("svd.recover_u"):
            U = _recover_u(A, s, V, rcond)
        info = dict(info, a_passes=info["a_passes"] + 1)  # the U = A(VΣ⁻¹) pass
    return SVDResult(U=U, s=s, V=V, info=info)


def compute_pca(A: RowMatrix, k: int) -> tuple[Array, Array]:
    """Principal components from the Gram matrix with the rank-one mean
    correction — never materializes the centered matrix (it would be dense
    even when A is sparse).  Returns (components (n,k), explained variance)."""
    m, n = A.shape
    stats = A.column_stats()
    mu = stats["mean"]
    G = A.gram().astype(jnp.float32)
    cov = (G - m * jnp.outer(mu, mu)) / max(m - 1, 1)
    w, V = driver_eigh(cov, k)
    return V, jnp.maximum(w, 0.0)
