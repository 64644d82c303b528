"""Plain references for singular values and both singular factors.

`host_gram` forms AᵀA in float64 on the host, one row block at a time:
a float32 Gram accumulated by XLA over a million rows, even at precision
highest, is off by about 2e-5 relative on the chip (PERF.md), which
would hide the error it is meant to measure.  `host_eigh` is LAPACK in
float64 on the host.  Where AᵀA is too large to form, `operator_top`
finds its top eigenvalues by ARPACK from products with it alone."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def host_gram(A, block):
    """AᵀA (n, n) in float64, from A (m, n) one row block at a time."""
    m, n = A.shape
    G = np.zeros((n, n), np.float64)
    for i in range(0, m, block):
        blk = np.asarray(A[i:i + block], np.float64)
        G += blk.T @ blk
    return G


def host_eigh(M, k):
    """Top-k eigenpairs of a symmetric matrix, largest first, float64."""
    w, V = np.linalg.eigh(np.asarray(M, np.float64))
    return w[::-1][:k], V[:, ::-1][:, :k]


def operator_top(apply, n, k, *, vectors=False):
    """Top-k eigenvalues (and with `vectors` eigenvectors) of a symmetric
    positive semi-definite n × n operator given as `apply(X)` for (n, s)
    blocks X, largest first: ARPACK (`scipy.sparse.linalg.eigsh`) to
    machine precision, from a fixed start vector."""
    from scipy.sparse.linalg import LinearOperator, eigsh
    op = LinearOperator((n, n), dtype=np.float64,
                        matvec=lambda x: apply(x.reshape(n, 1))[:, 0],
                        matmat=apply)
    w, V = eigsh(op, k, which="LA", tol=0.0, v0=np.ones(n))
    order = np.argsort(w)[::-1]
    return (w[order], V[:, order]) if vectors else w[order]


def rel_gaps(got, ref):
    """Largest relative gap of each entry against the reference."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref) / np.abs(ref)))


def factor_residual(AV, U, s):
    """‖A·V − U·Σ‖_F / ‖A·V‖_F, with A·V from the reference: a wrong U
    shows here, whatever σ says."""
    US = U[:AV.shape[0]] * jnp.asarray(s, jnp.float32)[None, :]
    return float(jnp.linalg.norm(US - AV) / jnp.linalg.norm(AV))


def eigen_residual(GV, V, s):
    """‖G·V − V·Σ²‖_F / ‖V·Σ²‖_F in float64, with G·V = AᵀA·V from the
    reference: a V whose columns are not the eigenvectors of the σ beside
    them shows here, though U = A·V·Σ⁻¹ would pass `factor_residual`."""
    V = np.asarray(V, np.float64)
    VS2 = V * np.asarray(s, np.float64)[None, :] ** 2
    return float(np.linalg.norm(GV - VS2) / np.linalg.norm(VS2))
