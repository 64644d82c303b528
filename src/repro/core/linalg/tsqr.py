"""Tall-and-skinny QR (paper §3.4, ref [2] Benson–Gleich–Demmel).

Indirect TSQR adapted from MapReduce to the mesh: each row shard computes a
local Householder QR (map), the small R factors are concatenated and
re-factored (reduce — on TPU this is an all-gather of n×n tiles followed by
a replicated QR, i.e. a driver/vector op), and Q is recovered by a
triangular solve against the broadcast R — the same "broadcast the small
factor" pattern as U-recovery in the SVD.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.distmat import types as T
from repro.core.distmat.rowmatrix import RowMatrix

Array = jax.Array


def _nonneg_diag(R: Array) -> Array:
    """Fix the sign convention (R diagonal ≥ 0) for determinism."""
    d = jnp.sign(jnp.diagonal(R))
    d = jnp.where(d == 0, 1.0, d)
    return R * d[:, None]


def tsqr(A: RowMatrix) -> tuple[RowMatrix, Array]:
    """Returns (Q as RowMatrix, R replicated (n, n)) with A = Q R."""
    mesh, row_axes = A.mesh, A.row_axes
    spec = P(row_axes, None)
    n = A.rows.shape[1]

    def local_r(a):
        # Map step: local QR, keep only R.  Padding rows are zero and only
        # shrink the local R's column norms consistently — harmless.
        r = jnp.linalg.qr(a, mode="r")
        return _nonneg_diag(r)

    Rs = T.shard_map(local_r, mesh, (spec,), spec)(A.rows)    # (P·n, n) row-sharded
    # Reduce step: replicated second-level QR of the stacked R factors.
    R = _nonneg_diag(jnp.linalg.qr(
        T.put(Rs, T.replicated(mesh)), mode="r"))

    # Q = A R⁻¹ — form R⁻¹ once (replicated n×n triangular solve), then
    # broadcast it and recover Q with a per-shard autotuned GEMM — the same
    # "broadcast the small factor" pattern as U-recovery in the SVD, now
    # inheriting tuned block sizes from kernels/autotune.py on TPU.
    # Orthogonality of the recovered Q degrades as cond(R)·eps either way
    # (explicit-inverse multiply and per-shard back-substitution share that
    # bound); callers needing better than that for severely ill-conditioned
    # inputs should re-run tsqr on Q (one extra pass halves the defect).
    from repro.kernels import ops as _ops
    r_inv = jax.scipy.linalg.solve_triangular(
        R, jnp.eye(n, dtype=R.dtype), lower=False)

    def recover_q(a, ri):
        return _ops.gemm(a, ri, out_dtype=a.dtype)

    Q = T.shard_map(recover_q, mesh, (spec, P()), spec)(A.rows, r_inv)
    from dataclasses import replace
    return replace(A, rows=Q), R
