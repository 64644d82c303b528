"""Linear operators for composite objectives (paper §3.2.2 `LinopMatrix`).

The linear component is the *expensive, distributed* part of a TFOCS
composite objective — exactly the paper's matrix/vector split: `apply` maps
the replicated ("driver") variable into the row-sharded ("cluster") data
space; `adjoint` reduces back.  All solver math above this layer is
representation-agnostic and mesh-agnostic: it sees global arrays and lets
the operators own the collectives.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Protocol

import jax
import jax.numpy as jnp

from repro.core.distmat.rowmatrix import RowMatrix
from repro.core.distmat.sparserow import SparseRowMatrix

Array = jax.Array

_DIST = (RowMatrix, SparseRowMatrix)


class LinearOperator(Protocol):
    in_shape: tuple[int, ...]
    out_shape: tuple[int, ...]

    def apply(self, x: Array) -> Array: ...
    def adjoint(self, y: Array) -> Array: ...


@dataclass(frozen=True)
class LinopMatrix:
    """y = A x for a distributed RowMatrix / SparseRowMatrix (or a plain
    local matrix)."""
    A: RowMatrix | SparseRowMatrix | Array

    @property
    def in_shape(self) -> tuple[int, ...]:
        return (self.A.shape[1],)

    @property
    def out_shape(self) -> tuple[int, ...]:
        # Padded row count — the data-space vectors (b, weights) must be
        # padded consistently; `pad_data` below does this for callers.
        if isinstance(self.A, RowMatrix):
            return (self.A.rows.shape[0],)
        if isinstance(self.A, SparseRowMatrix):
            return (self.A.m_pad,)
        return (self.A.shape[0],)

    def apply(self, x: Array) -> Array:
        if isinstance(self.A, _DIST):
            return self.A.matvec(x)
        return self.A @ x

    def adjoint(self, y: Array) -> Array:
        if isinstance(self.A, _DIST):
            return self.A.rmatvec(y)
        return self.A.T @ y

    def fused_grad(self, x: Array, sep, residual: Array | None = None):
        """(f(Ax), Aᵀ∇f(Ax), Ax) in one streaming pass over A for a
        row-separable smooth (kernels/fusedgrad) — half the HBM traffic of
        apply + adjoint.  `sep` is the smooth's RowSeparable form.

        `residual` (distributed operands only; see
        RowMatrix.init_psum_residual) switches the gradient all-reduce to
        the compressed int8 wire and returns (f, g, z, new_residual)."""
        if isinstance(self.A, _DIST):
            if residual is not None:
                return self.A.fused_grad(x, sep, residual=residual)
            return self.A.fused_grad(x, sep)
        from repro.kernels import ops as _ops
        t = self.pad_data(jnp.asarray(sep.target))
        w = jnp.ones_like(t) if sep.weights is None \
            else self.pad_data(jnp.asarray(sep.weights))
        return _ops.fused_grad(jnp.asarray(self.A), jnp.asarray(x), t, w,
                               loss=sep.kind,
                               param=float(getattr(sep, "param", 1.0)))

    def astype_store(self, dtype) -> "LinopMatrix":
        """Recast the operand's storage (the solver's precision="auto"
        dispatch lands here) — distributed operands keep their sharding;
        compute still upcasts on-chip and accumulates f32."""
        if isinstance(self.A, _DIST):
            return LinopMatrix(self.A.astype_store(dtype))
        return LinopMatrix(jnp.asarray(self.A).astype(dtype))

    def init_psum_residual(self):
        """Zeroed error-feedback residual for the compressed gradient
        psum; None for local operands (no wire to compress)."""
        if isinstance(self.A, _DIST):
            return self.A.init_psum_residual()
        return None

    def fused_grad_multi(self, x: Array, seps) -> tuple[Array, Array, Array]:
        """Request-batched fused gradients: (f (k,), g (k × n), z (k × m))
        for a GROUP of k right-hand sides in one streaming pass over A —
        each HBM read of A is amortized across every request in the group.
        `x` is (k × n); `seps` a sequence of k RowSeparable smooths sharing
        one loss kind/param (or a single stacked-target smooth)."""
        if isinstance(self.A, _DIST):
            return self.A.fused_grad_multi(x, seps)
        from repro.core.distmat import types as _T
        from repro.kernels import ops as _ops
        kind, t, w, prm = _T.row_separable_batch_inputs(
            seps, self.out_shape[0], lambda: self.row_weights())
        return _ops.fused_grad_multi(jnp.asarray(self.A),
                                     jnp.atleast_2d(jnp.asarray(x)), t, w,
                                     loss=kind, param=prm)

    def operand_dtype(self):
        """dtype of the matrix operand (the planner dispatch input)."""
        A = self.A
        if isinstance(A, RowMatrix):
            return A.rows.dtype
        if isinstance(A, SparseRowMatrix):
            return A.data.dtype
        return jnp.asarray(A).dtype

    def block_layout(self) -> tuple[int, int] | None:
        """(bs, ell) of a block-sparse operand's BlockELL shards — the
        planner prices its fused gradient on the stored blocks — or None
        for a dense one."""
        A = self.A
        return (A.bs, A.ell) if isinstance(A, SparseRowMatrix) else None

    def row_shards(self) -> int:
        """Number of row shards the operand is split into — the fused-vs-
        unfused roofline is a per-shard decision, so the dispatch divides
        the global row count by this."""
        from repro.core.distmat import types as _T
        if isinstance(self.A, _DIST):
            return _T.axes_size(self.A.mesh, self.A.row_axes)
        return 1

    def pad_data(self, b: Array) -> Array:
        """Pad a data-space vector to the padded row count."""
        m = self.out_shape[0]
        return jnp.pad(b, (0, m - b.shape[0])) if b.shape[0] < m else b

    def row_weights(self) -> Array:
        """{0,1} mask of true rows — weights for the smooth component so the
        padding rows of the distributed layout contribute nothing."""
        if isinstance(self.A, _DIST):
            return self.A._row_mask()
        return jnp.ones(self.out_shape, jnp.float32)


@dataclass(frozen=True)
class LinopIdentity:
    n: int

    @property
    def in_shape(self):
        return (self.n,)

    @property
    def out_shape(self):
        return (self.n,)

    def apply(self, x: Array) -> Array:
        return x

    def adjoint(self, y: Array) -> Array:
        return y

    def pad_data(self, b: Array) -> Array:
        return b

    def row_weights(self) -> Array:
        return jnp.ones((self.n,), jnp.float32)


@dataclass
class CountingLinop:
    """Wraps an operator and counts its A-passes (apply / adjoint /
    fused_grad — each is exactly one streaming pass over A).

    The counters increment at *trace* time.  Solver loops are
    `lax.while_loop`s whose bodies trace exactly once, so the counts are
    the structural per-iteration pass counts — deterministic, independent
    of runtime iteration counts, and therefore non-flaky (bench_optim and
    the perf-smoke test rely on this)."""
    base: object
    counts: dict = field(default_factory=lambda: {
        "apply": 0, "adjoint": 0, "fused_grad": 0, "fused_grad_multi": 0})

    @property
    def in_shape(self):
        return self.base.in_shape

    @property
    def out_shape(self):
        return self.base.out_shape

    @property
    def A(self):
        return getattr(self.base, "A", None)

    def total(self) -> int:
        return sum(self.counts.values())

    def apply(self, x: Array) -> Array:
        self.counts["apply"] += 1
        return self.base.apply(x)

    def adjoint(self, y: Array) -> Array:
        self.counts["adjoint"] += 1
        return self.base.adjoint(y)

    def fused_grad(self, x: Array, sep, residual=None):
        self.counts["fused_grad"] += 1
        if residual is not None:
            return self.base.fused_grad(x, sep, residual=residual)
        return self.base.fused_grad(x, sep)

    def astype_store(self, dtype):
        return CountingLinop(self.base.astype_store(dtype), self.counts)

    def init_psum_residual(self):
        return self.base.init_psum_residual()

    def fused_grad_multi(self, x: Array, seps):
        # ONE pass over A regardless of group width — that equality is
        # exactly what the serving parity tests assert.
        self.counts["fused_grad_multi"] += 1
        return self.base.fused_grad_multi(x, seps)

    def operand_dtype(self):
        return self.base.operand_dtype()

    def row_shards(self) -> int:
        return self.base.row_shards()

    def block_layout(self) -> tuple[int, int] | None:
        return self.base.block_layout()

    def pad_data(self, b: Array) -> Array:
        return self.base.pad_data(b)

    def row_weights(self) -> Array:
        return self.base.row_weights()


@dataclass(frozen=True)
class LinopAdjoint:
    """The formal adjoint of another operator (used by the SCD dual solver,
    where the dual variable lives in data space)."""
    base: LinearOperator

    @property
    def in_shape(self):
        return self.base.out_shape

    @property
    def out_shape(self):
        return self.base.in_shape

    def apply(self, x: Array) -> Array:
        return self.base.adjoint(x)

    def adjoint(self, y: Array) -> Array:
        return self.base.apply(y)

    def pad_data(self, b: Array) -> Array:
        return b

    def row_weights(self) -> Array:
        return jnp.ones(self.out_shape, jnp.float32)
