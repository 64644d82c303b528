"""BlockMatrix — 2-D block-sharded distributed matrix (paper §2.3).

The RDD of ((bi, bj), Matrix) tiles becomes one logical 2-D array sharded
over BOTH mesh axes: P(row_axes, 'model').  Each device owns one
(m/R) × (n/C) dense tile in HBM — the direct analogue of "each block small
enough to fit in memory on a single machine".

`multiply` is SUMMA adapted to ICI: instead of the Spark shuffle-join of
block pairs, each device all-gathers its row panel of A (along 'model') and
its column panel of B (along the row axes) and performs one local MXU GEMM.
Per-device communication is k·(m/R + n/C) — the textbook SUMMA volume — and
the result is already in canonical layout, no reduction step needed.

Also here: the "vector as RDD" mode from paper §1.2 — matvec where the
parameter vector itself is sharded over the model axis (large linear model
parallelism, refs [4, 9]).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import types as T

Array = jax.Array


@dataclass(frozen=True)
class BlockMatrix(T.DistMatrix):
    data: Array                     # (m_pad, n_pad) sharded P(row_axes, col)
    dims: tuple[int, int]           # true (m, n)
    mesh: Mesh = field(repr=False)
    row_axes: tuple[str, ...] = T.ROW_AXES
    col_axis: str = T.COL_AXIS

    @staticmethod
    def create(x: Array, mesh: Mesh | None = None,
               row_axes: Sequence[str] | None = None,
               col_axis: str = T.COL_AXIS,
               block_rows: int | None = None,
               block_cols: int | None = None) -> "BlockMatrix":
        """`block_rows/cols` are advisory (Spark's rowsPerBlock); the actual
        tile size is the shard size — we validate compatibility instead."""
        mesh = mesh or T.single_device_mesh()
        row_axes = tuple(row_axes) if row_axes else T.row_axes_for(mesh)
        R = T.axes_size(mesh, row_axes)
        C = mesh.shape[col_axis]
        x = jnp.asarray(x)
        m, n = x.shape
        x, _ = T.pad_rows(x, R)
        x = jnp.swapaxes(T.pad_rows(jnp.swapaxes(x, 0, 1), C)[0], 0, 1)
        x = T.put(x, NamedSharding(mesh, P(row_axes, col_axis)))
        return BlockMatrix(data=x, dims=(m, n), mesh=mesh,
                           row_axes=row_axes, col_axis=col_axis)

    # -- bookkeeping ---------------------------------------------------------
    @property
    def shape(self) -> tuple[int, int]:
        return self.dims

    @property
    def block_shape(self) -> tuple[int, int]:
        R = T.axes_size(self.mesh, self.row_axes)
        C = self.mesh.shape[self.col_axis]
        return (self.data.shape[0] // R, self.data.shape[1] // C)

    def validate(self) -> None:
        """Paper's `validate`: block grid consistent with the declared mesh."""
        R = T.axes_size(self.mesh, self.row_axes)
        C = self.mesh.shape[self.col_axis]
        mp, np_ = self.data.shape
        if mp % R or np_ % C:
            raise ValueError(
                f"padded shape {self.data.shape} not divisible by mesh grid "
                f"({R}, {C})")
        if mp < self.dims[0] or np_ < self.dims[1]:
            raise ValueError("padded storage smaller than logical dims")
        want = NamedSharding(self.mesh, P(self.row_axes, self.col_axis))
        got = self.data.sharding
        if not got.is_equivalent_to(want, self.data.ndim):
            raise ValueError(f"bad sharding {got}, want {want}")

    def _smap(self, f, in_specs, out_specs):
        return T.shard_map(f, self.mesh, in_specs, out_specs)

    @property
    def _spec(self) -> P:
        return P(self.row_axes, self.col_axis)

    # -- paper API: add / multiply -------------------------------------------
    def add(self, other: "BlockMatrix") -> "BlockMatrix":
        if self.dims != other.dims:
            raise ValueError(f"dim mismatch {self.dims} vs {other.dims}")
        out = self._smap(jnp.add, in_specs=(self._spec, self._spec),
                         out_specs=self._spec)(self.data, other.data)
        return BlockMatrix(out, self.dims, self.mesh, self.row_axes,
                           self.col_axis)

    def multiply(self, other: "BlockMatrix") -> "BlockMatrix":
        """SUMMA: all-gather row/column panels, one local GEMM, no shuffle."""
        if self.dims[1] != other.dims[0]:
            raise ValueError(f"inner dim mismatch {self.dims} @ {other.dims}")
        if self.data.shape[1] != other.data.shape[0]:
            # Same logical k but different padding — re-pad other.
            other = BlockMatrix.create(other.to_local(), self.mesh,
                                       self.row_axes, self.col_axis)
        from repro.kernels import ops as _ops
        rows, col = self.row_axes, self.col_axis

        def body(a, b):
            # a: (m/R, k/C) at (r, c); b: (k/R, n/C) at (r, c).  The local
            # panel product is the autotuned Pallas GEMM on TPU (jnp
            # reference on CPU, identical f32-accumulated semantics).
            a_row = jax.lax.all_gather(a, col, axis=1, tiled=True)   # (m/R, k)
            b_col = jax.lax.all_gather(b, rows, axis=0, tiled=True)  # (k, n/C)
            return _ops.gemm(a_row, b_col,
                             out_dtype=jnp.float32).astype(a.dtype)

        out = self._smap(body, in_specs=(self._spec, self._spec),
                         out_specs=self._spec)(self.data, other.data)
        return BlockMatrix(out, (self.dims[0], other.dims[1]), self.mesh,
                           self.row_axes, self.col_axis)

    def transpose(self) -> "BlockMatrix":
        out = T.put(self.data.T, NamedSharding(
            self.mesh, P(self.row_axes, self.col_axis)))
        return BlockMatrix(out, (self.dims[1], self.dims[0]), self.mesh,
                           self.row_axes, self.col_axis)

    # -- matvec family ---------------------------------------------------------
    def matvec(self, v: Array) -> Array:
        """A v, v replicated → row-sharded (m,) vector."""
        rows, col = self.row_axes, self.col_axis

        def body(a, v):
            c = jax.lax.axis_index(col)
            vc = jax.lax.dynamic_slice_in_dim(v, c * a.shape[1], a.shape[1])
            return jax.lax.psum(a @ vc, col)

        return self._smap(body, in_specs=(self._spec, P()),
                          out_specs=P(rows))(self.data, v)

    def rmatvec(self, u: Array) -> Array:
        """Aᵀ u, u row-sharded → (n,) vector sharded over the model axis.
        (Logically a global vector; jit-level consumers reshard for free.)"""
        rows, col = self.row_axes, self.col_axis

        def body(a, u):
            part = a.T @ u                       # (n/C,) partial over rows
            return jax.lax.psum(part, rows)      # (n/C,) at every (·, c)

        return self._smap(body, in_specs=(self._spec, P(rows)),
                          out_specs=P(col))(self.data, u)

    # -- "vector as RDD": large linear model parallelism (refs [4, 9]) -------
    def matvec_model_sharded(self, w: Array) -> Array:
        """A w where w is itself distributed over the model axis
        (the paper's case of vectors too large for the driver)."""
        rows, col = self.row_axes, self.col_axis

        def body(a, w):
            return jax.lax.psum(a @ w, col)

        return self._smap(body, in_specs=(self._spec, P(col)),
                          out_specs=P(rows))(self.data, w)

    def rmatvec_model_sharded(self, u: Array) -> Array:
        """Aᵀ u → gradient vector kept sharded over the model axis."""
        rows, col = self.row_axes, self.col_axis

        def body(a, u):
            return jax.lax.psum(a.T @ u, rows)

        return self._smap(body, in_specs=(self._spec, P(rows)),
                          out_specs=P(col))(self.data, u)

    def frobenius_norm(self) -> Array:
        def body(a):
            return jax.lax.psum((a * a).sum(),
                                (*self.row_axes, self.col_axis))

        return jnp.sqrt(self._smap(body, in_specs=(self._spec,),
                                   out_specs=P())(self.data))

    def to_local(self) -> Array:
        return jax.device_get(self.data)[: self.dims[0], : self.dims[1]]
