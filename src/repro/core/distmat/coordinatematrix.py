"""CoordinateMatrix — entry-sharded COO distributed matrix (paper §2.2).

"Should be used only when both dimensions of the matrix are huge and the
matrix is very sparse."  The RDD[MatrixEntry] becomes three 1-D arrays
(row, col, value) sharded over the nnz dimension.  Vectors (length m or n)
are replicated — the paper's operating assumption for the square-SVD case is
precisely that the matrix does not fit on one machine but vectors do.

matvec/rmatvec are the operations ARPACK-style Lanczos needs; they are
implemented as shard_map bodies: local gather + segment_sum, then a tree
all-reduce over the entry shards.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import types as T

Array = jax.Array


@dataclass(frozen=True)
class CoordinateMatrix(T.DistMatrix):
    row_idx: Array                  # (nnz_padded,) int32, sharded P(row_axes)
    col_idx: Array                  # (nnz_padded,) int32, sharded P(row_axes)
    values: Array                   # (nnz_padded,) float, sharded P(row_axes)
    dims: tuple[int, int]
    nnz: int
    mesh: Mesh = field(repr=False)
    row_axes: tuple[str, ...] = T.ROW_AXES

    @staticmethod
    def create(row_idx: Array, col_idx: Array, values: Array,
               shape: tuple[int, int], mesh: Mesh | None = None,
               row_axes: Sequence[str] | None = None) -> "CoordinateMatrix":
        mesh = mesh or T.single_device_mesh()
        row_axes = tuple(row_axes) if row_axes else T.row_axes_for(mesh)
        nshards = T.axes_size(mesh, row_axes)
        nnz = int(values.shape[0])
        # Pad with explicit zeros at entry (0, 0) — harmless under summation.
        ri, _ = T.pad_rows(jnp.asarray(row_idx, jnp.int32), nshards)
        ci, _ = T.pad_rows(jnp.asarray(col_idx, jnp.int32), nshards)
        va, _ = T.pad_rows(jnp.asarray(values), nshards)
        sh = NamedSharding(mesh, P(row_axes))
        return CoordinateMatrix(T.put(ri, sh), T.put(ci, sh), T.put(va, sh),
                                dims=shape, nnz=nnz, mesh=mesh,
                                row_axes=row_axes)

    @property
    def shape(self) -> tuple[int, int]:
        return self.dims

    def _smap(self, f, in_specs, out_specs):
        return T.shard_map(f, self.mesh, in_specs, out_specs)

    def matvec(self, v: Array) -> Array:
        """A v: gather v at col indices, segment-sum into rows, all-reduce."""
        m, axes = self.dims[0], self.row_axes
        spec = P(self.row_axes)

        def body(ri, ci, va, v):
            contrib = va * v[ci]
            local = jax.ops.segment_sum(contrib, ri, num_segments=m)
            return jax.lax.psum(local, axes)

        return self._smap(body, in_specs=(spec, spec, spec, P()),
                          out_specs=P())(self.row_idx, self.col_idx,
                                         self.values, v)

    def rmatvec(self, u: Array) -> Array:
        """Aᵀ u — symmetric role swap of matvec."""
        n, axes = self.dims[1], self.row_axes
        spec = P(self.row_axes)

        def body(ri, ci, va, u):
            contrib = va * u[ri]
            local = jax.ops.segment_sum(contrib, ci, num_segments=n)
            return jax.lax.psum(local, axes)

        return self._smap(body, in_specs=(spec, spec, spec, P()),
                          out_specs=P())(self.row_idx, self.col_idx,
                                         self.values, u)

    def frobenius_norm(self) -> Array:
        spec = P(self.row_axes)

        def body(va):
            return jax.lax.psum((va * va).sum(), self.row_axes)

        return jnp.sqrt(self._smap(body, in_specs=(spec,),
                                   out_specs=P())(self.values))

    def transpose(self) -> "CoordinateMatrix":
        """Aᵀ by swapping the index arrays — entry sharding makes the
        transpose free (no shuffle, no copy); the SVD transpose dispatch
        for wide-and-short inputs rides on this."""
        return CoordinateMatrix(row_idx=self.col_idx, col_idx=self.row_idx,
                                values=self.values,
                                dims=(self.dims[1], self.dims[0]),
                                nnz=self.nnz, mesh=self.mesh,
                                row_axes=self.row_axes)

    # -- conversions (paper: toIndexedRowMatrix; global shuffle warning) ----
    def to_indexed_row_matrix(self):
        """Densify rows (test/driver scale only — the paper warns that format
        conversion is a global shuffle; here it is an all-gather + scatter)."""
        from .rowmatrix import IndexedRowMatrix
        ri = np.asarray(jax.device_get(self.row_idx))[: self.nnz]
        ci = np.asarray(jax.device_get(self.col_idx))[: self.nnz]
        va = np.asarray(jax.device_get(self.values))[: self.nnz]
        uniq, inv = np.unique(ri, return_inverse=True)
        dense = np.zeros((len(uniq), self.dims[1]), va.dtype)
        np.add.at(dense, (inv, ci), va)
        return IndexedRowMatrix.create(jnp.asarray(uniq), jnp.asarray(dense),
                                       self.mesh, self.row_axes)

    def to_sparse_row_matrix(self, bs: int | str = "auto"):
        """Block-compress into the row-sharded BSR type: entries are binned
        into (block-row, block-col) blocks in one vectorized pass and each
        contiguous block-row strip lands whole on its shard — no all-to-all
        (the paper's shuffle warning does not apply)."""
        from .sparserow import SparseRowMatrix
        ri = np.asarray(jax.device_get(self.row_idx))[: self.nnz]
        ci = np.asarray(jax.device_get(self.col_idx))[: self.nnz]
        va = np.asarray(jax.device_get(self.values))[: self.nnz]
        return SparseRowMatrix.from_entries(ri, ci, va, self.dims, bs=bs,
                                            mesh=self.mesh,
                                            row_axes=self.row_axes)

    def to_block_matrix(self, block_rows: int, block_cols: int):
        from .blockmatrix import BlockMatrix
        return BlockMatrix.create(self.to_local(), self.mesh,
                                  block_rows=block_rows, block_cols=block_cols)

    def to_local(self) -> Array:
        ri = np.asarray(jax.device_get(self.row_idx))[: self.nnz]
        ci = np.asarray(jax.device_get(self.col_idx))[: self.nnz]
        va = np.asarray(jax.device_get(self.values))[: self.nnz]
        out = np.zeros(self.dims, va.dtype)
        np.add.at(out, (ri, ci), va)
        return jnp.asarray(out)
