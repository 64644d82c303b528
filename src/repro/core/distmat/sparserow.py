"""SparseRowMatrix — row-sharded block-sparse distributed matrix.

The paper's sparse story has two halves: §2.2's entry-sharded
CoordinateMatrix ("both dimensions huge, matrix very sparse") and §4.2's
local sparse kernels (MLlib hand-rolls CCS SpMV/SpMM because JVM BLAS has no
sparse story).  This type is the production middle ground the paper's
workloads actually sit in — m huge, n moderate, rows sparse: each device
owns a contiguous strip of block-rows stored as a BlockELL (kernels/bsr.py),
so the hot paths are Pallas BSR SpMM/SpMV/transpose-multiply on the MXU
while the distributed structure (one shard per device, vectors replicated)
is identical to RowMatrix.

Density-aware dispatch: block-sparse storage stops paying once the stored
block fraction is high — the BSR kernel pays lane/sublane padding on every
block plus a per-block grid step, the dense GEMM streams at full MXU
utilization.  Every multiply therefore consults the execution planner
(``launch/planner.plan("sparse_matmul", ...)``, priced against the one
calibrated MachineModel every dispatch decision shares) and falls back to
densify-and-GEMM when the shard is too dense for BSR to win.  The decision
is pure Python over static shapes — trace-safe; ``plan(...).explain()``
shows the roofline terms behind it.

Sampled DIMSUM (paper refs [10, 11]) lives here and on RowMatrix:
column_similarities(threshold) keeps an entry of column i with probability
pᵢ = min(1, √γ/‖cᵢ‖) — so a pair (i, j) survives with the paper's
oversampling probability min(1, γ/‖cᵢ‖‖cⱼ‖) — and rescales kept entries by
1/pᵢ, which makes the estimator unbiased off the diagonal.  threshold=0
recovers the exact scaled-Gram similarity.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field, replace
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels import bsr as _bsr
from . import types as T
from .rowmatrix import RowMatrix, _shard_index

Array = jax.Array

# Column-strip width for AᵀX products with wide X (gram, sampled DIMSUM):
# the fused bsr_rmatmul kernel keeps an (n_pad × nx) f32 accumulator
# resident in VMEM (falling back to HBM partials + segment_sum when even a
# strip would overflow the budget), so wide right-hand sides are processed
# in bounded strips.
_RMATMUL_STRIP = 512


def _rmatmul_strips(ops_mod, local, X: Array) -> Array:
    """AᵀX in column strips of _RMATMUL_STRIP (static trace-time loop)."""
    nx = X.shape[1]
    outs = [ops_mod.bsr_rmatmul(local, X[:, i: i + _RMATMUL_STRIP])
            for i in range(0, nx, _RMATMUL_STRIP)]
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


def _rup(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _best_block_size(shape: tuple[int, int], dtype, ell_of_bs,
                     nx_hint: int) -> int:
    """Block-size selection via the execution planner
    (launch/planner.plan("bsr_bs")), evaluated on the *actual* ELL width
    each candidate produces for this matrix (`ell_of_bs(bs)` — the
    nnz-only estimate in ops.bsr_block_size assumes uniform scatter, which
    is pessimistic for block-structured sparsity).  Shared by the dense and
    the COO "auto" constructors so both pick the same block size for the
    same matrix."""
    from repro.kernels import autotune as at
    from repro.launch import planner as _planner
    m, n = shape
    sub = at.sublane(dtype)
    ell_by_bs = {bs: ell_of_bs(bs) for bs in _planner.BS_CANDIDATES
                 if bs % sub == 0}
    p = _planner.plan("bsr_bs", {"m": m, "n": n, "nx": nx_hint}, dtype,
                      context={"ell_by_bs": ell_by_bs})
    return int(p.blocks["bs"])


def _auto_block_size(a: np.ndarray, nx_hint: int) -> int:
    """Auto block size for dense input: actual per-candidate block stats."""
    m, n = a.shape
    nz = a != 0

    def ell_of_bs(bs):
        mp, npd = _rup(m, bs), _rup(n, bs)
        padded = np.zeros((mp, npd), bool)
        padded[:m, :n] = nz
        blocks = padded.reshape(mp // bs, bs, npd // bs, bs)
        return max(1, int(blocks.any(axis=(1, 3)).sum(axis=1).max()))

    return _best_block_size(a.shape, a.dtype, ell_of_bs, nx_hint)


# A pytree over its device arrays (see RowMatrix).
@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["data", "cols", "scales"],
                   meta_fields=["dims", "nnz", "mesh", "row_axes"])
@dataclass(frozen=True)
class SparseRowMatrix(T.DistMatrix):
    data: Array                 # (nbr_pad, ell, bs, bs), sharded P(row_axes)
    cols: Array                 # (nbr_pad, ell) int32,   sharded P(row_axes)
    dims: tuple[int, int]       # true (m, n) before any padding
    nnz: int
    mesh: Mesh = field(repr=False)
    row_axes: tuple[str, ...] = T.ROW_AXES
    # Per-stored-block f32 dequantization scales (nbr_pad, ell), sharded
    # like data — present iff the blocks are int8-quantized (kernels/bsr
    # quantized mode); None means exact storage.
    scales: Array | None = None

    # -- construction --------------------------------------------------------
    @staticmethod
    def from_dense(a, bs: int | str = "auto", mesh: Mesh | None = None,
                   row_axes: Sequence[str] | None = None, *,
                   nx_hint: int = 128, quantize: str = "none",
                   tol: float = 1e-3) -> "SparseRowMatrix":
        """Driver-scale constructor: block-compress a local dense matrix and
        scatter contiguous block-row strips across the mesh.

        `quantize` follows kernels/bsr.BlockELL.from_dense: "int8" stores
        blocks as int8 with per-block f32 scales, "auto" lets the planner's
        precision sweep decide whether int8 clears the `tol` guard and
        pays for itself, "none" keeps exact storage."""
        mesh = mesh or T.single_device_mesh()
        row_axes = tuple(row_axes) if row_axes else T.row_axes_for(mesh)
        nshards = T.axes_size(mesh, row_axes)
        a = np.asarray(jax.device_get(a))
        m, n = a.shape
        if bs == "auto":
            bs = _auto_block_size(a, nx_hint)
        bs = int(bs)
        n_pad = _rup(n, bs)
        nbr_pad = _rup(_rup(m, bs) // bs, nshards)
        padded = np.zeros((nbr_pad * bs, n_pad), a.dtype)
        padded[:m, :n] = a
        bell = _bsr.BlockELL.from_dense(padded, bs, quantize=quantize,
                                        tol=tol)
        sh = NamedSharding(mesh, P(row_axes))
        return SparseRowMatrix(T.put(bell.data, sh), T.put(bell.cols, sh),
                               dims=(m, n), nnz=int(np.count_nonzero(a)),
                               mesh=mesh, row_axes=row_axes,
                               scales=(None if bell.scales is None
                                       else T.put(bell.scales, sh)))

    @staticmethod
    def from_entries(row_idx, col_idx, values, shape: tuple[int, int],
                     bs: int | str = "auto", mesh: Mesh | None = None,
                     row_axes: Sequence[str] | None = None
                     ) -> "SparseRowMatrix":
        """COO entries → block-ELL without materializing the dense matrix:
        entries are binned into (block-row, block-col) keys with one
        np.unique + np.add.at pass — no per-entry Python loop, no shuffle
        (each block-row strip lands whole on its shard)."""
        mesh = mesh or T.single_device_mesh()
        row_axes = tuple(row_axes) if row_axes else T.row_axes_for(mesh)
        nshards = T.axes_size(mesh, row_axes)
        ri = np.asarray(jax.device_get(row_idx), np.int64)
        ci = np.asarray(jax.device_get(col_idx), np.int64)
        va = np.asarray(jax.device_get(values))
        m, n = shape
        if bs == "auto":
            bs = _entries_block_size(ri, ci, shape, va.dtype)
        bs = int(bs)
        n_pad = _rup(n, bs)
        nbc = n_pad // bs
        nbr_pad = _rup(_rup(m, bs) // bs, nshards)
        key = (ri // bs) * nbc + (ci // bs)
        uniq, inv = np.unique(key, return_inverse=True)
        blocks = np.zeros((max(len(uniq), 1), bs, bs), va.dtype)
        np.add.at(blocks, (inv, ri % bs, ci % bs), va)
        ubi, ubj = uniq // nbc, uniq % nbc
        counts = np.bincount(ubi, minlength=nbr_pad)
        ell = max(1, int(counts.max(initial=0)))
        starts = np.concatenate([[0], np.cumsum(counts)])
        slot = np.arange(len(uniq)) - starts[ubi]
        data = np.zeros((nbr_pad, ell, bs, bs), va.dtype)
        cols = np.zeros((nbr_pad, ell), np.int32)
        data[ubi, slot] = blocks[: len(uniq)]
        cols[ubi, slot] = ubj
        sh = NamedSharding(mesh, P(row_axes))
        nnz = int(np.count_nonzero(blocks))
        return SparseRowMatrix(T.put(data, sh), T.put(cols, sh),
                               dims=(m, n), nnz=nnz, mesh=mesh,
                               row_axes=row_axes)

    def remesh(self, mesh: Mesh, row_axes: Sequence[str] | None = None
               ) -> "SparseRowMatrix":
        """Re-shard the SAME logical matrix onto a different mesh (elastic
        re-mesh, train/elastic): the block-row strips are re-padded for the
        new shard count (padding block-rows are all-zero blocks with column
        0, which contribute nothing) and device_put with the new sharding.
        Block size, ELL width and the stored blocks are unchanged."""
        mesh = mesh or T.single_device_mesh()
        row_axes = tuple(row_axes) if row_axes else T.row_axes_for(mesh)
        nshards = T.axes_size(mesh, row_axes)
        nbr_true = _rup(self.dims[0], self.bs) // self.bs
        nbr_pad = _rup(nbr_true, nshards)
        data, cols, scales = self.data, self.cols, self.scales
        if nbr_pad <= data.shape[0]:
            data, cols = data[:nbr_pad], cols[:nbr_pad]
            if scales is not None:
                scales = scales[:nbr_pad]
        else:
            extra = nbr_pad - data.shape[0]
            data = jnp.concatenate(
                [data, jnp.zeros((extra,) + data.shape[1:], data.dtype)])
            cols = jnp.concatenate(
                [cols, jnp.zeros((extra,) + cols.shape[1:], cols.dtype)])
            if scales is not None:
                # Padding block-rows hold all-zero blocks: scale 1.0 (the
                # quantizer's zero-block convention).
                scales = jnp.concatenate(
                    [scales, jnp.ones((extra,) + scales.shape[1:],
                                      scales.dtype)])
        sh = NamedSharding(mesh, P(row_axes))
        return SparseRowMatrix(T.put(data, sh), T.put(cols, sh),
                               dims=self.dims, nnz=self.nnz, mesh=mesh,
                               row_axes=row_axes,
                               scales=(None if scales is None
                                       else T.put(scales, sh)))

    # -- bookkeeping ---------------------------------------------------------
    @property
    def shape(self) -> tuple[int, int]:
        return self.dims

    @property
    def bs(self) -> int:
        return self.data.shape[-1]

    @property
    def ell(self) -> int:
        return self.data.shape[1]

    @property
    def n_pad(self) -> int:
        return _rup(self.dims[1], self.bs)

    @property
    def m_pad(self) -> int:
        return self.data.shape[0] * self.bs

    def block_density(self) -> float:
        """Stored block fraction — the number density-aware dispatch acts on."""
        return self.ell / (self.n_pad // self.bs)

    @property
    def out_dtype(self):
        """Logical result dtype: float32 for quantized or sub-f32 storage."""
        d = self.data.dtype
        return jnp.dtype(jnp.float32) if (self.scales is not None
                                          or d.itemsize < 4) else d

    def dequantize(self) -> "SparseRowMatrix":
        """Exact-f32 copy (identity when storage is already exact) — the
        cold paths (stats, DIMSUM, materialization) route through this
        instead of threading scales everywhere."""
        if self.scales is None:
            return self
        data = self.data.astype(jnp.float32) * self.scales[..., None, None]
        return replace(self, data=data, scales=None)

    def astype_store(self, dtype) -> "SparseRowMatrix":
        """Recast the stored blocks.  int8 quantizes with per-block f32
        scales (absmax/127, zero blocks get scale 1.0); any float dtype
        dequantizes first and recasts.  Sharding is preserved."""
        if isinstance(dtype, str) and dtype == "int8":
            dtype = jnp.int8
        dtype = jnp.dtype(dtype)
        if dtype == jnp.int8:
            if self.scales is not None:
                return self
            d = self.data.astype(jnp.float32)
            absmax = jnp.max(jnp.abs(d), axis=(2, 3))
            scales = jnp.where(absmax > 0, absmax / 127.0, 1.0)
            q = jnp.round(d / scales[..., None, None]).astype(jnp.int8)
            return replace(self, data=q, scales=scales)
        out = self.dequantize()
        if dtype == out.data.dtype:
            return out
        return replace(out, data=out.data.astype(dtype))

    def _smap(self, f, in_specs, out_specs):
        return T.shard_map(f, self.mesh, in_specs, out_specs)

    @property
    def _dspec(self) -> P:
        return P(self.row_axes)

    def _local_rows(self) -> int:
        nshards = T.axes_size(self.mesh, self.row_axes)
        return self.m_pad // nshards

    def _use_bsr(self, nx: int, dispatch: str) -> bool:
        """Per-shard BSR-vs-dense decision (static, trace-safe) via the
        execution planner (launch/planner.plan("sparse_matmul"))."""
        if dispatch in ("bsr", "dense"):
            return dispatch == "bsr"
        if dispatch != "auto":
            raise ValueError(f"dispatch must be auto | bsr | dense, "
                             f"got {dispatch!r}")
        from repro.launch import planner as _planner
        return _planner.plan(
            "sparse_matmul",
            {"m": self._local_rows(), "n": self.n_pad,
             "nx": max(nx, 1), "ell": self.ell, "bs": self.bs},
            self.data.dtype.name).choice == "bsr"

    def _collective_plan(self, op: str, dims):
        """Comm-priced plan for a distributed op on this mesh (see
        RowMatrix._collective_plan; the dense-terms model is an upper bound
        for the sparse shard's compute, which is fine for the chunk
        decision — it only moves the overlap break-even conservatively)."""
        from repro.launch import mesh as _mesh
        from repro.launch import planner as _planner
        return _planner.plan(
            op, dims, self.data.dtype.name,
            context={"axes": _mesh.axis_sizes(self.mesh, self.row_axes)})

    def _resolve_chunks(self, chunks, plan) -> int:
        if chunks == "auto":
            return int(plan.blocks.get("chunks", 1))
        return max(int(chunks), 1)

    def _local(self, data: Array, cols: Array,
               scales: Array | None = None) -> _bsr.BlockELL:
        """The shard's BlockELL view (called inside shard_map bodies)."""
        return _bsr.BlockELL(data, cols, (data.shape[0] * self.bs,
                                          self.n_pad), scales)

    def _scale_ops(self) -> tuple:
        """Trailing shard_map operand for the quantization scales — empty
        for exact storage so existing two-operand bodies are unchanged."""
        return () if self.scales is None else (self.scales,)

    def _scale_specs(self) -> tuple:
        return () if self.scales is None else (self._dspec,)

    def _row_mask(self) -> Array:
        """Row-sharded {0,1} mask of true (non-padding) rows."""
        m = self.dims[0]
        local = self._local_rows()
        axes = self.row_axes

        def body():
            start = _shard_index(axes) * local
            return ((start + jnp.arange(local)) < m).astype(self.out_dtype)

        return self._smap(body, in_specs=(), out_specs=P(self.row_axes))()

    # -- cluster matrix ops --------------------------------------------------
    def matvec(self, v: Array, *, dispatch: str = "auto") -> Array:
        """A v with v replicated (driver) → row-sharded (m_pad,) result."""
        from repro.kernels import ops as _ops
        use_bsr = self._use_bsr(1, dispatch)
        vp = jnp.pad(jnp.asarray(v), (0, self.n_pad - self.dims[1]))

        def body(data, cols, v, *sc):
            local = self._local(data, cols, *sc)
            if use_bsr:
                return _ops.bsr_matvec(local, v)
            return local.to_dense() @ v

        return self._smap(body,
                          in_specs=(self._dspec, self._dspec, P())
                          + self._scale_specs(),
                          out_specs=P(self.row_axes))(
            self.data, self.cols, vp, *self._scale_ops())

    def rmatvec(self, u: Array, *, dispatch: str = "auto") -> Array:
        """Aᵀ u with u row-sharded → replicated (n,) vector (driver)."""
        from repro.kernels import ops as _ops
        axes = self.row_axes
        use_bsr = self._use_bsr(1, dispatch)
        u = jnp.asarray(u)
        if u.shape[0] != self.m_pad:
            u = jnp.pad(u, (0, self.m_pad - u.shape[0]))

        def body(data, cols, u, *sc):
            local = self._local(data, cols, *sc)
            if use_bsr:
                out = _ops.bsr_rmatmul(local, u[:, None])[:, 0]
            else:
                out = local.to_dense().T @ u
            return jax.lax.psum(out, axes)

        out = self._smap(body,
                         in_specs=(self._dspec, self._dspec, P(axes))
                         + self._scale_specs(),
                         out_specs=P())(self.data, self.cols, u,
                                        *self._scale_ops())
        return out[: self.dims[1]]

    def multiply_local(self, B: Array, *,
                       dispatch: str = "auto") -> RowMatrix:
        """A @ B for a small replicated B — the `U = A (VΣ⁻¹)` pattern.
        The product of a sparse matrix with a dense factor is dense, so the
        result is a RowMatrix (same row sharding, no collectives)."""
        from repro.kernels import ops as _ops
        B = jnp.asarray(B)
        use_bsr = self._use_bsr(B.shape[1], dispatch)
        Bp = jnp.pad(B, ((0, self.n_pad - self.dims[1]), (0, 0)))

        def body(data, cols, b, *sc):
            local = self._local(data, cols, *sc)
            if use_bsr:
                return _ops.bsr_matmul(local, b)
            return _ops.gemm(local.to_dense(), b, out_dtype=b.dtype)

        out = self._smap(body,
                         in_specs=(self._dspec, self._dspec, P())
                         + self._scale_specs(),
                         out_specs=P(self.row_axes, None))(
            self.data, self.cols, Bp, *self._scale_ops())
        return RowMatrix(rows=out, n_rows=self.dims[0], mesh=self.mesh,
                         row_axes=self.row_axes)

    def init_psum_residual(self) -> Array:
        """Zeroed per-shard f32 error-feedback residual for the compressed
        ("psum8") fused_grad reduction — see RowMatrix.init_psum_residual.
        Sized to the padded column count (the kernel-facing gradient)."""
        nshards = T.axes_size(self.mesh, self.row_axes)
        z = jnp.zeros((nshards, self.n_pad), jnp.float32)
        return T.put(z, NamedSharding(self.mesh, P(self.row_axes, None)))

    def fused_grad(self, x: Array, smooth, *, dispatch: str = "auto",
                   chunks: int | str = "auto",
                   residual: Array | None = None):
        """(f(Ax), Aᵀ∇f(Ax), Ax) in one pass over the stored blocks — the
        BSR form of the fused composite gradient (kernels/fusedgrad): z for
        a block-row accumulates while its blocks are staged in VMEM, the
        row-local residual is evaluated on-chip, and the transpose
        contributions scatter-add into a resident accumulator.  Dense
        fallback (densify + dense fused kernel) under the same density-aware
        dispatch as every other multiply.

        `chunks` > 1 runs the comm-overlapped schedule (planner-chosen on
        "auto", via plan("grad") with this mesh's axis sizes).  The dense
        fallback arm gets the full two-phase split RowMatrix.fused_grad
        uses (per-column-segment r·A[:, seg] contractions overlapping the
        partial psums); the BSR arm keeps its one-pass kernel — re-reading
        the stored blocks per segment would forfeit exactly the fusion the
        kernel exists for — and pipelines the gradient *reduction* in
        column segments instead, so successive partial psums overlap each
        other and the f psum.  Both arms are bit-identical to eager
        (segmented psums of the same per-shard values).

        `residual` (from init_psum_residual) switches the gradient psums
        to the compressed int8 wire with error feedback — see
        RowMatrix.fused_grad; returns (f, g, z, new_residual)."""
        from repro.kernels import fusedgrad as _fg
        from repro.kernels import ops as _ops
        from repro.launch import telemetry as _tel
        from repro.train import compression as _comp
        from .rowmatrix import _record_collective, chunk_bounds
        use_bsr = self._use_bsr(1, dispatch)
        axes = self.row_axes
        nshards = T.axes_size(self.mesh, self.row_axes)
        quant = self.scales is not None
        n = self.dims[1]
        kind, t, w, prm = T.row_separable_inputs(smooth, self.m_pad,
                                                 self._row_mask)
        x = jnp.asarray(x)
        xp = jnp.pad(x, (0, self.n_pad - x.shape[0])) \
            if x.shape[0] < self.n_pad else x
        plan = self._collective_plan("grad", {"m": self._local_rows(),
                                              "n": self.n_pad})
        c = self._resolve_chunks(chunks, plan)
        bounds = chunk_bounds(self.n_pad, c)

        def _reduce(f, g, z, res):
            """Gradient reduction in column segments (c > 1 pipelines the
            partial psums); int8 wire when an EF residual came in."""
            segs = bounds if c > 1 else ((0, self.n_pad),)
            if res is not None:
                gs, rs = [], []
                for s0, s1 in segs:
                    gseg, rseg = _comp.psum_int8(g[s0:s1], res[0, s0:s1],
                                                 axes, nshards)
                    gs.append(gseg)
                    rs.append(rseg)
                return (jax.lax.psum(f, axes), jnp.concatenate(gs), z,
                        jnp.concatenate(rs)[None])
            gs = [jax.lax.psum(g[s0:s1], axes) for s0, s1 in segs]
            return jax.lax.psum(f, axes), jnp.concatenate(gs), z

        def body(data, cols, xp, t, w, *rest):
            sc, rest = (rest[:1], rest[1:]) if quant else ((), rest)
            res = rest[0] if rest else None
            local = self._local(data, cols, *sc)
            if use_bsr:
                f, g, z = _ops.fused_grad_bsr(local, xp, t, w, loss=kind,
                                              param=prm)
                return _reduce(f, g, z, res)
            if c > 1 and res is None:
                # Two-phase dense split — fused_grad_jnp's exact math with
                # the gradient built per column segment (see RowMatrix).
                dense = local.to_dense()
                z = jnp.dot(dense, xp, preferred_element_type=jnp.float32)
                f, r = _fg.row_loss_grad(z, t, w, kind, prm)
                rc = r.astype(dense.dtype) \
                    if dense.dtype == jnp.float32 else r
                gs = [jax.lax.psum(
                    jnp.dot(rc, dense[:, s0:s1],
                            preferred_element_type=jnp.float32)
                    .astype(xp.dtype), axes) for s0, s1 in bounds]
                return jax.lax.psum(f, axes), jnp.concatenate(gs), z
            f, g, z = _ops.fused_grad(local.to_dense(), xp, t, w,
                                      loss=kind, param=prm)
            return _reduce(f, g, z, res)

        wire = "int8" if residual is not None else "f32"
        base_specs = (self._dspec, self._dspec, P(), P(axes), P(axes)) \
            + self._scale_specs()
        base_ops = (self.data, self.cols, xp, t, w) + self._scale_ops()
        with _tel.current().span("collective.fused_grad", op="grad",
                                 n=self.n_pad, chunks=c, wire=wire) as sp:
            if residual is None:
                f, g, z = self._smap(
                    body, in_specs=base_specs,
                    out_specs=(P(), P(), P(axes)))(*base_ops)
                out = (f, g[:n], z)
            else:
                f, g, z, nres = self._smap(
                    body, in_specs=base_specs + (P(self.row_axes, None),),
                    out_specs=(P(), P(), P(axes),
                               P(self.row_axes, None)))(*base_ops, residual)
                out = (f, g[:n], z, nres)
            sp.sync_on(out[1])
        _record_collective(plan, sp, collective="psum", chunks=c, wire=wire)
        return out

    def fused_grad_multi(self, x: Array, smooths, *,
                         dispatch: str = "auto"
                         ) -> tuple[Array, Array, Array]:
        """Request-batched fused gradients over the stored blocks: a GROUP
        of k right-hand sides answered with ONE read of each stored block
        (the BSR multi-RHS kernel), under the same density-aware dispatch
        as fused_grad.  `x` (k × n); `smooths` a sequence of k
        row-separable smooths sharing one loss kind/param.  Returns
        (replicated (k,) values, replicated (k × n) gradients, image
        sharded (k × m_pad) over the row axes)."""
        from repro.kernels import ops as _ops
        use_bsr = self._use_bsr(1, dispatch)
        axes = self.row_axes
        n = self.dims[1]
        kind, t, w, prm = T.row_separable_batch_inputs(smooths, self.m_pad,
                                                       self._row_mask)
        x = jnp.atleast_2d(jnp.asarray(x))
        xp = jnp.pad(x, ((0, 0), (0, self.n_pad - x.shape[1]))) \
            if x.shape[1] < self.n_pad else x

        def body(data, cols, xp, t, w, *sc):
            local = self._local(data, cols, *sc)
            if use_bsr:
                f, g, z = _ops.fused_grad_bsr_multi(local, xp, t, w,
                                                    loss=kind, param=prm)
            else:
                f, g, z = _ops.fused_grad_multi(local.to_dense(), xp, t, w,
                                                loss=kind, param=prm)
            return jax.lax.psum(f, axes), jax.lax.psum(g, axes), z

        f, g, z = self._smap(
            body,
            in_specs=(self._dspec, self._dspec, P(), P(None, axes),
                      P(None, axes)) + self._scale_specs(),
            out_specs=(P(), P(), P(None, axes)))(
            self.data, self.cols, xp, t, w, *self._scale_ops())
        return f, g[:, :n], z

    def gram(self, *, dispatch: str = "auto") -> Array:
        """AᵀA, replicated — per-shard AᵀA with the sparse operand on the
        transpose side (flops ∝ stored blocks · n), then a tree all-reduce.
        Falls back to the dense tsgram kernel when the shard is dense."""
        from repro.kernels import ops as _ops
        axes = self.row_axes
        use_bsr = self._use_bsr(self.n_pad, dispatch)

        def body(data, cols, *sc):
            local = self._local(data, cols, *sc)
            dense = local.to_dense()
            if use_bsr:
                g = _rmatmul_strips(_ops, local, dense.astype(jnp.float32))
            else:
                g = _ops.tsgram(dense, out_dtype=jnp.float32)
            return jax.lax.psum(g, axes)

        out = self._smap(body,
                         in_specs=(self._dspec, self._dspec)
                         + self._scale_specs(),
                         out_specs=P())(self.data, self.cols,
                                        *self._scale_ops())
        n = self.dims[1]
        return out[:n, :n].astype(self.out_dtype)

    def frobenius_norm(self) -> Array:
        if self.scales is not None:
            return self.dequantize().frobenius_norm()
        axes = self.row_axes

        def body(data):
            return jax.lax.psum((data * data).sum(), axes)

        return jnp.sqrt(self._smap(body, in_specs=(self._dspec,),
                                   out_specs=P())(self.data))

    def column_norms(self) -> Array:
        """Replicated per-column L2 norms (the DIMSUM scaling vector)."""
        if self.scales is not None:
            return self.dequantize().column_norms()
        axes, bs = self.row_axes, self.bs
        nbc = self.n_pad // bs

        def body(data, cols):
            sq = (data * data).sum(axis=2)            # (nbr_l, ell, bs)
            out = jnp.zeros((nbc, bs), sq.dtype).at[cols].add(sq)
            return jax.lax.psum(out.reshape(-1), axes)

        out = self._smap(body, in_specs=(self._dspec, self._dspec),
                         out_specs=P())(self.data, self.cols)
        return jnp.sqrt(out[: self.dims[1]])

    def scale_columns(self, d: Array) -> "SparseRowMatrix":
        """A · diag(d) with replicated d — scales stored blocks in place
        (the sparsity pattern is unchanged, so cols are shared).
        Quantized storage dequantizes first: per-column scaling breaks the
        shared per-block scale."""
        if self.scales is not None:
            return self.dequantize().scale_columns(d)
        bs = self.bs
        dp = jnp.pad(jnp.asarray(d), (0, self.n_pad - self.dims[1]))
        db = dp.reshape(-1, bs)                       # (nbc, bs)

        def body(data, cols, db):
            return data * db[cols][:, :, None, :]

        out = self._smap(body, in_specs=(self._dspec, self._dspec, P()),
                         out_specs=self._dspec)(self.data, self.cols, db)
        return replace(self, data=out)

    # -- DIMSUM --------------------------------------------------------------
    def column_similarities(self, threshold: float = 0.0, *,
                            gamma: float | None = None,
                            seed: int = 0, return_info: bool = False):
        """Sampled DIMSUM cosine similarities (see module docstring).
        threshold=0 → exact scaled-Gram path.  return_info=True returns
        (sim, info) with the sampling diagnostics — γ, per-column keep
        probabilities p, and the exact per-pair estimator variance
        Σ_k (ã_ki ã_kj)²·(1/(pᵢpⱼ) − 1) (ã column-scaled), which shrinks
        to 0 as γ grows."""
        if self.scales is not None:
            return self.dequantize().column_similarities(
                threshold, gamma=gamma, seed=seed, return_info=return_info)
        from repro.kernels import ops as _ops
        norms = self.column_norms()
        inv = jnp.where(norms > 0, 1.0 / jnp.maximum(norms, 1e-30), 0.0)
        if threshold <= 0.0:
            sim = self.scale_columns(inv).gram()
            if not return_info:
                return sim
            nd = self.dims[1]
            return sim, {"gamma": None, "p": jnp.ones((nd,), jnp.float32),
                         "variance": jnp.zeros((nd, nd), jnp.float32)}
        n, bs = self.dims[1], self.bs
        g = gamma if gamma is not None else dimsum_gamma(n, threshold)
        p = jnp.minimum(1.0, math.sqrt(g) * inv)
        scale = inv * jnp.where(p > 0, 1.0 / p, 0.0)
        pad = self.n_pad - n
        pb = jnp.pad(p, (0, pad)).reshape(-1, bs)
        sb = jnp.pad(scale, (0, pad)).reshape(-1, bs)
        axes = self.row_axes
        use_bsr = self._use_bsr(self.n_pad, "auto")

        def body(data, cols, pb, sb):
            key = jax.random.fold_in(jax.random.PRNGKey(seed),
                                     _shard_index(axes))
            keep = jax.random.uniform(key, data.shape) < pb[cols][:, :, None, :]
            d2 = jnp.where(keep, data, 0.0) * sb[cols][:, :, None, :]
            local = self._local(d2, cols)
            dense = local.to_dense()
            if use_bsr:
                g_ = _rmatmul_strips(_ops, local, dense.astype(jnp.float32))
            else:
                g_ = _ops.tsgram(dense, out_dtype=jnp.float32)
            return jax.lax.psum(g_, axes)

        sim = self._smap(body,
                         in_specs=(self._dspec, self._dspec, P(), P()),
                         out_specs=P())(self.data, self.cols, pb, sb)
        sim = sim[:n, :n].astype(self.out_dtype)
        # The diagonal estimator is biased (E[b²] = a²/p); its true value is
        # known exactly, so write it instead (MLlib does the same).
        diag = (norms > 0).astype(sim.dtype)
        sim = sim.at[jnp.arange(n), jnp.arange(n)].set(diag)
        if not return_info:
            return sim
        scaled = self.scale_columns(inv)
        sq = replace(scaled, data=scaled.data * scaled.data)
        s2 = sq.gram().astype(jnp.float32)
        var = T.dimsum_variance(s2, p)
        return sim, {"gamma": g, "p": p, "variance": var}

    # -- conversions ---------------------------------------------------------
    def to_row_matrix(self) -> RowMatrix:
        """Densify each shard in place — no collectives (shuffle-free): the
        block-row strips already live where RowMatrix wants the rows."""
        n = self.dims[1]

        def body(data, cols, *sc):
            return self._local(data, cols, *sc).to_dense()[:, :n]

        out = self._smap(body,
                         in_specs=(self._dspec, self._dspec)
                         + self._scale_specs(),
                         out_specs=P(self.row_axes, None))(
            self.data, self.cols, *self._scale_ops())
        return RowMatrix(rows=out, n_rows=self.dims[0], mesh=self.mesh,
                         row_axes=self.row_axes)

    def to_local(self) -> Array:
        if self.scales is not None:
            return self.dequantize().to_local()
        data = np.asarray(jax.device_get(self.data))
        cols = np.asarray(jax.device_get(self.cols))
        nbr, ell, bs = data.shape[0], data.shape[1], data.shape[-1]
        nbc = self.n_pad // bs
        out = np.zeros((nbr, nbc, bs, bs), data.dtype)
        np.add.at(out, (np.arange(nbr)[:, None], cols), data)
        dense = out.transpose(0, 2, 1, 3).reshape(self.m_pad, self.n_pad)
        return jnp.asarray(dense[: self.dims[0], : self.dims[1]])

    def transpose(self) -> "SparseRowMatrix":
        """Driver-scale transpose (the paper's format-conversion warning
        applies: this is a global reshuffle, done on the driver here)."""
        return SparseRowMatrix.from_dense(
            np.asarray(jax.device_get(self.to_local())).T, bs=self.bs,
            mesh=self.mesh, row_axes=self.row_axes)

    # -- linalg entry point --------------------------------------------------
    def compute_svd(self, k: int, **kw):
        from repro.core.linalg import svd as _svd
        return _svd.compute_svd(self, k, **kw)


def dimsum_gamma(n: int, threshold: float) -> float:
    """The paper's oversampling parameter: γ = 10·log(n)/threshold keeps the
    estimate of every pair with similarity ≥ threshold within ~20% relative
    error w.h.p. (DIMSUM analysis, refs [10, 11])."""
    return 10.0 * math.log(max(n, 2)) / threshold


def _entries_block_size(ri, ci, shape, dtype, *, nx_hint: int = 128) -> int:
    """Auto block size for COO input: per-candidate actual ELL widths from
    the index arrays alone (no densification)."""
    n = shape[1]

    def ell_of_bs(bs):
        nbc = _rup(n, bs) // bs
        key = np.unique((ri // bs) * nbc + (ci // bs))
        counts = np.bincount(key // nbc, minlength=1)
        return max(1, int(counts.max(initial=0)))

    return _best_block_size(shape, dtype, ell_of_bs, nx_hint)
