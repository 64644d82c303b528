"""RowMatrix / IndexedRowMatrix — row-sharded distributed matrices.

Paper §2.1: "a row-oriented distributed matrix ... backed by an RDD of its
rows, where each row is a local vector".  On the TPU mesh the RDD becomes a
2-D array sharded over the row axes (('pod','data') on multi-pod meshes) and
"local vector" means the row lives whole inside one device's HBM shard.

All cluster/driver separation from the paper is explicit here:
  * matrix ops (gram, matvec, multiply_local, column stats) are `shard_map`
    bodies — they run on the cluster shards with explicit collectives;
  * vector results (gram output, rmatvec output, stats) come back replicated
    (the "driver" copy, which on a TPU pod is every chip redundantly).

Each body is a module-level function of its static arguments, run as a
cached jitted program (types.program) keyed by the op, the mesh, the row
axes and those statics.  An eager shard_map of a fresh closure traces,
lowers and looks up its program again on every call (and a body with no
array inputs, the row mask, runs op by op), which cost more host time per
solve or SVD than the device spent on A; the cached program is dispatched
by jit's C++ fast path.  Called inside a trace (the solver's loop body) it
is a nested jit of the same computation.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field, replace
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels import fusedgrad as _fg
from repro.kernels import ops as _ops
from repro.train import compression as _comp

from . import types as T

Array = jax.Array


def _shard_index(axes: Sequence[str]) -> Array:
    """Flat index of this shard along the given (major→minor) mesh axes."""
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def chunk_bounds(n: int, chunks: int) -> tuple[tuple[int, int], ...]:
    """Static column-segment bounds for the overlapped collective bodies:
    `chunks` contiguous [s0, s1) segments covering [0, n)."""
    c = max(min(int(chunks), n), 1)
    step = -(-n // c)
    return tuple((s0, min(s0 + step, n)) for s0 in range(0, n, step))


def _out_dtype(dtype):
    """Logical result dtype of a matrix stored as `dtype`: float32 for
    sub-f32 storage (bf16/fp8), else the storage dtype."""
    d = jnp.dtype(dtype)
    return jnp.dtype(jnp.float32) if d.itemsize < 4 else d


# -- shard_map bodies: one function per op makes its body from its statics --

def _mask_body(axes, m, local, dtype):
    def body():
        start = _shard_index(axes) * local
        return ((start + jnp.arange(local)) < m).astype(dtype)
    return body


def _gram_body(axes, n, c):
    if c <= 1:
        def body(a):
            g = _ops.tsgram(a, out_dtype=jnp.float32)
            return jax.lax.psum(g, axes).astype(_out_dtype(a.dtype))
    else:
        bounds = chunk_bounds(n, c)

        def body(a):
            parts = [jax.lax.psum(
                _ops.randsketch(a, a[:, s0:s1], out_dtype=jnp.float32),
                axes) for s0, s1 in bounds]
            return jnp.concatenate(parts, axis=1).astype(_out_dtype(a.dtype))
    return body


def _matvec_body(axes):
    def body(a, v):
        return a @ v
    return body


def _rmatvec_body(axes):
    def body(a, u):
        return jax.lax.psum(a.T @ u, axes)
    return body


def _fused_grad_body(axes, nshards, kind, prm, n, c):
    """The body takes the residual as a fifth argument on the int8 wire
    (the program's in_specs, part of its key, say which)."""
    if c <= 1:
        def body(a, x, t, w, *res):
            f, g, z = _ops.fused_grad(a, x, t, w, loss=kind, param=prm)
            if res:
                g, nres = _comp.psum_int8(g, res[0][0], axes, nshards)
                return (jax.lax.psum(f, axes), g, z, nres[None])
            return jax.lax.psum(f, axes), jax.lax.psum(g, axes), z
    else:
        bounds = chunk_bounds(n, c)

        def body(a, x, t, w, *res):
            # Phase 1 — image + row residual, the exact math of
            # kernels.fusedgrad.fused_grad_jnp (the eager CPU path).
            z = jnp.dot(a, x, preferred_element_type=jnp.float32)
            f, r = _fg.row_loss_grad(z, t, w, kind, prm)
            rc = r.astype(a.dtype) if a.dtype == jnp.float32 else r
            # Phase 2 — per-segment gradient; segment k's partial psum
            # overlaps segment k+1's contraction.
            if res:
                gs, rs = [], []
                for s0, s1 in bounds:
                    part = jnp.dot(rc, a[:, s0:s1],
                                   preferred_element_type=jnp.float32)
                    gseg, rseg = _comp.psum_int8(
                        part, res[0][0, s0:s1], axes, nshards)
                    gs.append(gseg)
                    rs.append(rseg)
                return (jax.lax.psum(f, axes), jnp.concatenate(gs), z,
                        jnp.concatenate(rs)[None])
            gs = [jax.lax.psum(
                jnp.dot(rc, a[:, s0:s1],
                        preferred_element_type=jnp.float32)
                .astype(x.dtype), axes) for s0, s1 in bounds]
            return jax.lax.psum(f, axes), jnp.concatenate(gs), z
    return body


def _fused_grad_multi_body(axes, kind, prm):
    def body(a, x, t, w):
        f, g, z = _ops.fused_grad_multi(a, x, t, w, loss=kind, param=prm)
        return jax.lax.psum(f, axes), jax.lax.psum(g, axes), z
    return body


def _gemm_body(axes):
    def body(a, b):
        return _ops.gemm(a, b, out_dtype=a.dtype)
    return body


def _sketch_body(axes, n, r, seed):
    def body(a):
        key = jax.random.PRNGKey(seed)       # same key ⇒ same Ω per shard
        omega = jax.random.normal(key, (n, r), a.dtype)
        return a @ omega
    return body


def _project_body(axes):
    def body(a, q):
        partial = _ops.randsketch(a, q, out_dtype=jnp.float32)
        return jax.lax.psum(partial, axes)
    return body


def _scale_body(axes):
    def body(a, d):
        return a * d[None, :]
    return body


def _stats_body(axes):
    def body(a, mask):
        am = a * mask[:, None]
        s = jax.lax.psum(am.sum(0), axes)
        sq = jax.lax.psum((am * am).sum(0), axes)
        nnz = jax.lax.psum((am != 0).sum(0), axes)
        big = jnp.asarray(jnp.inf, a.dtype)
        sel_lo = jnp.where(mask[:, None] > 0, a, big)
        sel_hi = jnp.where(mask[:, None] > 0, a, -big)
        mn = jax.lax.pmin(sel_lo.min(0), axes)
        mx = jax.lax.pmax(sel_hi.max(0), axes)
        return s, sq, nnz, mn, mx
    return body


def _sampled_gram_body(axes, seed):
    def body(a, p, scale):
        key = jax.random.fold_in(jax.random.PRNGKey(seed),
                                 _shard_index(axes))
        keep = jax.random.uniform(key, a.shape) < p[None, :]
        b = jnp.where(keep, a, 0.0) * scale[None, :]
        return jax.lax.psum(_ops.tsgram(b, out_dtype=jnp.float32), axes)
    return body


def _frobenius_body(axes):
    def body(a):
        return jax.lax.psum((a * a).sum(), axes)
    return body


def _record_collective(plan, span, **attrs) -> None:
    """Plan-vs-actual for one distributed op: the span's synced duration
    next to the comm-priced plan (launch/telemetry collects the records;
    their comm terms feed MachineModel.calibrate's link column).  An op
    traced into a loop body has a named scope for a span, with no
    duration, and writes no record: its clock would time the tracer."""
    from repro.launch import telemetry as _tel
    rec = _tel.current()
    if rec.enabled and span.dur_s > 0:
        rec.record_plan_actual(plan, span.dur_s, **attrs)


# A pytree over its device array, so jitted code takes the matrix as an
# argument instead of baking it into the executable as a constant.
@functools.partial(jax.tree_util.register_dataclass, data_fields=["rows"],
                   meta_fields=["n_rows", "mesh", "row_axes"])
@dataclass(frozen=True)
class RowMatrix(T.DistMatrix):
    rows: Array                      # (m_padded, n), sharded P(row_axes, None)
    n_rows: int                      # true row count (pre-padding)
    mesh: Mesh = field(repr=False)
    row_axes: tuple[str, ...] = T.ROW_AXES

    # -- construction ------------------------------------------------------
    @staticmethod
    def create(rows: Array, mesh: Mesh | None = None,
               row_axes: Sequence[str] | None = None,
               store_dtype=None) -> "RowMatrix":
        """`store_dtype` (bf16/fp8 where the platform supports it) keeps
        the sharded residency at reduced width; every compute path upcasts
        tiles on-chip and accumulates float32, so results come back at the
        logical `out_dtype` (f32 for sub-f32 storage)."""
        mesh = mesh or T.single_device_mesh()
        row_axes = tuple(row_axes) if row_axes else T.row_axes_for(mesh)
        nshards = T.axes_size(mesh, row_axes)
        rows = jnp.asarray(rows)
        if store_dtype is not None:
            rows = rows.astype(store_dtype)
        padded, m = T.pad_rows(rows, nshards)
        padded = T.put(padded, NamedSharding(mesh, P(row_axes, None)))
        return RowMatrix(rows=padded, n_rows=m, mesh=mesh, row_axes=row_axes)

    @property
    def shape(self) -> tuple[int, int]:
        return (self.n_rows, self.rows.shape[1])

    @property
    def out_dtype(self):
        """Logical result dtype: float32 when storage is sub-f32 (bf16/
        fp8) — low-precision residency never narrows the math the caller
        sees."""
        return _out_dtype(self.rows.dtype)

    def astype_store(self, dtype) -> "RowMatrix":
        """Recast the sharded storage (the planner's bf16 pick lands
        here).  Row padding and sharding are preserved; identity when the
        dtype already matches."""
        dtype = jnp.dtype(dtype)
        if dtype == self.rows.dtype:
            return self
        return replace(self, rows=self.rows.astype(dtype))

    @property
    def _spec(self) -> P:
        return P(self.row_axes, None)

    def _program(self, build, in_specs, out_specs, *static):
        """The cached jitted shard_map program of `build(row_axes,
        *static)` on this mesh (types.program).  The key is `build`, the
        mesh, the row axes, the specs and the statics: everything the
        body closes over."""
        mesh, axes = self.mesh, self.row_axes
        return T.program(
            (build, mesh, axes, in_specs, out_specs) + static,
            lambda: T.shard_map(build(axes, *static), mesh, in_specs,
                                out_specs))

    def _local_rows(self) -> int:
        return self.rows.shape[0] // T.axes_size(self.mesh, self.row_axes)

    def _collective_plan(self, op: str, dims):
        """Comm-priced plan for a distributed op on this mesh: per-shard
        dims + the row-axis device counts as the collective topology."""
        from repro.launch import mesh as _mesh
        from repro.launch import planner as _planner
        return _planner.plan(
            op, dims, self.rows.dtype.name,
            context={"axes": _mesh.axis_sizes(self.mesh, self.row_axes)})

    def _resolve_chunks(self, chunks, plan) -> int:
        """The overlap chunk count: planner-chosen on "auto" (1 = eager),
        else the caller's explicit override (tests force both paths)."""
        if chunks == "auto":
            return int(plan.blocks.get("chunks", 1))
        return max(int(chunks), 1)

    def _row_mask(self) -> Array:
        """Row-sharded {0,1} mask of true (non-padding) rows."""
        return self._program(_mask_body, (), P(self.row_axes), self.n_rows,
                             self._local_rows(), self.out_dtype)()

    # -- cluster matrix ops --------------------------------------------------
    def gram(self, *, chunks: int | str = "auto") -> Array:
        """AᵀA, replicated — the paper's one-all-to-one DIMSUM reduction.

        Per-shard partial Gram then an all-reduce over the row axes.  The
        shard reduction is the Pallas tsgram kernel (autotuned block sizes)
        on TPU; on CPU `ops.tsgram` dispatches to the jnp reference, which
        stays the ground truth.  Padding rows are zero so they do not
        contribute.

        `chunks` > 1 runs the comm-overlapped schedule the planner prices
        (plan("gram") with this mesh's axis sizes): C column-segment
        cross-grams Aᵀ·A[:, seg], each segment's partial psum pipelined
        behind the next segment's compute.  Every segment is the same
        columns of the same product, so the result is bit-identical to the
        eager body; "auto" defers to the planner (1 — eager — unless the
        modeled collective dominates the extra A reads).
        """
        from repro.launch import telemetry as _tel
        n = self.rows.shape[1]
        plan = self._collective_plan("gram", {"m": self._local_rows(),
                                              "n": n})
        c = self._resolve_chunks(chunks, plan)
        prog = self._program(_gram_body, (self._spec,), P(), n, c)
        with _tel.current().span("collective.gram", op="gram", n=n,
                                 chunks=c) as sp:
            out = prog(self.rows)
            sp.sync_on(out)
        _record_collective(plan, sp, collective="psum", chunks=c)
        return out

    def matvec(self, v: Array) -> Array:
        """A v with v replicated (driver) → row-sharded result (cluster)."""
        return self._program(_matvec_body, (self._spec, P()),
                             P(self.row_axes))(self.rows, v)

    def rmatvec(self, u: Array) -> Array:
        """Aᵀ u with u row-sharded → replicated n-vector (back to driver)."""
        from repro.launch import telemetry as _tel
        plan = self._collective_plan("matvec", {"m": self._local_rows(),
                                                "n": self.rows.shape[1]})
        prog = self._program(_rmatvec_body, (self._spec, P(self.row_axes)),
                             P())
        with _tel.current().span("collective.rmatvec", op="matvec",
                                 n=self.rows.shape[1]) as sp:
            out = prog(self.rows, u)
            sp.sync_on(out)
        _record_collective(plan, sp, collective="psum")
        return out

    def init_psum_residual(self) -> Array:
        """Zeroed per-shard f32 error-feedback residual for the compressed
        ("psum8") fused_grad reduction: one (n,) row per row shard, laid
        out P(row_axes, None) so each shard owns exactly its own row."""
        nshards = T.axes_size(self.mesh, self.row_axes)
        z = jnp.zeros((nshards, self.rows.shape[1]), jnp.float32)
        return T.put(z, NamedSharding(self.mesh, P(self.row_axes, None)))

    def fused_grad(self, x: Array, smooth, *, chunks: int | str = "auto",
                   residual: Array | None = None):
        """(f(Ax), Aᵀ∇f(Ax), Ax) in ONE streaming pass over the shard — the
        paper's one-pass treeAggregate gradient, fused on-chip
        (kernels/fusedgrad).  `smooth` is a row-separable smooth (or its
        RowSeparable form); its target/weights are data-space vectors and
        get padded to the sharded row count, with padding rows weighted 0.
        Returns (replicated f32 scalar, replicated (n,) gradient,
        row-sharded image).

        `chunks` > 1 runs the planner's overlapped schedule (plan("grad")
        with this mesh's axis sizes, blocks["chunks"]): one full pass
        computes the image and row residual with the exact
        ``fused_grad_jnp`` math, then the gradient is assembled per column
        segment — r·A[:, seg] — with each segment's partial psum pipelined
        behind the next segment's compute.  Segmented psums of the same
        products make it bit-identical to the eager body; the price (one
        extra read of A) is the planner's break-even, so "auto" stays
        eager until the modeled collective dominates.

        `residual` (from init_psum_residual) switches the gradient psum to
        the compressed int8 wire (train.compression.psum_int8): shards
        quantize their partials against a shared pmax'd scale, the
        all-reduce ships int8, and the quantization error is carried in
        the returned residual for re-injection next call.  Returns a
        4-tuple (f, g, z, new_residual) in that mode."""
        from repro.launch import telemetry as _tel
        nshards = T.axes_size(self.mesh, self.row_axes)
        kind, t, w, prm = T.row_separable_inputs(smooth, self.rows.shape[0],
                                                 self._row_mask)
        x = jnp.asarray(x)
        n = self.rows.shape[1]
        plan = self._collective_plan("grad", {"m": self._local_rows(),
                                              "n": n})
        c = self._resolve_chunks(chunks, plan)
        rows = P(self.row_axes)
        statics = (nshards, kind, prm, n, c)
        wire = "int8" if residual is not None else "f32"
        with _tel.current().span("collective.fused_grad", op="grad", n=n,
                                 chunks=c, wire=wire) as sp:
            if residual is None:
                out = self._program(
                    _fused_grad_body, (self._spec, P(), rows, rows),
                    (P(), P(), rows), *statics)(self.rows, x, t, w)
            else:
                out = self._program(
                    _fused_grad_body, (self._spec, P(), rows, rows,
                                       self._spec),
                    (P(), P(), rows, self._spec),
                    *statics)(self.rows, x, t, w, residual)
            sp.sync_on(out[1])
        _record_collective(plan, sp, collective="psum", chunks=c, wire=wire)
        return out

    def fused_grad_multi(self, x: Array, smooths
                         ) -> tuple[Array, Array, Array]:
        """Request-batched fused gradients: (f, g, z) for a GROUP of k
        right-hand sides in ONE streaming pass over the shard — each HBM
        read of an A block is amortized across every request.  `x` is
        (k × n); `smooths` is a sequence of k row-separable smooths sharing
        one loss kind/param (or a single smooth with stacked 2-D targets).
        Returns (replicated (k,) values, replicated (k × n) gradients,
        image sharded (k × m) over the row axes)."""
        kind, t, w, prm = T.row_separable_batch_inputs(
            smooths, self.rows.shape[0], self._row_mask)
        x = jnp.atleast_2d(jnp.asarray(x))
        rows = P(None, self.row_axes)
        return self._program(
            _fused_grad_multi_body, (self._spec, P(), rows, rows),
            (P(), P(), rows), kind, prm)(self.rows, x, t, w)

    def multiply_local(self, B: Array) -> "RowMatrix":
        """A @ B for a small replicated B — the `U = A (VΣ⁻¹)` pattern:
        broadcast the small factor, then embarrassingly parallel (autotuned
        Pallas GEMM per shard on TPU, jnp reference on CPU)."""
        out = self._program(_gemm_body, (self._spec, P()),
                            self._spec)(self.rows, B)
        return replace(self, rows=out)

    def sketch(self, r: int, *, seed: int = 0) -> "RowMatrix":
        """Y = A Ω for an (n × r) Gaussian test matrix Ω (randomized
        range finder).  Ω is generated *inside* each shard from the shared
        seed — every chip derives the identical Ω locally, so the sketch
        matrix is never materialized on (or broadcast from) the driver;
        the only HBM traffic is one pass over A."""
        out = self._program(_sketch_body, (self._spec,), self._spec,
                            self.rows.shape[1], r, seed)(self.rows)
        return replace(self, rows=out)

    def project(self, Q: "RowMatrix", *, out_dtype=jnp.float32) -> Array:
        """B = AᵀQ for a row-conforming Q, replicated — the randomized-SVD
        projection: per-shard streaming cross-Gram (Pallas randsketch
        kernel) then a tree all-reduce over the row axes.  Padding rows are
        zero in both operands so they do not contribute."""
        out = self._program(_project_body, (self._spec, self._spec),
                            P())(self.rows, Q.rows)
        return out.astype(out_dtype)

    def scale_columns(self, d: Array) -> "RowMatrix":
        """A · diag(d) with replicated d (DIMSUM column scaling)."""
        out = self._program(_scale_body, (self._spec, P()),
                            self._spec)(self.rows, d)
        return replace(self, rows=out)

    def column_stats(self) -> dict[str, Array]:
        """Replicated per-column statistics (MLlib colStats)."""
        m = self.n_rows
        s, sq, nnz, mn, mx = self._program(
            _stats_body, (self._spec, P(self.row_axes)),
            (P(), P(), P(), P(), P()))(self.rows, self._row_mask())
        mean = s / m
        var = jnp.maximum(sq / m - mean * mean, 0.0) * (m / max(m - 1, 1))
        return {"mean": mean, "variance": var, "num_nonzeros": nnz,
                "min": mn, "max": mx, "norm_l2": jnp.sqrt(sq)}

    def column_similarities(self, threshold: float = 0.0, *,
                            gamma: float | None = None,
                            seed: int = 0, return_info: bool = False):
        """DIMSUM cosine similarity of columns (paper refs [10, 11]).

        threshold=0 (the default) computes cos(i,j) = (AᵀA)ij/(‖cᵢ‖‖cⱼ‖)
        exactly via the scaled Gram — on ICI the one-all-reduce reduction is
        bandwidth-optimal.  threshold>0 runs *sampled* DIMSUM: entries of
        column i survive with probability pᵢ = min(1, √γ/‖cᵢ‖), so a pair
        (i, j) is sampled with the paper's oversampling probability
        min(1, γ/‖cᵢ‖‖cⱼ‖); kept entries are rescaled by 1/pᵢ, making the
        estimator unbiased off the diagonal (the diagonal is written exactly
        — its value is known).  γ defaults to 10·log(n)/threshold, which
        preserves all similarities ≥ threshold w.h.p.  Sampling happens
        per shard from a fold_in'd key, so no randomness crosses the
        interconnect.

        return_info=True returns (sim, info) where info carries the sampling
        diagnostics: γ, the per-column keep probabilities p, and the
        per-pair variance of the estimator,
            Var[ŝᵢⱼ] = Σ_k (a_ki a_kj)² / (‖cᵢ‖²‖cⱼ‖²) · (1/(pᵢpⱼ) − 1),
        computed exactly via one extra Gram over the squared scaled matrix
        — it shrinks to 0 as γ grows (all pᵢ → 1).
        """
        norms = self.column_stats()["norm_l2"]
        inv = jnp.where(norms > 0, 1.0 / jnp.maximum(norms, 1e-30), 0.0)
        n = self.shape[1]
        if threshold <= 0.0:
            sim = self.scale_columns(inv).gram()
            if not return_info:
                return sim
            return sim, {"gamma": None, "p": jnp.ones((n,), jnp.float32),
                         "variance": jnp.zeros((n, n), jnp.float32)}
        from .sparserow import dimsum_gamma
        g = gamma if gamma is not None else dimsum_gamma(n, threshold)
        p = jnp.minimum(1.0, float(np.sqrt(g)) * inv)
        scale = inv * jnp.where(p > 0, 1.0 / p, 0.0)
        sim = self._program(_sampled_gram_body, (self._spec, P(), P()),
                            P(), seed)(self.rows, p, scale)
        sim = sim.astype(self.out_dtype)
        diag = (norms > 0).astype(sim.dtype)
        sim = sim.at[jnp.arange(n), jnp.arange(n)].set(diag)
        if not return_info:
            return sim
        scaled = self.scale_columns(inv)
        sq = replace(scaled, rows=scaled.rows * scaled.rows)
        s2 = sq.gram().astype(jnp.float32)       # Σ_k (ãki ãkj)², ã scaled
        var = T.dimsum_variance(s2, p)
        return sim, {"gamma": g, "p": p, "variance": var}

    def remesh(self, mesh: Mesh, row_axes: Sequence[str] | None = None
               ) -> "RowMatrix":
        """Re-shard the SAME logical matrix onto a different mesh (elastic
        re-mesh, train/elastic): strip the old mesh's padding rows, re-pad
        for the new shard count and device_put with the new sharding.  Used
        mid-solve after a straggler/device loss — the solver state (driver
        vectors) is mesh-independent, so only the matrix moves."""
        return RowMatrix.create(self.rows[: self.n_rows], mesh, row_axes)

    def to_sparse_row_matrix(self, bs: int | str = "auto"):
        """Block-compress into the BSR-backed sparse type (driver-scale,
        like the other format conversions)."""
        from .sparserow import SparseRowMatrix
        return SparseRowMatrix.from_dense(self.to_local(), bs=bs,
                                          mesh=self.mesh,
                                          row_axes=self.row_axes)

    def frobenius_norm(self) -> Array:
        return jnp.sqrt(self._program(_frobenius_body, (self._spec,),
                                      P())(self.rows))

    # -- materialization ----------------------------------------------------
    def to_local(self) -> Array:
        return jax.device_get(self.rows)[: self.n_rows]

    # -- linalg entry points (implemented in core.linalg) -------------------
    def compute_svd(self, k: int, **kw):
        from repro.core.linalg import svd as _svd
        return _svd.compute_svd(self, k, **kw)

    def compute_pca(self, k: int, **kw):
        from repro.core.linalg import svd as _svd
        return _svd.compute_pca(self, k, **kw)

    def tall_skinny_qr(self):
        from repro.core.linalg import tsqr as _tsqr
        return _tsqr.tsqr(self)


@dataclass(frozen=True)
class IndexedRowMatrix(T.DistMatrix):
    """RowMatrix plus meaningful long-typed row indices (paper §2.1)."""
    indices: Array                   # (m_padded,), int32/64, row-sharded
    inner: RowMatrix

    @staticmethod
    def create(indices: Array, rows: Array, mesh: Mesh | None = None,
               row_axes: Sequence[str] | None = None) -> "IndexedRowMatrix":
        rm = RowMatrix.create(rows, mesh, row_axes)
        nshards = T.axes_size(rm.mesh, rm.row_axes)
        idx, _ = T.pad_rows(jnp.asarray(indices), nshards)
        idx = T.put(idx, NamedSharding(rm.mesh, P(rm.row_axes)))
        return IndexedRowMatrix(indices=idx, inner=rm)

    @property
    def shape(self) -> tuple[int, int]:
        return self.inner.shape

    def to_row_matrix(self) -> RowMatrix:
        return self.inner

    def matvec(self, v: Array) -> Array:
        return self.inner.matvec(v)

    def rmatvec(self, u: Array) -> Array:
        return self.inner.rmatvec(u)

    def to_local(self) -> Array:
        idx = np.asarray(jax.device_get(self.indices))[: self.inner.n_rows]
        dense = np.asarray(self.inner.to_local())
        out = np.zeros((int(idx.max()) + 1 if idx.size else 0,
                        dense.shape[1]), dense.dtype)
        out[idx] = dense
        return jnp.asarray(out)
