"""Mesh plumbing and the DistMatrix protocol.

The paper lays matrices out across a cluster as RDDs; here the cluster is a
TPU mesh and the layout is a NamedSharding.  Every distributed matrix type
carries (data, mesh, row_axes, col_axis) and exposes the same small protocol
(shape / matvec / rmatvec / to_local) so the linalg layer is representation
agnostic, exactly like MLlib's DistributedMatrix interface.

"Driver-local" quantities (the paper's vectors) are replicated arrays:
PartitionSpec() over the same mesh.  "Cluster" quantities are sharded.

`program` keeps each distmat shard_map body as one jitted program per
static signature, so a repeated call runs a compiled program instead of
tracing and lowering its body again.
"""
from __future__ import annotations

import collections
import functools
import threading
from dataclasses import dataclass
from typing import Callable, Hashable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


Array = jax.Array

# Default logical axis names.  Row-sharding uses the batch-like axes; column /
# block sharding uses the model axis.  The multi-pod mesh adds a leading
# "pod" axis which is treated as an extra row axis.
ROW_AXES = ("data",)
COL_AXIS = "model"


@functools.cache
def single_device_mesh() -> Mesh:
    """A (1, 1) mesh so the same shard_map code path runs on one CPU."""
    return make_mesh((1, 1), ("data", "model"))


def make_mesh(shape: Sequence[int], names: Sequence[str],
              devices: Sequence | None = None) -> Mesh:
    """A mesh with Auto axis types over `devices` (the first
    prod(shape) devices by default)."""
    names = tuple(names)
    return jax.make_mesh(tuple(shape), names,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(names),
                         devices=devices)


def shard_map(f: Callable, mesh: Mesh, in_specs, out_specs) -> Callable:
    """jax.shard_map for the distmat bodies.  The Pallas kernels they call
    declare no varying mesh axes on their outputs, so the varying-axes
    check is off (the out_specs state the layout).

    Called eagerly, a shard_map traces and lowers its body again on every
    call (a fresh closure is never recognised as one already run), and a
    body with no array inputs runs op by op.  RowMatrix therefore runs its
    bodies as `program`s: jitted once per static signature and kept."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# Jitted programs by static key, least recently used first.
PROGRAM_CACHE_SIZE = 256
_programs: "collections.OrderedDict[Hashable, Callable]" = \
    collections.OrderedDict()
_programs_lock = threading.Lock()


def program(key: Hashable, build: Callable[[], Callable]) -> Callable:
    """The jitted program kept under `key`, made as ``jax.jit(build())``
    on a miss.  `key` holds everything `build`'s function closes over (the
    op, the mesh, the row axes, the static values); array shapes, dtypes
    and the matmul-precision context are jit's own cache key.  Device
    arrays are always arguments of the program, never part of `key`, so a
    cached program neither bakes a matrix in nor keeps one alive.  Later
    calls take jit's C++ fast path, with no tracing or lowering.

    Counts ``distmat.program`` (result=hit|miss) on the current telemetry
    recorder; a miss's first call, which traces and compiles, runs inside
    the span ``distmat.build``.  Holds the last PROGRAM_CACHE_SIZE keys."""
    from repro.launch import telemetry as _tel
    rec = _tel.current()
    with _programs_lock:
        fn = _programs.get(key)
        if fn is not None:
            _programs.move_to_end(key)
    if fn is not None:
        rec.counter("distmat.program", result="hit").inc()
        return fn
    rec.counter("distmat.program", result="miss").inc()
    fn = jax.jit(build())
    with _programs_lock:
        fn = _programs.setdefault(key, fn)
        _programs.move_to_end(key)
        while len(_programs) > PROGRAM_CACHE_SIZE:
            _programs.popitem(last=False)

    def first_call(*args):
        with rec.span("distmat.build"):
            return fn(*args)
    return first_call


def row_axes_for(mesh: Mesh) -> tuple[str, ...]:
    """All mesh axes that shard rows: ('pod','data') on multi-pod meshes."""
    return tuple(n for n in mesh.axis_names if n != COL_AXIS)


def axes_size(mesh: Mesh, axes: Sequence[str]) -> int:
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def row_sharding(mesh: Mesh, row_axes: Sequence[str] | None = None) -> NamedSharding:
    row_axes = tuple(row_axes) if row_axes is not None else row_axes_for(mesh)
    return NamedSharding(mesh, P(row_axes, None))


def block_sharding(mesh: Mesh, row_axes: Sequence[str] | None = None,
                   col_axis: str = COL_AXIS) -> NamedSharding:
    row_axes = tuple(row_axes) if row_axes is not None else row_axes_for(mesh)
    return NamedSharding(mesh, P(row_axes, col_axis))


def put(x: Array, sharding: NamedSharding) -> Array:
    """Place `x` with `sharding` (device_put works inside or outside jit).
    A host array goes shard by shard to its devices, never through one
    device whole."""
    if not isinstance(x, (jax.Array, np.ndarray)):
        x = jnp.asarray(x)
    return jax.device_put(x, sharding)


def pad_rows(x: Array, multiple: int) -> tuple[Array, int]:
    """Pad axis 0 of `x` to a multiple; returns (padded, original_rows)."""
    m = x.shape[0]
    rem = (-m) % multiple
    if rem:
        pad_width = [(0, rem)] + [(0, 0)] * (x.ndim - 1)
        x = jnp.pad(x, pad_width)
    return x, m


def row_separable_inputs(smooth, m_pad: int, row_mask_fn):
    """Resolve a smooth (or its RowSeparable form) into fused-gradient
    kernel inputs: (kind, target, weights, param) with the data-space
    vectors padded to the sharded row count `m_pad`.  Default weights come
    from `row_mask_fn()` so padding rows contribute nothing; explicit
    weights are zero-padded, same effect.  `param` is the loss's static
    scalar (huber δ; 1.0 elsewhere).  Shared by RowMatrix.fused_grad and
    SparseRowMatrix.fused_grad."""
    sep = smooth if hasattr(smooth, "kind") else (
        smooth.as_row_separable()
        if hasattr(smooth, "as_row_separable") else None)
    if sep is None:
        raise ValueError("fused_grad needs a row-separable smooth")
    t = jnp.asarray(sep.target)
    t = jnp.pad(t, (0, m_pad - t.shape[0])) if t.shape[0] < m_pad else t
    if sep.weights is None:
        w = row_mask_fn()
    else:
        w = jnp.asarray(sep.weights)
        w = jnp.pad(w, (0, m_pad - w.shape[0])) if w.shape[0] < m_pad else w
    return sep.kind, t, w, float(getattr(sep, "param", 1.0))


def row_separable_batch_inputs(smooths, m_pad: int, row_mask_fn):
    """Resolve a *group* of row-separable smooths into multi-RHS fused
    kernel inputs: (kind, targets (k × m_pad), weights (k × m_pad), param).

    `smooths` is either a sequence of k smooths (all must share the same
    loss kind and static param — that is what makes them one servable
    group) or a single smooth whose target/weights are already stacked
    2-D (k × m) arrays.  Shared by RowMatrix.fused_grad_multi and
    SparseRowMatrix.fused_grad_multi."""
    def resolve(s):
        sep = s if hasattr(s, "kind") else (
            s.as_row_separable() if hasattr(s, "as_row_separable") else None)
        if sep is None:
            raise ValueError("fused_grad_multi needs row-separable smooths")
        return sep

    if not isinstance(smooths, (list, tuple)):
        sep = resolve(smooths)
        t = jnp.atleast_2d(jnp.asarray(sep.target))
        seps = [sep]
        ts = [t[i] for i in range(t.shape[0])]
        ws = ([None] * t.shape[0] if sep.weights is None else
              [jnp.atleast_2d(jnp.asarray(sep.weights))[i]
               for i in range(t.shape[0])])
    else:
        seps = [resolve(s) for s in smooths]
        ts = [jnp.asarray(s.target) for s in seps]
        ws = [None if s.weights is None else jnp.asarray(s.weights)
              for s in seps]

    kinds = {s.kind for s in seps}
    params = {float(getattr(s, "param", 1.0)) for s in seps}
    if len(kinds) != 1 or len(params) != 1:
        raise ValueError(
            f"a fused group must share one loss kind/param, got "
            f"{sorted(kinds)} / {sorted(params)}")

    mask = row_mask_fn()

    def pad1(v):
        return jnp.pad(v, (0, m_pad - v.shape[0])) if v.shape[0] < m_pad else v

    t2 = jnp.stack([pad1(t) for t in ts])
    w2 = jnp.stack([mask if w is None else pad1(w) for w in ws])
    return kinds.pop(), t2, w2, params.pop()


def dimsum_variance(s2: Array, p: Array) -> Array:
    """Per-pair sampled-DIMSUM estimator variance,
        Var[ŝᵢⱼ] = Σ_k (ã_ki ã_kj)² · (1/(pᵢpⱼ) − 1),
    from the Gram `s2` of the squared column-scaled matrix and the
    per-column keep probabilities `p`.  The diagonal is written exactly by
    the estimator, so its variance is 0.  Shared by both distmat types."""
    n = p.shape[0]
    pp = p[:, None] * p[None, :]
    var = s2 * jnp.where(pp > 0, 1.0 / jnp.maximum(pp, 1e-30) - 1.0, 0.0)
    return var.at[jnp.arange(n), jnp.arange(n)].set(0.0)


@dataclass(frozen=True)
class DistMatrix:
    """Base for distributed matrices; subclasses set `data` layout."""

    @property
    def shape(self) -> tuple[int, int]:  # pragma: no cover - abstract
        raise NotImplementedError

    def matvec(self, v: Array) -> Array:  # pragma: no cover - abstract
        raise NotImplementedError

    def rmatvec(self, u: Array) -> Array:  # pragma: no cover - abstract
        raise NotImplementedError

    def to_local(self) -> Array:  # pragma: no cover - abstract
        raise NotImplementedError

    def normal_op(self) -> Callable[[Array], Array]:
        """v ↦ Aᵀ(A v): the only operator ARPACK-style SVD ever needs."""
        return lambda v: self.rmatvec(self.matvec(v))
