"""Block-sparse (BSR/ELL) × dense matmul — paper §4.2 adapted to the MXU.

MLlib stores local sparse matrices in CCS and hand-rolls SpMV/SpMM because
JVM BLAS has no sparse story.  A TPU has the *opposite* problem: scalar
gathers are slow but dense (8·k × 128·l) tiles are free, so the TPU-native
sparse format is block-CSR padded to ELL: every block-row holds a fixed
number of (bs × bs) blocks (zero-padded — a zero block contributes nothing,
which removes all control flow from the kernel).

Every kernel stages one block-row's ell stored blocks per grid step.  The
row's column list rides along as a small SMEM block, so the per-block
column offsets are scalar reads that scale with ell, not with the matrix:

  * ``bsr_matmul``  — y = A @ X   (SpMM: the X block of each slot is
    gathered by the index_map from scalar-prefetched column indices; the
    prefetch runs in chunks of block-rows sized to fit SMEM);
  * ``bsr_matvec``  — y = A @ x   (SpMV: x stays resident in VMEM as an
    (nbc × bs) array, each slot reads its row at the block-column offset,
    and the block product is a (1 × bs)·(bs × bs) row-vector matmul on the
    MXU);
  * ``bsr_rmatmul`` — y = Aᵀ @ X  (transpose-multiply: the scatter-add over
    block columns is fused into the kernel — the full (nbc × bs × nx)
    accumulator stays resident in VMEM and each per-block partial Aᵢⱼᵀ Xᵢ
    is added at offset cols[i, slot] as soon as it is computed.  The grid
    is sequential ("arbitrary"), so the read-modify-write is race-free and
    no HBM partials buffer is needed.  When the resident accumulator would
    overflow the VMEM budget (n·nx too large) the kernel falls back to
    emitting (nbr, ell, bs, nx) partials + one XLA segment_sum — the old
    scheme, kept for the wide regime).

Per-block-row vectors (z, t, w, the column lists) travel as (nbr, 1, ·)
arrays so each grid step's block spans the array's two minor dims — the
layout rule Mosaic enforces on blocks narrower than (8 × 128).

The ``*_jnp`` variants are structure-exploiting gather/einsum forms of the
same contractions (flops ∝ stored blocks, not m·n) — the off-TPU dispatch
target in kernels/ops.py.  The densifying oracles stay in kernels/ref.py.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from .autotune import HIGHEST, compiler_params

Array = jax.Array


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("data", "cols", "scales"),
                   meta_fields=("shape",))
@dataclass(frozen=True)
class BlockELL:
    """Fixed-width block-sparse rows: data[i, j] is the j-th stored block of
    block-row i, at block-column col[i, j] (padding blocks are zero with
    col = 0).

    Quantized mode: with ``scales`` set (per stored block, f32), the stored
    block is ``data[i, j].astype(f32) * scales[i, j]`` — int8 data at 1/4
    the HBM traffic, dequantized on-chip by the kernels.  ``scales=None``
    is the exact mode (f32 or bf16 data)."""
    data: Array      # (n_block_rows, ell, bs, bs)
    cols: Array      # (n_block_rows, ell) int32
    shape: tuple[int, int]
    scales: Array | None = None    # (n_block_rows, ell) f32, int8 mode only

    @property
    def bs(self) -> int:
        return self.data.shape[-1]

    @property
    def ell(self) -> int:
        return self.data.shape[1]

    @staticmethod
    def from_dense(a: np.ndarray, bs: int, quantize: str = "none",
                   tol: float = 1e-3) -> "BlockELL":
        """Pack a dense (m × n) array into BlockELL.

        ``quantize``: "none" keeps a.dtype; "int8" stores int8 blocks with
        per-block f32 scales; "auto" asks the planner — the shard is
        quantized iff plan("sparse_matmul", ..., context={"tol": tol})
        picks the int8 precision (i.e. the tolerance clears the int8 guard
        AND the modeled byte savings clear the floor)."""
        m, n = a.shape
        assert m % bs == 0 and n % bs == 0, (a.shape, bs)
        nbr, nbc = m // bs, n // bs
        blocks = np.asarray(a).reshape(nbr, bs, nbc, bs).transpose(0, 2, 1, 3)
        nz = np.abs(blocks).sum(axis=(2, 3)) > 0          # (nbr, nbc)
        ell = max(int(nz.sum(1).max()), 1)
        # Stable argsort on ~nz packs each block-row's nonzero columns into
        # the leading slots in ascending column order (no Python loop).
        order = np.argsort(~nz, axis=1, kind="stable")[:, :ell]
        valid = np.take_along_axis(nz, order, axis=1)     # (nbr, ell)
        cols = np.where(valid, order, 0).astype(np.int32)
        data = blocks[np.arange(nbr)[:, None], order] * valid[..., None, None]
        out = BlockELL(jnp.asarray(data.astype(a.dtype)), jnp.asarray(cols),
                       (m, n))
        if quantize == "auto":
            from repro.launch import planner
            p = planner.plan("sparse_matmul",
                             {"m": m, "n": n, "nx": 1, "ell": ell, "bs": bs},
                             context={"tol": float(tol)})
            quantize = "int8" if p.precision == "int8" else "none"
        if quantize == "int8":
            return out.quantize_int8()
        if quantize != "none":
            raise ValueError(f"quantize must be 'none'|'int8'|'auto', "
                             f"got {quantize!r}")
        return out

    def quantize_int8(self) -> "BlockELL":
        """Int8 + per-block-scale form of this matrix: scale = absmax/127
        per stored block, data = round(block/scale).  Zero (padding) blocks
        get scale 1 so they stay exactly zero."""
        if self.scales is not None:
            return self
        d = self.data.astype(jnp.float32)
        absmax = jnp.max(jnp.abs(d), axis=(2, 3))          # (nbr, ell)
        scales = jnp.where(absmax > 0, absmax / 127.0, 1.0)
        q = jnp.round(d / scales[..., None, None]).astype(jnp.int8)
        return BlockELL(q, self.cols, self.shape,
                        scales.astype(jnp.float32))

    def dequantize(self) -> "BlockELL":
        """Exact-mode (f32 data, no scales) copy of this matrix."""
        if self.scales is None:
            return self
        return BlockELL(effective_data(self), self.cols, self.shape)

    def to_dense(self) -> Array:
        m, n = self.shape
        data = effective_data(self)
        bs, nbr, ell = self.bs, data.shape[0], self.ell
        out = jnp.zeros((nbr, n // bs, bs, bs), data.dtype)
        rows = jnp.repeat(jnp.arange(nbr), ell)
        out = out.at[rows, self.cols.reshape(-1)].add(
            data.reshape(-1, bs, bs))
        return out.transpose(0, 2, 1, 3).reshape(m, n)

    def density(self) -> float:
        nbc = self.shape[1] // self.bs
        return self.ell / nbc


def effective_data(a: BlockELL) -> Array:
    """The stored blocks as the values they represent: dequantized (int8 ×
    per-block scale) or as stored.  The identity for exact-mode f32 data —
    the jnp paths below route through this, so the unquantized fast path
    is bit-for-bit what it always was."""
    if a.scales is not None:
        return a.data.astype(jnp.float32) * a.scales[..., None, None]
    return a.data


# SMEM words the scalar-prefetched column indices of one bsr_matmul call
# may take (SMEM holds 1 MiB; the rest is left to the compiler).
PREFETCH_WORDS = 64 * 1024


def row_vectors(v: Array, nbr: int) -> Array:
    """A per-block-row vector or index table as (nbr, 1, ·), so the one-row
    block of each grid step spans the array's two minor dims."""
    return v.reshape(nbr, 1, -1)


def smem_rows(width: int) -> pl.BlockSpec:
    """This grid step's row of a (nbr, 1, width) table, in SMEM."""
    return pl.BlockSpec((1, 1, width), lambda i, *_: (i, 0, 0),
                        memory_space=pltpu.SMEM)


def load_block(a_ref, s_ref, j) -> Array:
    """Slot j of the staged (1, ell, bs, bs) block-row as f32: upcast
    sub-f32 storage on-chip and apply the per-block dequant scale when the
    matrix is quantized.  The identity for exact-mode f32 data."""
    a = a_ref[0, j]
    if a.dtype != jnp.float32:
        a = a.astype(jnp.float32)
    if s_ref is not None:
        a = a * s_ref[0, 0, j]
    return a


def _row_operands(a: BlockELL, nbr: int):
    """(in_specs, operands) of the staged block-row: column list, blocks,
    and the scales when quantized."""
    specs = [smem_rows(a.ell),
             pl.BlockSpec((1, a.ell, a.bs, a.bs), lambda i, *_: (i, 0, 0, 0))]
    ops = [row_vectors(a.cols, nbr), a.data]
    if a.scales is not None:
        specs.append(smem_rows(a.ell))
        ops.append(row_vectors(a.scales, nbr))
    return specs, ops


def _bsr_kernel(cols_ref, base_ref, a_ref, *args, quantized: bool):
    del cols_ref, base_ref   # consumed by the index_map gathers
    if quantized:
        s_ref, x_ref, _, o_ref, acc_ref = args
    else:
        (x_ref, _, o_ref, acc_ref), s_ref = args, None
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    blk = a_ref[0, 0]
    if blk.dtype != jnp.float32:
        blk = blk.astype(jnp.float32)
    if s_ref is not None:
        blk = blk * s_ref[0, 0, j]
    acc_ref[...] += jnp.dot(blk, x_ref[...],
                            preferred_element_type=jnp.float32,
                            precision=HIGHEST)

    @pl.when(j == pl.num_programs(1) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bsr_matmul(a: BlockELL, x: Array, *, interpret: bool = False) -> Array:
    """y = A @ X for block-ELL A (m × n) and dense X (n × nx).

    The X block of every slot is gathered by the index_map, so the column
    indices are scalar-prefetched into SMEM.  To keep that prefetch within
    PREFETCH_WORDS whatever the matrix size, the block-rows run in chunks of
    `rows` per call, each call writing its rows of one aliased output; a
    ragged last chunk repeats the final block-row, which rewrites the same
    values."""
    m, n = a.shape
    assert x.shape[0] == n, (a.shape, x.shape)
    nx = x.shape[1]
    bs, ell = a.bs, a.ell
    nbr = m // bs
    quantized = a.scales is not None
    rows = max(1, min(nbr, PREFETCH_WORDS // ell))
    chunks = -(-nbr // rows)
    last = nbr - 1

    def blk_row(i, base):
        return jnp.minimum(base[0] + i, last)

    in_specs = [pl.BlockSpec((1, 1, bs, bs),
                             lambda i, j, cols, base: (blk_row(i, base), j,
                                                       0, 0))]
    operands = [a.data]
    if quantized:
        in_specs.append(pl.BlockSpec(
            (1, 1, ell), lambda i, j, cols, base: (blk_row(i, base), 0, 0),
            memory_space=pltpu.SMEM))
        operands.append(row_vectors(a.scales, nbr))
    in_specs.append(pl.BlockSpec(
        (bs, nx), lambda i, j, cols, base: (cols[i * ell + j], 0)))
    operands.append(x)
    alias = 2 + len(operands)            # the previous y, after the prefetch
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    call = pl.pallas_call(
        functools.partial(_bsr_kernel, quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows, ell),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (bs, nx), lambda i, j, cols, base: (blk_row(i, base), 0)),
            scratch_shapes=[pltpu.VMEM((bs, nx), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, nx), x.dtype),
        input_output_aliases={alias: 0},
        compiler_params=compiler_params("arbitrary", "arbitrary"),
        interpret=interpret,
        name="repro_bsr_matmul",
    )
    # Column indices per chunk, the ragged tail padded with the last row's.
    idx = jnp.minimum(jnp.arange(chunks * rows), last)
    cols = a.cols[idx].reshape(chunks, rows * ell)

    def chunk(k, y):
        base = (k * rows)[None].astype(jnp.int32)
        return call(cols[k], base, *operands, y)

    return jax.lax.fori_loop(0, chunks, chunk,
                             jnp.zeros((m, nx), x.dtype))


def _bsr_spmv_kernel(cols_ref, a_ref, *args, ell: int, quantized: bool):
    if quantized:
        s_ref, x_ref, o_ref = args
    else:
        (x_ref, o_ref), s_ref = args, None
    bs = x_ref.shape[-1]

    # (1 × bs) · (bs × bs): the row-vector form of A_block @ x_block, so the
    # contraction still lands on the MXU.
    def step(j, acc):
        xj = x_ref[pl.ds(cols_ref[0, 0, j], 1), :]
        return acc + jnp.dot(xj, load_block(a_ref, s_ref, j).T,
                             preferred_element_type=jnp.float32,
                             precision=HIGHEST)

    z = jax.lax.fori_loop(0, ell, step, jnp.zeros((1, bs), jnp.float32))
    o_ref[0] = z.astype(o_ref.dtype)


def spmv_vmem(a: BlockELL) -> int:
    """VMEM working set of bsr_matvec: the staged block-row and the
    resident x, each double-buffered, tiles padded to (8 × 128)."""
    bs, nbc = a.bs, a.shape[1] // a.bs
    db = jnp.dtype(a.data.dtype).itemsize
    bsl, bsp = -(-bs // 8) * 8, -(-bs // 128) * 128
    return 2 * a.ell * bsl * bsp * db + 2 * nbc * bsp * 4 + 4 * 8 * bsp * 4


@functools.partial(jax.jit, static_argnames=("interpret",))
def bsr_matvec(a: BlockELL, x: Array, *, interpret: bool = False) -> Array:
    """y = A @ x for block-ELL A (m × n) and a dense vector x (n,)."""
    m, n = a.shape
    assert x.shape == (n,), (a.shape, x.shape)
    bs, ell = a.bs, a.ell
    nbr, nbc = m // bs, n // bs
    quantized = a.scales is not None
    in_specs, operands = _row_operands(a, nbr)
    in_specs.append(pl.BlockSpec((nbc, bs), lambda i: (0, 0)))
    operands.append(x.reshape(nbc, bs))
    out = pl.pallas_call(
        functools.partial(_bsr_spmv_kernel, ell=ell, quantized=quantized),
        grid=(nbr,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, bs), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nbr, 1, bs), x.dtype),
        compiler_params=compiler_params("parallel"),
        interpret=interpret,
        name="repro_bsr_matvec",
    )(*operands)
    return out.reshape(m)


def _bsr_rmm_kernel(cols_ref, a_ref, *args, ell: int, quantized: bool):
    # The f32 output block stays resident across the whole grid (its
    # index_map is constant), so it is the accumulator itself.
    if quantized:
        s_ref, x_ref, acc_ref = args
    else:
        (x_ref, acc_ref), s_ref = args, None

    @pl.when(pl.program_id(0) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xi = x_ref[...]
    if xi.dtype != jnp.float32:
        xi = xi.astype(jnp.float32)

    def step(j, carry):
        acc_ref[cols_ref[0, 0, j]] += jnp.dot(
            load_block(a_ref, s_ref, j).T, xi,
            preferred_element_type=jnp.float32,
            precision=HIGHEST)
        return carry

    jax.lax.fori_loop(0, ell, step, 0)


def _bsr_rmm_partials_kernel(a_ref, *args, quantized: bool):
    if quantized:
        s_ref, x_ref, o_ref = args
    else:
        (x_ref, o_ref), s_ref = args, None
    blk = a_ref[0, 0]
    if blk.dtype != jnp.float32:
        blk = blk.astype(jnp.float32)
    if s_ref is not None:
        blk = blk * s_ref[0, 0, pl.program_id(1)]
    o_ref[0, 0] = jnp.dot(blk.T, x_ref[...],
                          preferred_element_type=jnp.float32,
                          precision=HIGHEST)


# Double-buffered streams + the resident f32 output/accumulator block
# must fit VMEM for the fused-scatter kernel to be legal.
def _rmm_fused_vmem(nbc: int, bs: int, nx: int, itemsize: int,
                    ell: int = 1) -> int:
    bsl, bsp = -(-bs // 8) * 8, -(-bs // 128) * 128
    return (2 * ell * bsl * bsp * itemsize + 2 * bsl * nx * itemsize
            + 2 * nbc * bsl * nx * 4)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bsr_rmatmul(a: BlockELL, x: Array, *, interpret: bool = False) -> Array:
    """y = Aᵀ @ X for block-ELL A (m × n) and dense X (m × nx).

    The transpose scatters: block (i, slot) contributes Aᵢⱼᵀ Xᵢ to output
    block-row j = cols[i, slot], and several grid steps can hit the same j.
    The scatter-add is fused into the kernel: the whole (nbc, bs, nx)
    accumulator is VMEM-resident and each partial is added at its
    block-column offset the moment the MXU produces it (the sequential grid
    makes the read-modify-write safe).  Padding slots carry zero data, so
    their contribution to block-row 0 vanishes.

    The resident accumulator scales with n·nx, so when it cannot fit the
    VMEM budget (wide matrix × wide right-hand side — sparserow strips nx
    at 512, but n is unbounded) the kernel falls back to the emit-partials
    form: one (nbr, ell, bs, nx) HBM buffer of per-block products plus an
    XLA segment_sum over block columns.
    """
    from . import autotune as _at
    m, n = a.shape
    assert x.shape[0] == m, (a.shape, x.shape)
    nx = x.shape[1]
    bs, ell = a.bs, a.ell
    nbr, nbc = m // bs, n // bs
    quantized = a.scales is not None

    if _rmm_fused_vmem(nbc, bs, nx, x.dtype.itemsize, ell) \
            > _at.VMEM_BUDGET:
        in_specs = [pl.BlockSpec((1, 1, bs, bs), lambda i, j: (i, j, 0, 0))]
        operands = [a.data]
        if quantized:
            in_specs.append(smem_rows(ell))
            operands.append(row_vectors(a.scales, nbr))
        in_specs.append(pl.BlockSpec((bs, nx), lambda i, j: (i, 0)))
        operands.append(x)
        partial = pl.pallas_call(
            functools.partial(_bsr_rmm_partials_kernel, quantized=quantized),
            grid=(nbr, ell),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, bs, nx),
                                   lambda i, j: (i, j, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((nbr, ell, bs, nx), jnp.float32),
            compiler_params=compiler_params("parallel", "parallel"),
            interpret=interpret,
            name="repro_bsr_rmatmul_partials",
        )(*operands)
        out = jax.ops.segment_sum(partial.reshape(nbr * ell, bs, nx),
                                  a.cols.reshape(-1), num_segments=nbc)
        return out.reshape(n, nx).astype(x.dtype)

    in_specs, operands = _row_operands(a, nbr)
    in_specs.append(pl.BlockSpec((bs, nx), lambda i: (i, 0)))
    operands.append(x)
    out = pl.pallas_call(
        functools.partial(_bsr_rmm_kernel, ell=ell, quantized=quantized),
        grid=(nbr,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((nbc, bs, nx), lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nbc, bs, nx), jnp.float32),
        compiler_params=compiler_params("arbitrary"),
        interpret=interpret,
        name="repro_bsr_rmatmul",
    )(*operands)
    return out.reshape(n, nx).astype(x.dtype)


# -- structure-exploiting jnp forms (off-TPU dispatch targets) ----------------

def bsr_matmul_jnp(a: BlockELL, x: Array) -> Array:
    """y = A @ X via gather + block einsum — flops ∝ stored blocks."""
    bs = a.bs
    xb = x.reshape(a.shape[1] // bs, bs, -1)              # (nbc, bs, nx)
    gathered = xb[a.cols]                                 # (nbr, ell, bs, nx)
    y = jnp.einsum("reij,rejn->rin", effective_data(a), gathered,
                   preferred_element_type=jnp.float32)
    return y.reshape(a.shape[0], -1).astype(x.dtype)


def bsr_matvec_jnp(a: BlockELL, x: Array) -> Array:
    """y = A @ x via gather + block einsum."""
    bs = a.bs
    xb = x.reshape(a.shape[1] // bs, bs)
    gathered = xb[a.cols]                                 # (nbr, ell, bs)
    y = jnp.einsum("reij,rej->ri", effective_data(a), gathered,
                   preferred_element_type=jnp.float32)
    return y.reshape(a.shape[0]).astype(x.dtype)


def bsr_rmatmul_jnp(a: BlockELL, x: Array) -> Array:
    """y = Aᵀ @ X: per-block partials + scatter-add over block columns."""
    bs = a.bs
    nbr = a.data.shape[0]
    nbc = a.shape[1] // bs
    xr = x.reshape(nbr, bs, -1)                           # (nbr, bs, nx)
    partial = jnp.einsum("reij,rin->rejn", effective_data(a), xr,
                         preferred_element_type=jnp.float32)
    out = jnp.zeros((nbc, bs, partial.shape[-1]), jnp.float32)
    out = out.at[a.cols.reshape(-1)].add(
        partial.reshape(-1, bs, partial.shape[-1]))
    return out.reshape(a.shape[1], -1).astype(x.dtype)
