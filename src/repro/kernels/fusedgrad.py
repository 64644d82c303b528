"""Single-pass fused composite gradient — the optimizer hot-path kernel.

The paper's recipe keeps the matrix on the cluster and the vectors on the
driver (§3.2–3.3), and its optimizer loop consumes exactly (value, gradient)
pairs of f(Ax).  Computed naively that is TWO streaming passes over A per
evaluation: apply (z = A x) and adjoint (g = Aᵀ ∇f(z)).  But for the
row-separable losses of the whole Figure-1 family — f(z) = Σᵢ wᵢ ℓ(zᵢ, tᵢ)
with ℓ ∈ {quadratic, logistic, huber, poisson} — the residual of a row block
depends only on
that block's rows, so it can be evaluated *on-chip* between the two products
while the block is still in VMEM.  That is Spark's one-pass treeAggregate
gradient pattern, executed one level down the memory hierarchy:

    per (bm × n) row block of A (one HBM read):
        z_blk = A_blk x                      (MXU)
        r_blk = w_blk ∘ ℓ'(z_blk, t_blk)     (VPU, on-chip)
        g    += A_blkᵀ r_blk                 (MXU, resident accumulator)
        f    += Σ w_blk ℓ(z_blk, t_blk)      (scalar accumulator)

One pass over A instead of two — on an HBM-bound kernel that halves the
per-evaluation time.  The kernel also writes z out (it is computed anyway;
m·4 B next to m·n·db is noise), so callers that want the image A x — parity
checks, future cached-image schemes — get it for free.

Two layouts share the row-local loss math:

  * ``fused_grad``     — dense tall-skinny row shards (the RowMatrix path);
  * ``fused_grad_bsr`` — BlockELL shards (kernels/bsr.py layout): the whole
    block-row's stored blocks are staged per grid step, z accumulates over
    the ell slots against a VMEM-resident x, and the transpose
    contributions scatter-add into a resident (nbc × bs) accumulator at
    the block-column read from the row's SMEM column list — each stored
    block is read once.

The ``*_jnp`` forms are the structure-exploiting off-TPU dispatch targets
(kernels/ops.py); the densifying oracle lives in kernels/ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from .autotune import HIGHEST, compiler_params
from .bsr import BlockELL, effective_data, load_block, row_vectors, smem_rows

Array = jax.Array

LOSSES = ("quad", "logistic", "huber", "poisson")


def row_loss_elem(z: Array, t: Array, w: Array, loss: str,
                  param: float = 1.0) -> tuple[Array, Array]:
    """Elementwise (w ∘ ℓ(z, t), w ∘ ℓ'(z, t)) in float32 — the row-local
    residual shared by the kernels and the structured jnp paths.  Keeping
    the loss un-summed lets the multi-RHS kernels accumulate a per-request
    value over any axis layout.

      quad:     ℓ(z, b) = ½ (z − b)²,            ℓ' = z − b
      logistic: ℓ(z, y) = log(1 + e^(−y z)),     ℓ' = −y σ(−y z)
      huber:    ℓ(z, b) = ½d² if |d| ≤ δ else δ(|d| − ½δ),  d = z − b,
                ℓ' = clip(d, ±δ)                (δ = `param`, static)
      poisson:  ℓ(z, y) = e^z − y z (log-link NLL, + const), ℓ' = e^z − y

    `param` is a static Python float (it reaches the Pallas kernels as a
    compile-time constant alongside the loss id)."""
    z = z.astype(jnp.float32)
    t = t.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if loss == "quad":
        d = z - t
        return 0.5 * w * d * d, w * d
    if loss == "logistic":
        mz = -t * z
        return w * jnp.logaddexp(0.0, mz), w * (-t) * jax.nn.sigmoid(mz)
    if loss == "huber":
        delta = jnp.float32(param)
        d = z - t
        a = jnp.abs(d)
        le = w * jnp.where(a <= delta, 0.5 * d * d,
                           delta * (a - 0.5 * delta))
        return le, w * jnp.clip(d, -delta, delta)
    if loss == "poisson":
        ez = jnp.exp(z)
        return w * (ez - t * z), w * (ez - t)
    raise ValueError(f"loss must be one of {LOSSES}, got {loss!r}")


def row_loss_grad(z: Array, t: Array, w: Array, loss: str,
                  param: float = 1.0) -> tuple[Array, Array]:
    """(Σ wᵢ ℓ(zᵢ, tᵢ), w ∘ ℓ'(z, t)) in float32 — the fully-reduced form
    of `row_loss_elem` (the single-RHS kernels and jnp paths use this)."""
    le, r = row_loss_elem(z, t, w, loss, param)
    return jnp.sum(le), r


# -- dense tall-skinny kernel -------------------------------------------------

def _fused_grad_kernel(a_ref, x_ref, t_ref, w_ref, f_ref, g_ref, z_ref,
                       g_acc, f_acc, *, m_steps: int, loss: str,
                       param: float):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        g_acc[...] = jnp.zeros_like(g_acc)
        f_acc[0, 0] = jnp.float32(0.0)

    blk = a_ref[...]                                     # (bm, n)
    # Sub-f32 storage upcasts in VMEM (no-op for f32): one narrow HBM read,
    # f32 math on-chip.
    if blk.dtype != jnp.float32:
        blk = blk.astype(jnp.float32)
    # Row-vector matmuls keep both contractions on the MXU: z = x Aᵀ and
    # g += r A are (1 × bm)·(bm × n) products over the block already in VMEM.
    z = jnp.dot(x_ref[...], blk.T, preferred_element_type=jnp.float32,
                                   precision=HIGHEST)
    fpart, r = row_loss_grad(z, t_ref[...], w_ref[...], loss, param)
    z_ref[...] = z
    g_acc[...] += jnp.dot(r.astype(blk.dtype), blk,
                          preferred_element_type=jnp.float32,
                          precision=HIGHEST)
    f_acc[0, 0] += fpart

    @pl.when(pl.program_id(0) == m_steps - 1)
    def _flush():
        g_ref[...] = g_acc[...]
        f_ref[0, 0] = f_acc[0, 0]


@functools.partial(jax.jit,
                   static_argnames=("loss", "param", "bm", "interpret"))
def fused_grad(a: Array, x: Array, t: Array, w: Array, *, loss: str,
               bm: int, param: float = 1.0, interpret: bool = False
               ) -> tuple[Array, Array, Array]:
    """(f, g, z) = (Σ wᵢ ℓ((Ax)ᵢ, tᵢ), Aᵀ(w ∘ ℓ'(Ax, t)), Ax) in ONE
    streaming pass over A.  Layout: a (m × n) with m % bm == 0 and
    n % 128 == 0; x (1 × n); t, w (1 × m) — ops.fused_grad pads.
    Outputs are float32: f (1 × 1), g (1 × n), z (1 × m)."""
    m, n = a.shape
    assert m % bm == 0, (m, bm)
    assert x.shape == (1, n) and t.shape == (1, m) and w.shape == (1, m), \
        (a.shape, x.shape, t.shape, w.shape)
    m_steps = m // bm

    return pl.pallas_call(
        functools.partial(_fused_grad_kernel, m_steps=m_steps, loss=loss,
                          param=float(param)),
        grid=(m_steps,),
        in_specs=[
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, bm), lambda i: (0, i)),
            pl.BlockSpec((1, bm), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, bm), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, m), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, n), jnp.float32),
                        pltpu.SMEM((1, 1), jnp.float32)],
        compiler_params=compiler_params("arbitrary"),
        interpret=interpret,
        name="repro_fused_grad",
    )(a, x, t, w)


# -- BlockELL (BSR) kernel ----------------------------------------------------

def fused_grad_bsr_vmem(bs: int, ell: int, n: int, dtype) -> int:
    """Resident VMEM working-set estimate for the BSR fused kernel over an
    n-wide BlockELL of `ell` (bs × bs) blocks per block-row, every
    pipelined block counted twice: the staged block-row, x, the f32
    gradient accumulator and output, and the t/w/z (1 × bs) strips.  Tiles
    pad to (8 sublanes × 128 lanes).  Past the budget the planner's "grad"
    decision picks the two passes, and ops dispatch composes them (mirroring
    bsr_rmatmul's own fallback)."""
    nbc = -(-n // bs)
    db = jnp.dtype(dtype).itemsize
    bsl, bsp = -(-bs // 8) * 8, -(-bs // 128) * 128
    return (2 * ell * bsl * bsp * db       # block-row stream
            + 2 * nbc * bsp * db           # resident x
            + 3 * nbc * bsp * 4            # g accumulator + g out (f32)
            + 6 * 8 * bsp * 4)             # t, w, z (1 × bs) strips


def _fused_grad_bsr_kernel(cols_ref, a_ref, x_ref, t_ref, w_ref,
                           f_ref, g_ref, z_ref, g_acc, f_acc, *, ell: int,
                           loss: str, param: float):
    i = pl.program_id(0)
    bs = x_ref.shape[-1]

    @pl.when(i == 0)
    def _init():
        g_acc[...] = jnp.zeros_like(g_acc)
        f_acc[0, 0] = jnp.float32(0.0)

    # z for the whole block-row: accumulate over the ell stored blocks while
    # they are staged in VMEM (padding slots are zero, so col 0 is harmless).
    def zstep(j, zacc):
        xj = x_ref[pl.ds(cols_ref[0, 0, j], 1), :]
        return zacc + jnp.dot(xj, load_block(a_ref, None, j).T,
                              preferred_element_type=jnp.float32,
                              precision=HIGHEST)

    z = jax.lax.fori_loop(0, ell, zstep, jnp.zeros((1, bs), jnp.float32))
    fpart, r = row_loss_grad(z, t_ref[0], w_ref[0], loss, param)
    z_ref[0] = z
    f_acc[0, 0] += fpart

    # Second sweep over the SAME staged blocks (no HBM re-read): scatter-add
    # each Aᵢⱼᵀ r into the resident block-column accumulator.
    def gstep(j, carry):
        c = cols_ref[0, 0, j]
        g_acc[pl.ds(c, 1), :] += jnp.dot(r, load_block(a_ref, None, j),
                                         preferred_element_type=jnp.float32,
                                         precision=HIGHEST)
        return carry

    jax.lax.fori_loop(0, ell, gstep, 0)

    @pl.when(i == pl.num_programs(0) - 1)
    def _flush():
        g_ref[...] = g_acc[...]
        f_ref[0, 0] = f_acc[0, 0]


@functools.partial(jax.jit, static_argnames=("loss", "param", "interpret"))
def fused_grad_bsr(a: BlockELL, x: Array, t: Array, w: Array, *, loss: str,
                   param: float = 1.0,
                   interpret: bool = False) -> tuple[Array, Array, Array]:
    """Fused (f, g, z) for a BlockELL shard: every stored block is read from
    HBM exactly once.  x (n,), t/w (m,) over the padded BlockELL dims;
    outputs f () , g (n,), z (m,) in float32."""
    m, n = a.shape
    assert x.shape == (n,) and t.shape == (m,) and w.shape == (m,), \
        (a.shape, x.shape, t.shape, w.shape)
    bs, ell = a.bs, a.ell
    nbr, nbc = m // bs, n // bs
    assert a.scales is None, "quantized shards take the two-pass form"
    row = pl.BlockSpec((1, 1, bs), lambda i: (i, 0, 0))
    f, g, z = pl.pallas_call(
        functools.partial(_fused_grad_bsr_kernel, ell=ell, loss=loss,
                          param=float(param)),
        grid=(nbr,),
        in_specs=[smem_rows(ell),
                  pl.BlockSpec((1, ell, bs, bs), lambda i: (i, 0, 0, 0)),
                  pl.BlockSpec((nbc, bs), lambda i: (0, 0)), row, row],
        out_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((nbc, bs), lambda i: (0, 0)),
            row,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((nbc, bs), jnp.float32),
            jax.ShapeDtypeStruct((nbr, 1, bs), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((nbc, bs), jnp.float32),
                        pltpu.SMEM((1, 1), jnp.float32)],
        compiler_params=compiler_params("arbitrary"),
        interpret=interpret,
        name="repro_fused_grad_bsr",
    )(row_vectors(a.cols, nbr), a.data, x.reshape(nbc, bs),
      row_vectors(t, nbr), row_vectors(w, nbr))
    return f[0, 0], g.reshape(n), z.reshape(m)


# -- multi-RHS (request-batched) dense kernel ---------------------------------

def _fused_grad_multi_kernel(a_ref, x_ref, t_ref, w_ref, f_ref, g_ref, z_ref,
                             g_acc, f_acc, *, m_steps: int, loss: str,
                             param: float):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        g_acc[...] = jnp.zeros_like(g_acc)
        f_acc[...] = jnp.zeros_like(f_acc)

    blk = a_ref[...]                                     # (bm, n)
    if blk.dtype != jnp.float32:
        blk = blk.astype(jnp.float32)          # sub-f32 storage upcast
    x = x_ref[...]                                       # (kp, n)
    # One block read serves every request: z = X Aᵀ is a (kp × n)·(n × bm)
    # product over the block already in VMEM — the whole point of grouping.
    z = jnp.dot(x, blk.T, preferred_element_type=jnp.float32,
                          precision=HIGHEST)   # (kp, bm)
    le, r = row_loss_elem(z, t_ref[...], w_ref[...], loss, param)
    z_ref[...] = z
    g_acc[...] += jnp.dot(r.astype(blk.dtype), blk,
                          preferred_element_type=jnp.float32,
                          precision=HIGHEST)
    # Per-request loss: fold the lane-aligned bm axis down to one 128-lane
    # strip (bm % 128 == 0 by layout contract); the host sums the strip.
    kp, bm = le.shape
    f_acc[...] += le.reshape(kp, bm // 128, 128).sum(axis=1)

    @pl.when(pl.program_id(0) == m_steps - 1)
    def _flush():
        g_ref[...] = g_acc[...]
        f_ref[...] = f_acc[...]


@functools.partial(jax.jit,
                   static_argnames=("loss", "param", "bm", "interpret"))
def fused_grad_multi(a: Array, x: Array, t: Array, w: Array, *, loss: str,
                     bm: int, param: float = 1.0, interpret: bool = False
                     ) -> tuple[Array, Array, Array]:
    """Request-batched fused gradients: (f, g, z) for kp right-hand sides
    in ONE streaming pass over A — each A block is read from HBM once and
    amortized across every request in the group.  Layout: a (m × n) with
    m % bm == 0, bm % 128 == 0, n % 128 == 0; x (kp × n); t, w (kp × m)
    with kp a multiple of 8 (sublane) — ops.fused_grad_multi pads.
    Outputs are float32: f (kp × 128) [sum axis 1 for the per-request
    values], g (kp × n), z (kp × m)."""
    m, n = a.shape
    kp = x.shape[0]
    assert m % bm == 0 and bm % 128 == 0, (m, bm)
    assert kp % 8 == 0, kp
    assert x.shape == (kp, n) and t.shape == (kp, m) and w.shape == (kp, m), \
        (a.shape, x.shape, t.shape, w.shape)
    m_steps = m // bm

    return pl.pallas_call(
        functools.partial(_fused_grad_multi_kernel, m_steps=m_steps,
                          loss=loss, param=float(param)),
        grid=(m_steps,),
        in_specs=[
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
            pl.BlockSpec((kp, n), lambda i: (0, 0)),
            pl.BlockSpec((kp, bm), lambda i: (0, i)),
            pl.BlockSpec((kp, bm), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((kp, 128), lambda i: (0, 0)),
            pl.BlockSpec((kp, n), lambda i: (0, 0)),
            pl.BlockSpec((kp, bm), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((kp, 128), jnp.float32),
            jax.ShapeDtypeStruct((kp, n), jnp.float32),
            jax.ShapeDtypeStruct((kp, m), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((kp, n), jnp.float32),
                        pltpu.VMEM((kp, 128), jnp.float32)],
        compiler_params=compiler_params("arbitrary"),
        interpret=interpret,
        name="repro_fused_grad_multi",
    )(a, x, t, w)


# -- multi-RHS BlockELL (BSR) kernel ------------------------------------------

def fused_grad_bsr_multi_vmem(a: BlockELL, kp: int) -> int:
    """Resident VMEM working-set estimate for the multi-RHS BSR fused
    kernel: the per-request copies of x, the gradient accumulator, and the
    t/w/z strips all scale with kp; the staged block-row does not.  Every
    pipelined block counted twice, tiles padded to (8 × 128)."""
    bs, ell = a.bs, a.ell
    nbc = a.shape[1] // bs
    db = jnp.dtype(a.data.dtype).itemsize
    bsl, bsp = -(-bs // 8) * 8, -(-bs // 128) * 128
    return (2 * ell * bsl * bsp * db        # block-row stream
            + 2 * nbc * kp * bsp * db       # resident x (nbc × kp × bs)
            + 3 * nbc * kp * bsp * 4        # g accumulator + g out (f32)
            + 3 * kp * bsp * 4              # f accumulator + f out
            + 6 * kp * bsp * 4)             # t, w, z (kp × bs) strips


def _fused_grad_bsr_multi_kernel(cols_ref, a_ref, x_ref, t_ref, w_ref,
                                 f_ref, g_ref, z_ref, g_acc, f_acc, *,
                                 ell: int, loss: str, param: float):
    i = pl.program_id(0)
    kp, bs = x_ref.shape[1], x_ref.shape[2]

    @pl.when(i == 0)
    def _init():
        g_acc[...] = jnp.zeros_like(g_acc)
        f_acc[...] = jnp.zeros_like(f_acc)

    # z for the whole block-row, all requests at once: each staged block is
    # contracted against the (kp × bs) slab of x for its block-column.
    def zstep(j, zacc):
        xj = x_ref[cols_ref[0, 0, j]]                     # (kp, bs)
        return zacc + jnp.dot(xj, load_block(a_ref, None, j).T,
                              preferred_element_type=jnp.float32,
                              precision=HIGHEST)

    z = jax.lax.fori_loop(0, ell, zstep, jnp.zeros((kp, bs), jnp.float32))
    le, r = row_loss_elem(z, t_ref[0], w_ref[0], loss, param)
    z_ref[0] = z
    f_acc[...] += le                                     # (kp, bs), summed on host

    # Second sweep over the SAME staged blocks (no HBM re-read): scatter-add
    # each (kp × bs) Aᵢⱼᵀ r slab into the resident block-column accumulator.
    def gstep(j, carry):
        c = cols_ref[0, 0, j]
        g_acc[c] += jnp.dot(r, load_block(a_ref, None, j),
                            preferred_element_type=jnp.float32,
                            precision=HIGHEST)
        return carry

    jax.lax.fori_loop(0, ell, gstep, 0)

    @pl.when(i == pl.num_programs(0) - 1)
    def _flush():
        g_ref[...] = g_acc[...]
        f_ref[...] = f_acc[...]


@functools.partial(jax.jit, static_argnames=("loss", "param", "interpret"))
def fused_grad_bsr_multi(a: BlockELL, x: Array, t: Array, w: Array, *,
                         loss: str, param: float = 1.0,
                         interpret: bool = False
                         ) -> tuple[Array, Array, Array]:
    """Request-batched fused (f, g, z) for a BlockELL shard: every stored
    block is read from HBM exactly once and serves all kp requests.
    x (kp, n), t/w (kp, m) over the padded BlockELL dims, kp % 8 == 0;
    outputs f (kp,), g (kp, n), z (kp, m) in float32."""
    m, n = a.shape
    kp = x.shape[0]
    assert kp % 8 == 0, kp
    assert x.shape == (kp, n) and t.shape == (kp, m) and w.shape == (kp, m), \
        (a.shape, x.shape, t.shape, w.shape)
    bs, ell = a.bs, a.ell
    nbr, nbc = m // bs, n // bs
    assert a.scales is None, "quantized shards take the two-pass form"

    def rows(v):            # (kp, m) -> (nbr, kp, bs): one block-row a step
        return v.reshape(kp, nbr, bs).transpose(1, 0, 2)

    row = pl.BlockSpec((1, kp, bs), lambda i: (i, 0, 0))
    f, g, z = pl.pallas_call(
        functools.partial(_fused_grad_bsr_multi_kernel, ell=ell,
                          loss=loss, param=float(param)),
        grid=(nbr,),
        in_specs=[smem_rows(ell),
                  pl.BlockSpec((1, ell, bs, bs), lambda i: (i, 0, 0, 0)),
                  pl.BlockSpec((nbc, kp, bs), lambda i: (0, 0, 0)),
                  row, row],
        out_specs=[
            pl.BlockSpec((kp, bs), lambda i: (0, 0)),
            pl.BlockSpec((nbc, kp, bs), lambda i: (0, 0, 0)),
            row,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((kp, bs), jnp.float32),
            jax.ShapeDtypeStruct((nbc, kp, bs), jnp.float32),
            jax.ShapeDtypeStruct((nbr, kp, bs), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((nbc, kp, bs), jnp.float32),
                        pltpu.VMEM((kp, bs), jnp.float32)],
        compiler_params=compiler_params("arbitrary"),
        interpret=interpret,
        name="repro_fused_grad_bsr_multi",
    )(row_vectors(a.cols, nbr), a.data,
      x.reshape(kp, nbc, bs).transpose(1, 0, 2), rows(t), rows(w))
    return (f.sum(axis=1), g.transpose(1, 0, 2).reshape(kp, n),
            z.transpose(1, 0, 2).reshape(kp, m))


# -- structured jnp forms (off-TPU dispatch targets) --------------------------

def fused_grad_jnp(a: Array, x: Array, t: Array, w: Array, *,
                   loss: str, param: float = 1.0
                   ) -> tuple[Array, Array, Array]:
    """Dense (f, g, z) with the same row-local loss math as the kernel;
    x/t/w are flat vectors here.  g is the row-vector contraction r·A —
    the kernel's own form, and measurably faster than Aᵀr on CPU too (no
    transposed operand)."""
    z = jnp.dot(a, x, preferred_element_type=jnp.float32)
    f, r = row_loss_grad(z, t, w, loss, param)
    # The residual stays f32 for sub-f32 storage (matching the kernel,
    # which never narrows r); for f32 a this cast is a no-op.
    rc = r.astype(a.dtype) if a.dtype == jnp.float32 else r
    g = jnp.dot(rc, a, preferred_element_type=jnp.float32)
    return f, g, z


def fused_grad_bsr_jnp(a: BlockELL, x: Array, t: Array, w: Array, *,
                       loss: str, param: float = 1.0
                       ) -> tuple[Array, Array, Array]:
    """BlockELL (f, g, z) via gather/einsum + scatter-add — flops ∝ stored
    blocks, no densification (the CPU dispatch target)."""
    bs = a.bs
    nbr, ell = a.data.shape[0], a.ell
    nbc = a.shape[1] // bs
    data = effective_data(a)
    xb = x.reshape(nbc, bs)
    gathered = xb[a.cols]                                 # (nbr, ell, bs)
    z = jnp.einsum("reij,rej->ri", data, gathered,
                   preferred_element_type=jnp.float32).reshape(a.shape[0])
    f, r = row_loss_grad(z, t, w, loss, param)
    rb = r.astype(data.dtype).reshape(nbr, bs)
    partial = jnp.einsum("reij,ri->rej", data, rb,
                         preferred_element_type=jnp.float32)
    g = jnp.zeros((nbc, bs), jnp.float32).at[a.cols.reshape(-1)].add(
        partial.reshape(nbr * ell, bs))
    return f, g.reshape(a.shape[1]), z


def fused_grad_multi_jnp(a: Array, x: Array, t: Array, w: Array, *,
                         loss: str, param: float = 1.0
                         ) -> tuple[Array, Array, Array]:
    """Dense multi-RHS (f, g, z) with the kernel's row-local loss math:
    x (k, n), t/w (k, m) → f (k,), g (k, n), z (k, m).  One logical pass
    over A shared by all k requests (XLA reads A once per contraction)."""
    z = jnp.dot(x, a.T, preferred_element_type=jnp.float32)
    le, r = row_loss_elem(z, t, w, loss, param)
    rc = r.astype(a.dtype) if a.dtype == jnp.float32 else r
    g = jnp.dot(rc, a, preferred_element_type=jnp.float32)
    return le.sum(axis=1), g, z


def fused_grad_bsr_multi_jnp(a: BlockELL, x: Array, t: Array, w: Array, *,
                             loss: str, param: float = 1.0
                             ) -> tuple[Array, Array, Array]:
    """BlockELL multi-RHS (f, g, z) via gather/einsum + scatter-add —
    flops ∝ stored blocks × k, no densification (the CPU dispatch target).
    x (k, n), t/w (k, m) → f (k,), g (k, n), z (k, m)."""
    bs = a.bs
    nbr, ell = a.data.shape[0], a.ell
    nbc = a.shape[1] // bs
    k = x.shape[0]
    data = effective_data(a)
    xb = x.reshape(k, nbc, bs)
    gathered = xb[:, a.cols]                              # (k, nbr, ell, bs)
    z = jnp.einsum("reij,krej->kri", data, gathered,
                   preferred_element_type=jnp.float32).reshape(k, a.shape[0])
    le, r = row_loss_elem(z, t, w, loss, param)
    rb = r.astype(data.dtype).reshape(k, nbr, bs)
    partial = jnp.einsum("reij,kri->krej", data, rb,
                         preferred_element_type=jnp.float32)
    g = jnp.zeros((k, nbc, bs), jnp.float32).at[:, a.cols.reshape(-1)].add(
        partial.reshape(k, nbr * ell, bs))
    return le.sum(axis=1), g.reshape(k, a.shape[1]), z
