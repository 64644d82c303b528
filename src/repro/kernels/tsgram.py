"""Tall-skinny Gram kernel: G = AᵀA for m ≫ n (the DIMSUM hotspot, §3.1.2).

This is the per-shard compute inside RowMatrix.gram(): each chip reduces its
(m_local × n) row shard to an (n × n) partial Gram before the cross-chip
psum.  The kernel streams row blocks through VMEM while a float32
accumulator stays resident — fully MXU-bound, no (m × n) intermediate ever
materialized in HBM.

The output is tiled in (bn × bn) tiles (grid axes 0 and 1).  With bn = n
the whole Gram is one tile and A is read exactly once — the paper's "AᵀA
fits on the driver" regime one level down the memory hierarchy.  Wider
Grams, whose (n × n) accumulator cannot sit in VMEM, run bn < n: tile
(i, j) streams the row blocks' column blocks i and j (two operands over the
same buffer, never a copy), so A is read 2·n/bn times.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from .autotune import HIGHEST, compiler_params

Array = jax.Array
# lhsᵀ·rhs contracting the row-block axis of both operands, no transpose.
_TN = (((0,), (0,)), ((), ()))


def _tsgram_kernel(l_ref, *refs, m_steps: int, tiled: bool):
    if tiled:
        r_ref, o_ref, acc_ref = refs
    else:
        (o_ref, acc_ref), r_ref = refs, l_ref

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lhs, rhs = l_ref[...], r_ref[...]
    # Sub-f32 storage upcasts in VMEM; the accumulator is f32 regardless.
    if lhs.dtype != jnp.float32:
        lhs, rhs = lhs.astype(jnp.float32), rhs.astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(lhs, rhs, _TN,
                                        preferred_element_type=jnp.float32,
                                        precision=HIGHEST)

    @pl.when(pl.program_id(2) == m_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret",
                                             "out_dtype"))
def tsgram(a: Array, *, bm: int, bn: int | None = None, out_dtype=None,
           interpret: bool = False) -> Array:
    """G = AᵀA streaming over row blocks of size `bm`, output in (bn × bn)
    tiles (both autotuned by ops.tsgram; bn defaults to n).  m must be a
    multiple of bm, n a multiple of 128 and of bn (ops.tsgram pads)."""
    m, n = a.shape
    bn = n if bn is None else min(bn, n)
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    out_dtype = out_dtype or a.dtype
    m_steps = m // bm
    tiled = bn < n
    in_specs = [pl.BlockSpec((bm, bn), lambda i, j, k: (k, i))]
    operands = [a]
    if tiled:
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, k: (k, j)))
        operands.append(a)

    return pl.pallas_call(
        functools.partial(_tsgram_kernel, m_steps=m_steps, tiled=tiled),
        grid=(n // bn, n // bn, m_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bn, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bn, bn), jnp.float32)],
        compiler_params=compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="repro_tsgram",
    )(*operands)
