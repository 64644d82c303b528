"""Streaming cross-Gram kernel: B = AᵀQ for m ≫ r (the randomized-SVD
projection hotspot).

This is the per-shard compute inside RowMatrix.project(): each chip reduces
its (m_local × n) row shard of A against the conforming (m_local × r) row
shard of the range basis Q down to an (n × r) partial projection before the
cross-chip psum.  Same VMEM-accumulator structure as tsgram (HBM→VMEM
streaming over row blocks, resident float32 accumulator, fully MXU-bound)
but generalized two ways:

  * two streamed operands — the (m × n) and (m × r) inputs are never joined
    in HBM; only the small (n × r) product ever exists;
  * the output is tiled over n (grid axis 0), so the accumulator is
    (bn × r) regardless of how wide A is — exactly the n > GRAM_THRESHOLD
    regime the randomized SVD mode dispatches to.  Each n-tile re-streams
    Q's row blocks (r ≤ k+p is tiny, so the re-read traffic is noise next
    to the single pass over A).

Not implemented as tsgram(a, a): the single-operand Gram kernel reads each
row block once where this one would DMA it twice — for the Gram hotspot
that is a 2× HBM-traffic difference, so the two kernels stay separate.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from .autotune import HIGHEST, compiler_params

Array = jax.Array


def _randsketch_kernel(a_ref, q_ref, o_ref, acc_ref, *, m_steps: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    q = q_ref[...]
    # Sub-f32 storage upcasts in VMEM; the accumulator is f32 regardless.
    if a.dtype != jnp.float32:
        a = a.astype(jnp.float32)
    if q.dtype != jnp.float32:
        q = q.astype(jnp.float32)
    acc_ref[...] += jnp.dot(a.T, q, preferred_element_type=jnp.float32,
                                    precision=HIGHEST)

    @pl.when(pl.program_id(1) == m_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "interpret", "out_dtype"))
def randsketch(a: Array, q: Array, *, bm: int, bn: int,
               out_dtype=None, interpret: bool = False) -> Array:
    """B = AᵀQ streaming over conforming (bm)-row blocks, output tiled in
    (bn)-column strips (both autotuned by ops.randsketch).
    m % bm == 0, n % bn == 0, r % 128 == 0 (ops.randsketch pads)."""
    m, n = a.shape
    mq, r = q.shape
    assert m == mq, (m, mq)
    assert m % bm == 0, (m, bm)
    assert n % bn == 0, (n, bn)
    out_dtype = out_dtype or a.dtype
    m_steps, n_steps = m // bm, n // bn

    return pl.pallas_call(
        functools.partial(_randsketch_kernel, m_steps=m_steps),
        grid=(n_steps, m_steps),
        in_specs=[pl.BlockSpec((bm, bn), lambda j, i: (i, j)),
                  pl.BlockSpec((bm, r), lambda j, i: (i, 0))],
        out_specs=pl.BlockSpec((bn, r), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((n, r), out_dtype),
        scratch_shapes=[pltpu.VMEM((bn, r), jnp.float32)],
        compiler_params=compiler_params("parallel", "arbitrary"),
        interpret=interpret,
        name="repro_randsketch",
    )(a, q)
