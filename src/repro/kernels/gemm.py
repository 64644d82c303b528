"""Blocked MXU GEMM — the TPU answer to the paper's §4 BLAS benchmark.

The paper asks "how close to the hardware can a managed runtime get for
GEMM?" and answers with netlib-java→OpenBLAS.  Here the managed runtime is
XLA and the hand-tuned path is this Pallas kernel: an (bm × bn) output tile
stays resident in a VMEM float32 accumulator while the K dimension streams
through in (bm × bk)·(bk × bn) MXU-aligned chunks.

Tiling rules (TPU v5e):
  * last dim multiples of 128 (lane), second-to-last multiples of 8
    (sublane; 16 for bf16) — callers pad via ops.gemm.
  * bm/bn/bk have no baked-in default: ops.gemm resolves them through the
    shape-aware autotuner (kernels/autotune.py), which enumerates
    layout-legal tiles under the double-buffered VMEM budget and ranks
    them by roofline cost (cached winners on real hardware).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from .autotune import HIGHEST, compiler_params

Array = jax.Array


def _gemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    b = b_ref[...]
    # Sub-f32 storage (bf16/fp8) upcasts in VMEM: A streams from HBM at the
    # narrow width, the MXU contraction runs in f32.  No-op for f32 input.
    if a.dtype != jnp.float32:
        a = a.astype(jnp.float32)
    if b.dtype != jnp.float32:
        b = b.astype(jnp.float32)
    acc_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32,
                                  precision=HIGHEST)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret",
                                             "out_dtype"))
def gemm(a: Array, b: Array, *, bm: int, bn: int, bk: int,
         out_dtype=None, interpret: bool = False) -> Array:
    """C = A @ B with explicit VMEM tiling.  Shapes must be multiples of the
    tile sizes — `ops.gemm` pads arbitrary shapes."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, \
        f"({m},{k},{n}) not multiples of ({bm},{bk},{bn})"
    out_dtype = out_dtype or a.dtype
    k_steps = k // bk

    return pl.pallas_call(
        functools.partial(_gemm_kernel, k_steps=k_steps),
        grid=(m // bm, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="repro_gemm",
    )(a, b)
