"""Fused causal flash attention (forward) with native GQA.

The perf-critical hotspot of every assigned LM architecture.  Online-softmax
streaming over KV blocks keeps the (bq × d) output tile and running
(m, l) statistics in VMEM — the (S × S) score matrix never exists in HBM,
which is what makes prefill_32k shapes feasible at all.

GQA is handled in the grid machinery, not by materializing repeated KV
heads: the flattened (batch·q_head) grid axis maps to its KV head inside
the BlockSpec index_maps (hkv = hq // group), so KV blocks are DMA'd once
per group position — no memory amplification.

Used for serving (prefill) and available for training forward; the training
path defaults to XLA attention + remat since this kernel is forward-only
(decision recorded in DESIGN.md — a Pallas backward is a beyond-paper
extension tracked in EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


Array = jax.Array

MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)
LANES = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, bq: int, bk: int, nk: int, causal: bool):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[...] = jnp.zeros_like(l_ref)

    live = (ki * bk <= qi * bq + bq - 1) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0]                                   # (bq, d)
        k = k_ref[0]                                   # (bk, d)
        v = v_ref[0]                                   # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, MASK_VALUE)
        m_prev = m_ref[:, :1]                          # (bq, 1)
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                         # (bq, bk)
        corr = jnp.exp(m_prev - m_new)                 # (bq, 1)
        l_new = l_prev * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == nk - 1)
    def _flush():
        lsum = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(lsum, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk", "scale",
                                             "interpret", "q_heads_per_kv"))
def flash_attention(q: Array, k: Array, v: Array, *, scale: float | None = None,
                    causal: bool = True, bq: int, bk: int,
                    q_heads_per_kv: int = 1,
                    interpret: bool = False) -> Array:
    """q: (BHq, S, D) flattened batch·q-heads; k, v: (BHkv, S, D).

    BHq = BHkv · q_heads_per_kv with q-head-major flattening per batch
    element (ops.flash_attention handles the reshapes and padding).
    """
    bhq, sq, d = q.shape
    bhkv, sk, _ = k.shape
    assert bhq == bhkv * q_heads_per_kv, (q.shape, k.shape, q_heads_per_kv)
    assert sq % bq == 0 and sk % bk == 0, (sq, sk, bq, bk)
    scale = scale if scale is not None else 1.0 / float(np.sqrt(d))
    nq, nk = sq // bq, sk // bk
    g = q_heads_per_kv

    def kv_map(bh, qi, ki):
        return (bh // g, ki, 0)

    return pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, bq=bq, bk=bk, nk=nk,
                          causal=causal),
        grid=(bhq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bhq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="repro_flash_attention",
    )(q, k, v)
