"""Fused Mamba1 selective scan — the kernel §Perf hillclimb A calls for.

The XLA-expressible chunked associative scan moves O(passes · B·S·d·N)
f32 through HBM (~1.7 TB/layer/device measured on falcon-mamba train_4k;
three XLA-level levers measured refuted/marginal — EXPERIMENTS.md §Perf A).
This kernel keeps the recurrence state resident in VMEM and touches HBM
exactly once per input/output element:

    reads : x, dt (B,S,d) + B, C (B,S,N) + A (d,N), D (d)
    writes: y (B,S,d) [+ final state (B,d,N)]

→ traffic ≈ B·S·(2d + 2N)·4 B per layer ≈ 0.27 GB vs ~1.7 TB: the ~400×
the roofline analysis projects.

Layout: grid (B, d/bd, S/Q); the VMEM state tile is (N, bd) — N (=16)
on sublanes, the d-block (=128·k) on lanes, elementwise VPU math; the
sequential S dimension walks Q-sized chunks with the state carried in a
VMEM scratch across grid steps ("arbitrary" dimension semantics).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


Array = jax.Array


def _scan_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, y_ref, h_ref,
                 *, q: int, s_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a_log = a_ref[...]                    # (N, bd)  (= A, laid out N×d)
    d_skip = d_ref[...]                   # (1, bd)

    def step(t, h):
        xt = x_ref[0, t]                  # (bd,)
        dtt = dt_ref[0, t]                # (bd,)
        bt = b_ref[0, t]                  # (N,)
        ct = c_ref[0, t]                  # (N,)
        decay = jnp.exp(dtt[None, :] * a_log)          # (N, bd)
        h = decay * h + (dtt * xt)[None, :] * bt[:, None]
        yt = jnp.sum(h * ct[:, None], axis=0) + d_skip[0] * xt
        y_ref[0, t] = yt.astype(y_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, q, step, h_ref[...])


@functools.partial(jax.jit, static_argnames=("q", "bd", "interpret"))
def selective_scan(x: Array, dt: Array, A: Array, B: Array, C: Array,
                   D: Array, *, q: int, bd: int = 128,
                   interpret: bool = False) -> Array:
    """y[b,t,d] for h_t = exp(dt·A)∘h_{t-1} + dt·B_t·x_t, y_t = C_t·h_t + D·x_t.

    x, dt: (Bt, S, d); A: (d, N); B, C: (Bt, S, N); D: (d,).
    S % q == 0 and d % bd == 0 (ops wrapper pads)."""
    Bt, S, d = x.shape
    N = A.shape[1]
    assert S % q == 0 and d % bd == 0, (x.shape, q, bd)
    a_nd = A.T                                     # (N, d)
    d_2d = D[None, :]                              # (1, d)
    s_steps = S // q

    return pl.pallas_call(
        functools.partial(_scan_kernel, q=q, s_steps=s_steps),
        grid=(Bt, d // bd, s_steps),
        in_specs=[
            pl.BlockSpec((1, q, bd), lambda b, j, s: (b, s, j)),   # x
            pl.BlockSpec((1, q, bd), lambda b, j, s: (b, s, j)),   # dt
            pl.BlockSpec((1, q, N), lambda b, j, s: (b, s, 0)),    # B
            pl.BlockSpec((1, q, N), lambda b, j, s: (b, s, 0)),    # C
            pl.BlockSpec((N, bd), lambda b, j, s: (0, j)),         # A (N,d)
            pl.BlockSpec((1, bd), lambda b, j, s: (0, j)),         # D
        ],
        out_specs=pl.BlockSpec((1, q, bd), lambda b, j, s: (b, s, j)),
        out_shape=jax.ShapeDtypeStruct((Bt, S, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, bd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="repro_selective_scan",
    )(x, dt, B, C, a_nd, d_2d)
