"""fig1-dense: a dense float32 RowMatrix drawn on the device from the seed.

`build(cfg, key)` returns the matrix the program is given and the plain
references over the same array (the names `bench/loadgen.py` reads).  The
draw and the reference operators work one row block at a time, so that
neither holds a second copy of A."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from refs import spectral

# The sizes the benchmark's own tests run this configuration at on the CPU.
TINY = {"rows": 8192, "cols": 64, "row_block": 2048}


def draw(key, m, n, block):
    """A (m, n) standard normal matrix, filled one row block at a time."""
    @jax.jit
    def run(key):
        def body(i, A):
            blk = jax.random.normal(jax.random.fold_in(key, i), (block, n),
                                    jnp.float32)
            return jax.lax.dynamic_update_slice_in_dim(A, blk, i * block, 0)
        return jax.lax.fori_loop(0, m // block, body,
                                 jnp.zeros((m, n), jnp.float32))
    return run(key)


def ops(prec, block):
    """A·X and Aᵀ·U for (n, s) and (m, s) blocks, one row block of A at a
    time, at matmul precision `prec`."""
    def mv(A, X):
        m = A.shape[0]

        def body(i, out):
            blk = jax.lax.dynamic_slice_in_dim(A, i * block, block, 0)
            return jax.lax.dynamic_update_slice_in_dim(
                out, jnp.dot(blk, X, precision=prec), i * block, 0)
        return jax.lax.fori_loop(0, m // block, body,
                                 jnp.zeros((m, X.shape[1]), jnp.float32))

    def rmv(A, U):
        m, n = A.shape

        def body(i, acc):
            blk = jax.lax.dynamic_slice_in_dim(A, i * block, block, 0)
            ub = jax.lax.dynamic_slice_in_dim(U, i * block, block, 0)
            return acc + jnp.dot(blk.T, ub, precision=prec)
        return jax.lax.fori_loop(0, m // block, body,
                                 jnp.zeros((n, U.shape[1]), jnp.float32))
    return mv, rmv


class Dense:
    kind = "dense"

    def __init__(self, cfg, key):
        from repro.core.distmat import RowMatrix
        self.cfg = cfg
        self.block = min(cfg["row_block"], cfg["rows"])
        self.shape = (cfg["rows"], cfg["cols"])
        self.data = draw(key, *self.shape, self.block)
        self.program = RowMatrix.create(self.data)
        self._host_gram = None

    def ops(self, prec):
        return ops(prec, self.block)

    def host_gram(self):
        """AᵀA in float64 on the host, formed once, row block by row block."""
        if self._host_gram is None:
            self._host_gram = spectral.host_gram(self.data, self.block)
        return self._host_gram

    def gram_top(self, k):
        return spectral.host_eigh(self.host_gram(), k)[0]

    def gram_times(self, V):
        return self.host_gram() @ np.asarray(V, np.float64)

    def times(self, V, prec):
        mv, _ = self.ops(prec)
        return jax.jit(mv)(self.data, jnp.asarray(V)[:self.shape[1]])

    def svd_at(self, prec, k):
        """The Gram row block by row block at `prec`, its eigenpairs on the
        host, U = A·V·Σ⁻¹ at `prec`."""
        w, V = spectral.host_eigh(self.gram(prec), k)
        V = jnp.asarray(V, jnp.float32)
        s = np.sqrt(w)
        return self.times(V / jnp.asarray(s, jnp.float32), prec), s, V

    def gram(self, prec):
        """AᵀA, one row block at a time."""
        block = self.block

        @jax.jit
        def run(A):
            m, n = A.shape

            def body(i, G):
                blk = jax.lax.dynamic_slice_in_dim(A, i * block, block, 0)
                return G + jnp.dot(blk.T, blk, precision=prec)
            return jax.lax.fori_loop(0, m // block, body,
                                     jnp.zeros((n, n), jnp.float32))
        return run(self.data)

    def lowprec_program(self):
        """The program's own bfloat16 storage of the same matrix."""
        from repro.core.distmat import RowMatrix
        return RowMatrix.create(self.data, store_dtype=jnp.bfloat16)

    def work(self):
        m, n = self.shape
        return {"m": m, "n": n, "stored_bytes": m * n * 4}


def build(cfg, key):
    return Dense(cfg, key)
