"""throwaway-blocksparse: a SparseRowMatrix of dense bs × bs tiles, made as
BlockELL arrays (`data`, `cols`) on the device from the seed.

`tile_rows` block-rows hold `tiles_per_row` tiles each, in distinct
block-columns.  The tiles' columns and values are the same for every
seed; the seed only chooses which block-rows hold them, so AᵀA, and with
it the work of an SVD, is the same on every seed.  The references never
form an n × n array: float64 products tile by tile, and ARPACK over
AᵀA·X for σ (`refs/blocksparse.py`)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from refs import blocksparse, spectral

TINY = {"rows": 8192, "cols": 1024, "tile_rows": 24}


def draw(key, nbr, nbc, used, ell, bs):
    """(held block-rows, data (nbr, ell, bs, bs), cols (nbr, ell))."""
    @jax.jit
    def run(key):
        kc, kv = jax.random.split(jax.random.PRNGKey(0))
        held = jax.random.permutation(key, nbr)[:used]
        cols = jax.vmap(lambda k: jax.random.permutation(k, nbc)[:ell])(
            jax.random.split(kc, used)).astype(jnp.int32)
        tiles = jax.random.normal(kv, (used, ell, bs, bs), jnp.float32)
        data = jnp.zeros((nbr, ell, bs, bs), jnp.float32).at[held].set(tiles)
        return held, data, jnp.zeros((nbr, ell), jnp.int32).at[held].set(cols)
    return run(key)


class BlockSparse:
    kind = "blocksparse"

    def __init__(self, cfg, key):
        from repro.core.distmat import SparseRowMatrix, types
        bs, ell = cfg["block_size"], cfg["tiles_per_row"]
        used = cfg["tile_rows"]
        m, n = cfg["rows"], cfg["cols"]
        self.shape = (m, n)
        self.held, self.data, self.cols = draw(key, -(-m // bs), -(-n // bs),
                                               used, ell, bs)
        mesh = types.single_device_mesh()
        self.program = SparseRowMatrix(
            self.data, self.cols, dims=self.shape, nnz=used * ell * bs * bs,
            mesh=mesh, row_axes=types.row_axes_for(mesh))
        self._tiles = None

    def tiles(self):
        """The tiles that are there, in float64 on the host."""
        if self._tiles is None:
            held = np.asarray(self.held)
            data = np.asarray(self.data[self.held])
            cols = np.asarray(self.cols[self.held])
            self._tiles = blocksparse.Tiles(
                np.repeat(held, cols.shape[1]), cols.reshape(-1),
                data.reshape((-1,) + data.shape[2:]), self.shape)
        return self._tiles

    def gram_top(self, k):
        return self.tiles().gram_top(k)

    def gram_times(self, V):
        return self.tiles().gram_times(V)

    def times(self, V, prec):
        m, n = self.shape
        return blocksparse.ell_times(self.data, self.cols,
                                     jnp.asarray(V, jnp.float32)[:n],
                                     m=m, prec=prec)

    def svd_at(self, prec, k):
        """AᵀA's top eigenpairs by ARPACK over products at `prec`,
        U = A·V·Σ⁻¹ at `prec`."""
        n = self.shape[1]

        def apply(X):
            AX = self.times(X, prec)
            return np.asarray(blocksparse.ell_rtimes(
                self.data, self.cols, AX, n=n, prec=prec), np.float64)
        w, V = spectral.operator_top(apply, n, k, vectors=True)
        V = jnp.asarray(V, jnp.float32)
        s = np.sqrt(w)
        return self.times(V / jnp.asarray(s, jnp.float32), prec), s, V

    def lowprec_program(self):
        """The program's own bfloat16 storage of the same tiles."""
        return self.program.astype_store(jnp.bfloat16)

    def work(self):
        m, n = self.shape
        return {"m": m, "n": n,
                "tiles": int(self.held.shape[0]) * self.cols.shape[1]}


def build(cfg, key):
    return BlockSparse(cfg, key)
