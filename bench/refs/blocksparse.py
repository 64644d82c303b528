"""Plain references for a block-sparse matrix of dense bs × bs tiles.

`Tiles` holds the tiles that are there, tile t at block-row `rows[t]`
and block-column `cols[t]` (tiles that share a block add), and multiplies
by them one tile at a time in float64 on the host: no m × n or n × n
array is formed, so AᵀA's top eigenvalues come from ARPACK over AᵀA·X.
`ell_times` and `ell_rtimes` multiply a BlockELL layout (`data` (nbr,
ell, bs, bs), `cols` (nbr, ell)) on the device at a stated matmul
precision, for a reference computed at lower precision."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from refs import spectral


class Tiles:
    def __init__(self, rows, cols, tiles, shape):
        self.rows = np.asarray(rows, np.int64)
        self.cols = np.asarray(cols, np.int64)
        self.tiles = np.asarray(tiles, np.float64)
        self.shape = tuple(shape)
        self.bs = self.tiles.shape[-1]

    def _blocks(self, X, extent):
        """X (extent, s) as (blocks, bs, s), zero-padded to whole blocks."""
        X = np.asarray(X, np.float64)
        nb = -(-extent // self.bs)
        Xp = np.zeros((nb * self.bs, X.shape[1]))
        Xp[:extent] = X[:extent]
        return Xp.reshape(nb, self.bs, X.shape[1])

    def _scatter(self, parts, at, extent):
        nb = -(-extent // self.bs)
        out = np.zeros((nb, self.bs, parts.shape[-1]))
        np.add.at(out, at, parts)
        return out.reshape(nb * self.bs, -1)[:extent]

    def times(self, V):
        """A·V (m, s) in float64 for V (n, s)."""
        m, n = self.shape
        Vb = self._blocks(V, n)[self.cols]
        return self._scatter(np.einsum("tij,tjs->tis", self.tiles, Vb),
                             self.rows, m)

    def rtimes(self, U):
        """Aᵀ·U (n, s) in float64 for U (m, s)."""
        m, n = self.shape
        Ub = self._blocks(U, m)[self.rows]
        return self._scatter(np.einsum("tji,tjs->tis", self.tiles, Ub),
                             self.cols, n)

    def gram_times(self, V):
        """AᵀA·V (n, s) in float64."""
        return self.rtimes(self.times(V))

    def gram_top(self, k):
        """The top-k eigenvalues of AᵀA in float64, largest first."""
        return spectral.operator_top(self.gram_times, self.shape[1], k)


@functools.partial(jax.jit, static_argnames=("m", "prec"))
def ell_times(data, cols, V, *, m, prec):
    """A·V (m, s) from a BlockELL layout at matmul precision `prec`."""
    nbr, _, bs, _ = data.shape
    nbc = -(-V.shape[0] // bs)
    Vb = jnp.pad(V, ((0, nbc * bs - V.shape[0]), (0, 0))).reshape(
        nbc, bs, V.shape[1])[cols]
    out = jnp.einsum("rlij,rljs->ris", data, Vb, precision=prec)
    return out.reshape(nbr * bs, V.shape[1])[:m]


@functools.partial(jax.jit, static_argnames=("n", "prec"))
def ell_rtimes(data, cols, U, *, n, prec):
    """Aᵀ·U (n, s) from a BlockELL layout at matmul precision `prec`."""
    nbr, ell, bs, _ = data.shape
    nbc = -(-n // bs)
    Ub = jnp.pad(U, ((0, nbr * bs - U.shape[0]), (0, 0))).reshape(
        nbr, bs, U.shape[1])
    parts = jnp.einsum("rlji,rjs->rlis", data, Ub, precision=prec)
    out = jax.ops.segment_sum(parts.reshape(nbr * ell, bs, U.shape[1]),
                              cols.reshape(-1), num_segments=nbc)
    return out.reshape(nbc * bs, U.shape[1])[:n]
