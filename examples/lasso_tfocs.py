"""Paper §3.2.2 — LASSO with the three-part composite objective, showing
the explicit (linear, smooth, nonsmooth) decomposition and the solver
variants from Figure 1.

    PYTHONPATH=src python examples/lasso_tfocs.py
"""
import jax.numpy as jnp
import numpy as np

from repro.core.distmat import RowMatrix
from repro.core.tfocs import (LinopMatrix, SmoothQuad, ProxL1, tfocs,
                              TfocsOptions)
from repro import compile_cache

compile_cache.enable()

rng = np.random.default_rng(1)
m, n = 2000, 256
A = rng.normal(size=(m, n)).astype(np.float32)
xt = np.zeros(n, np.float32)
xt[:10] = rng.normal(size=10) * 2
b = (A @ xt + 0.05 * rng.normal(size=m)).astype(np.float32)
lam = 1.0

rm = RowMatrix.create(A)
linop = LinopMatrix(rm)                       # the expensive, distributed part
smooth = SmoothQuad(b=linop.pad_data(jnp.asarray(b)),
                    weights=linop.row_weights())
prox = ProxL1(lam)                            # driver-local vector math

for name, opts in {
    "gra":    TfocsOptions(max_iters=300, accel=False, backtracking=False,
                           Lexact=float(np.linalg.norm(A, 2) ** 2)),
    "acc":    TfocsOptions(max_iters=300, backtracking=False,
                           Lexact=float(np.linalg.norm(A, 2) ** 2)),
    "acc_rb": TfocsOptions(max_iters=300, backtracking=True, restart=True),
}.items():
    x, info = tfocs(smooth, linop, prox, jnp.zeros(n), opts)
    f = 0.5 * np.linalg.norm(A @ np.asarray(x) - b) ** 2 \
        + lam * np.abs(np.asarray(x)).sum()
    print(f"{name:7s} f={f:10.4f} iters={int(info['iterations']):4d} "
          f"backtracks={int(info['n_backtracks']):3d} "
          f"restarts={int(info['n_restarts'])}")
