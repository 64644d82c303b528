"""Paper §3.1 / Table 1 — distributed SVD at Netflix-prize-like aspect
ratios (scaled to this machine), via all three code paths (Gram,
matrix-free Lanczos, and the randomized range finder).

    PYTHONPATH=src python examples/svd_distributed.py

On TPU the per-shard hotspots (Gram reduction, randomized-SVD projection,
U recovery) run through the Pallas kernels with `tune="auto"` block sizes:
the shape-aware autotuner (repro.kernels.autotune) picks tiles per
(backend, dtype, shape-bucket) from its persistent JSON cache
($REPRO_AUTOTUNE_CACHE or ~/.cache/repro/autotune.json, with shipped v5e
defaults), falling back to roofline cost-model ranking.  Re-sweep on new
hardware with `python -m benchmarks.bench_autotune`.
"""
import time

import jax.numpy as jnp
import numpy as np

from repro.core.distmat import CoordinateMatrix, RowMatrix
from repro.core.linalg import compute_svd
from repro import compile_cache

compile_cache.enable()

rng = np.random.default_rng(0)

# Netflix-shaped (17770 × 480189 in the paper; transpose-scaled here):
# tall-skinny path — Gram on the "driver", U recovered in parallel.
A = rng.normal(size=(50_000, 128)).astype(np.float32)
t0 = time.time()
res = compute_svd(RowMatrix.create(A), k=5)
print(f"tall-skinny ({A.shape}): mode={res.info['mode']} "
      f"σ={np.round(np.asarray(res.s), 2)}  [{time.time()-t0:.2f}s]")

# square sparse path — ARPACK-analogue Lanczos, matrix-free matvecs.
m = n = 5000
nnz = 100_000
ri, ci = rng.integers(0, m, nnz), rng.integers(0, n, nnz)
va = rng.normal(size=nnz).astype(np.float32)
cm = CoordinateMatrix.create(jnp.asarray(ri), jnp.asarray(ci),
                             jnp.asarray(va), (m, n))
t0 = time.time()
res = compute_svd(cm, k=5, mode="lanczos", tol=1e-4)
print(f"square sparse ({m}x{n}, nnz={nnz}): "
      f"σ={np.round(np.asarray(res.s), 3)} "
      f"restarts={int(res.info['restarts'])}  [{time.time()-t0:.2f}s]")

# moderately-rectangular dense path — randomized range finder: too wide for
# a comfortable driver-side Gram, dense enough that Lanczos pays one full
# pass over A per extracted direction; the sketch needs 2+2q passes total.
W = rng.normal(size=(30_000, 2048)).astype(np.float32)
W[:, :16] *= np.linspace(40.0, 8.0, 16)[None, :]     # plant a signal
t0 = time.time()
res = compute_svd(RowMatrix.create(W), k=8, mode="randomized")
print(f"wide dense ({W.shape}): mode={res.info['mode']} "
      f"passes={res.info['passes_over_A']} "
      f"tail_ratio={float(res.info['tail_ratio']):.3f} "
      f"σ={np.round(np.asarray(res.s), 2)}  [{time.time()-t0:.2f}s]")
