"""Paper §3.2.3 — smoothed linear programming via the SCD formulation.

    PYTHONPATH=src python examples/lp_solver.py
"""
import jax.numpy as jnp
import numpy as np

from repro.core.tfocs import solve_smoothed_lp, TfocsOptions
from repro import compile_cache

compile_cache.enable()

rng = np.random.default_rng(3)
m, n = 20, 60

# LP with a known optimum (constructed via strict complementarity)
xstar = np.zeros(n, np.float32)
xstar[:m // 2] = rng.random(m // 2) + 0.5
A = rng.normal(size=(m, n)).astype(np.float32)
b = A @ xstar
y = rng.normal(size=m).astype(np.float32)
s = np.zeros(n, np.float32)
s[m // 2:] = rng.random(n - m // 2) + 0.1
c = A.T @ y + s


class Op:
    in_shape = (n,)
    out_shape = (m,)
    apply = staticmethod(lambda x: jnp.asarray(A) @ x)
    adjoint = staticmethod(lambda lam: jnp.asarray(A).T @ lam)


x, lam, info = solve_smoothed_lp(
    jnp.asarray(c), Op, jnp.asarray(b), mu=1e-2, continuations=6,
    opts=TfocsOptions(max_iters=600, backtracking=True, restart=True))

kkt = {k: float(v) for k, v in info["kkt"].items()}
print("KKT residuals:", kkt)
print("objective (solver):", kkt["objective"])
print("objective (known) :", float(c @ xstar))
print("max |x - x*|:", float(np.abs(np.asarray(x) - xstar).max()))
