"""FISTA for the Figure 1 family, and the optimality measure of a
solution.

FISTA is copied in substance from the repository's chip smoke test, so
that later changes to the program cannot move the yardstick: step 1/L,
stopped like the program's solvers (relative step ‖Δx‖ / max(1, ‖x‖)
below `tol`).  It runs on several right-hand sides at once, each column
with its own target, weight and stopping flag; a column that has stopped
keeps its iterate.  With `restart` it restarts its momentum whenever the
step turns against it (O'Donoghue and Candès), which carries it on to
the optimum on these strongly convex problems instead of circling it."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def loss_grad(kind, z, b):
    """Per-row loss and its derivative in z."""
    if kind == "logistic":
        return jnp.logaddexp(0.0, -b * z), -b * jax.nn.sigmoid(-b * z)
    r = z - b
    return 0.5 * r * r, r


def prox(kind, x, t, lam):
    if kind == "l1":
        return jnp.sign(x) * jnp.maximum(jnp.abs(x) - t * lam, 0.0)
    if kind == "l2":
        return x / (1.0 + t * lam)
    return x


def reg_value(kind, x, lam):
    """Per-column regularizer value, x of shape (n, s)."""
    if kind == "l1":
        return lam * jnp.sum(jnp.abs(x), axis=0)
    if kind == "l2":
        return 0.5 * lam * jnp.sum(x * x, axis=0)
    return jnp.zeros(x.shape[1:], x.dtype)


def fista(mv, rmv, data, B, loss, reg, lam, L, *, n, tol, max_iters,
          restart=False):
    """Solve min f(A x_j; b_j) + h(x_j) for every column j of B (m, s).

    `lam` and `L` are (s,) arrays; `mv(data, X)` and `rmv(data, U)` are the
    matrix's operators on (n, s) and (m, s) blocks.  Returns X (n, s) and
    the iteration count of each column."""
    @jax.jit
    def run(data, B, lam, L):
        s = B.shape[1]

        def body(st):
            k, X, Y, t, done, iters = st
            _, gz = loss_grad(loss, mv(data, Y), B)
            X1 = prox(reg, Y - rmv(data, gz) / L, 1.0 / L, lam)
            t1 = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
            Y1 = X1 + ((t - 1.0) / t1) * (X1 - X)
            if restart:
                back = jnp.sum((Y - X1) * (X1 - X), axis=0) > 0
                t1 = jnp.where(back, 1.0, t1)
                Y1 = jnp.where(back[None, :], X1, Y1)
            rel = jnp.linalg.norm(X1 - X, axis=0) / jnp.maximum(
                1.0, jnp.linalg.norm(X1, axis=0))
            keep = done[None, :]
            X1 = jnp.where(keep, X, X1)
            Y1 = jnp.where(keep, Y, Y1)
            iters = jnp.where(done, iters, k + 1)
            return k + 1, X1, Y1, t1, done | (rel < tol), iters

        X0 = jnp.zeros((n, s), jnp.float32)
        init = (0, X0, X0, jnp.ones((s,), jnp.float32),
                jnp.zeros((s,), bool), jnp.zeros((s,), jnp.int32))
        _, X, _, _, _, iters = jax.lax.while_loop(
            lambda st: (~jnp.all(st[4])) & (st[0] < max_iters), body, init)
        return X, iters
    X, iters = run(data, B, jnp.asarray(lam, jnp.float32),
                   jnp.asarray(L, jnp.float32))
    return X, np.asarray(iters)


def objective(mv, data, B, loss, reg, lam, X, *, blocks=64):
    """f(A x_j; b_j) + h(x_j) per column, in float64 on the host: the
    loss is summed per row block on the device and the block sums are
    added in float64, so the reference's own rounding stays far below
    what the program's float32 accumulation shows."""
    @jax.jit
    def parts(data, B, X):
        ell, _ = loss_grad(loss, mv(data, X), B)
        m = ell.shape[0]
        return ell.reshape(blocks, m // blocks, -1).sum(axis=1), \
            reg_value(reg, X, jnp.ones((X.shape[1],), jnp.float32))
    f_parts, h_unit = parts(data, B, X)
    f = np.asarray(f_parts, np.float64).sum(axis=0)
    return f + np.asarray(lam, np.float64) * np.asarray(h_unit, np.float64)


def prox_step(mv, rmv, data, B, loss, reg, lam, L, X):
    """The relative step ‖x − prox(x − ∇f(x)/L)‖ / max(1, ‖x‖) that one
    proximal gradient iteration would take from each column x of X: the
    quantity the solvers' stopping rule bounds, taken at x itself.  It is
    0 exactly at the optimum, whatever method found it."""
    @jax.jit
    def run(data, B, lam, L, X):
        _, gz = loss_grad(loss, mv(data, X), B)
        X1 = prox(reg, X - rmv(data, gz) / L, 1.0 / L, lam)
        return jnp.linalg.norm(X1 - X, axis=0) / jnp.maximum(
            1.0, jnp.linalg.norm(X, axis=0))
    return np.asarray(run(data, B, jnp.asarray(lam, jnp.float32),
                          jnp.asarray(L, jnp.float32), X), np.float64)


def lipschitz(loss, sq):
    """The smooth part's gradient Lipschitz bound, with 5% headroom over
    the estimate `sq` of ‖A‖₂²."""
    return 1.05 * sq * (0.25 if loss == "logistic" else 1.0)
