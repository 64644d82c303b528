"""The TFOCS first-order engine (paper §3.2): Auslender–Teboulle accelerated
proximal gradient with backtracking Lipschitz estimation, gradient-test
restart, and linear-operator structure caching.

Composite problem:  minimize  f(A x) + h(x)
  * `linop`  (A)  — distributed matrix ops (cluster)
  * `smooth` (f)  — evaluated in data space
  * `prox`   (h)  — vector math on the replicated variable (driver)

The linear-operator caching is the paper's "the optimizer may evaluate the
(expensive) linear component and cache the result": the iterates x̄, z carry
their images A x̄, A z, so  A y = (1−θ)A x̄ + θA z  costs no matvec, and each
iteration performs exactly ONE apply and ONE adjoint (per backtracking
attempt) — the minimum possible *for the cached accelerated scheme*.

For non-accelerated runs over a row-separable smooth there is a faster
floor: with θ ≡ 1 the gradient point of the next attempt IS the candidate
point of this one, so the single-pass fused gradient kernel
(kernels/fusedgrad) — which computes f(Ax), Aᵀ∇f(Ax) and Ax in one
streaming read of A — covers the whole attempt: ONE A-pass instead of an
apply + an adjoint.  `fused="auto"` (TfocsOptions) takes that path when the
smooth advertises separability, the operator supports it, and the execution
planner (launch/planner.plan("grad", ...)) prices it ahead.  `fused=False`
opts out.

Accelerated runs over a *quadratic* row-separable smooth get the same
one-pass floor by a different trick (`_tfocs_fused_accel`): ∇f(z) = w∘(z−b)
is affine, so the x-space gradient decomposes as Aᵀ∇f(A y) = u_y − u_b with
u_v ≔ Aᵀ(w∘A v) — and u_y = (1−θ)u_x + θu_z combines from carried vectors
exactly like the cached images.  Each attempt then needs only ONE fused
pass (at the candidate z⁺, which refreshes u_z); the momentum point's
gradient is free.  acc/acc_b/acc_r/acc_rb drop from two A-passes per
attempt to one.  Non-quadratic accelerated variants keep the cached
two-pass scheme (their data-space gradient is not affine in the image).

One engine serves the whole Figure-1 family:
  accel=False                         → `gra`   (proximal gradient)
  accel=True                          → `acc`
  accel=True,  restart=True           → `acc_r`
  accel=True,  backtracking=True      → `acc_b`
  accel=True,  both                   → `acc_rb`
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.launch import telemetry as _telemetry

from .smooth import row_separable

Array = jax.Array


# Relative objective change below which the backtracking test stops
# comparing f32 objective values (their difference is then rounding noise)
# and tests the local Lipschitz estimate 2⟨∇f(x⁺) − ∇f(y), x⁺ − y⟩ /
# ‖x⁺ − y‖² ≤ L instead — TFOCS's own switch (its `backtrack_tol`), at f32
# resolution.
BACKTRACK_TOL = 1e-5


def _backtrack_ok(f_new, f_y, rhs, curvature, L, xy_sq):
    """TFOCS's sufficient-decrease test: f(x⁺) ≤ rhs while the objective
    values differ by more than rounding, the gradient-based curvature
    ⟨∇f(x⁺) − ∇f(y), x⁺ − y⟩ ≤ L/2‖x⁺ − y‖² once they do not."""
    simple = jnp.abs(f_y - f_new) >= BACKTRACK_TOL * jnp.maximum(
        jnp.abs(f_new), jnp.abs(f_y))
    return jnp.where(simple, f_new <= rhs, 2.0 * curvature <= L * xy_sq)


@dataclass(frozen=True)
class TfocsOptions:
    max_iters: int = 500
    tol: float = 1e-8
    L0: float = 1.0              # initial Lipschitz estimate
    Lexact: float | None = None  # if set: no backtracking, fixed step 1/L
    alpha: float = 2.0           # backtracking increase factor
    beta: float = 0.9            # per-iteration optimistic L decay
    max_backtracks: int = 30
    accel: bool = True
    backtracking: bool = True
    restart: bool = False        # O'Donoghue–Candès gradient-test restart
    fused: bool | str = "auto"   # single-pass fused gradient (False opts out)
    # Compute/wire precision: "auto" lets the execution planner's precision
    # sweep (launch/planner, plan("grad", context={"tol": ...})) pick among
    #   "f32"   — exact storage and wire (always admissible),
    #   "bf16"  — recast the operand's storage to bfloat16 (kernels upcast
    #             tiles on-chip and accumulate f32); admitted when
    #             tol ≥ 1e-5 and the modeled byte savings clear the floor,
    #   "psum8" — compressed int8 gradient all-reduce with error feedback
    #             (train/compression.psum_int8); admitted when tol ≥ 1e-6.
    # The guard is opts.tol: the planner never picks a precision whose
    # error guard exceeds the solver's own convergence tolerance.  "psum8"
    # applies only to the θ ≡ 1 fused engine (the EF residual needs the
    # candidate/gradient-point identity); other engines fall back to f32
    # wire.  Explicit values force the choice.
    precision: str = "auto"


def _fused_capable(linop) -> bool:
    """True when the operator — and, for delegating wrappers like
    CountingLinop (whose methods exist unconditionally and just forward to
    `.base`), the whole wrapped chain — implements fused_grad."""
    if not hasattr(linop, "fused_grad"):
        return False
    base = getattr(linop, "base", None)
    return True if base is None else _fused_capable(base)


def fused_gradient_enabled(smooth, linop, fused: bool | str = "auto",
                           *, needs_theta_one: bool = False,
                           accel: bool = False) -> bool:
    """Whether a (smooth, linop) composite should take the single-pass fused
    gradient path.  Structure gates first (row-separable smooth, a
    fused-capable operator, and — with needs_theta_one — no acceleration,
    since the θ ≡ 1 engine's candidate/gradient-point identity breaks under
    momentum; accelerated quadratic composites get their own affine fused
    engine, see `_tfocs_fused_accel`); `"auto"` then consults the execution
    planner (launch/planner.plan("grad", ...): one A read vs two, priced on
    the calibrated machine model)."""
    if fused is False or (needs_theta_one and accel):
        return False
    sep = row_separable(smooth)
    ok = sep is not None and _fused_capable(linop)
    if fused is True:
        if not ok:
            raise ValueError("fused=True needs a row-separable smooth and a "
                             "fused-capable linop (LinopMatrix)")
        return True
    if fused != "auto":
        raise ValueError(f"fused must be True, False or 'auto', got {fused!r}")
    if not ok:
        return False
    try:
        m, n = int(linop.out_shape[0]), int(linop.in_shape[0])
        dtype = linop.operand_dtype() if hasattr(linop, "operand_dtype") \
            else jnp.float32
        # The roofline compares per-shard streaming passes, so price the
        # shard, not the global row count (lane-padding waste is per shard).
        shards = linop.row_shards() if hasattr(linop, "row_shards") else 1
        layout = linop.block_layout() if hasattr(linop, "block_layout") \
            else None
    except (AttributeError, TypeError):
        return True
    from repro.launch import planner as _planner
    dims = {"m": max(m // max(shards, 1), 1), "n": n}
    if layout is None:
        return _planner.plan("grad", dims, dtype).choice == "fused"
    # A block-sparse operand: the BSR fused kernel against its two passes.
    bs, ell = layout
    return _planner.plan("grad", {**dims, "bs": bs, "ell": ell}, dtype,
                         context={"kind": "sparse"}).choice == "fused"


_PRECISIONS = ("auto", "f32", "bf16", "psum8")


def resolve_precision(linop, opts: TfocsOptions) -> str:
    """The solver's precision choice: "auto" runs the planner's precision
    sweep — plan("grad", per-shard dims, context={"tol": opts.tol, axes})
    prices {f32, bf16 storage, int8-compressed psum} against the roofline
    and admits a candidate only when its error guard clears opts.tol AND
    the modeled byte savings clear the planner's floor.  Explicit "f32"/
    "bf16"/"psum8" force the choice; non-f32 operands (already recast) and
    non-matrix operators resolve to "f32"."""
    if opts.precision != "auto":
        if opts.precision not in _PRECISIONS:
            raise ValueError(f"precision must be one of {_PRECISIONS}, "
                             f"got {opts.precision!r}")
        return opts.precision
    if not (_fused_capable(linop) and hasattr(linop, "operand_dtype")):
        return "f32"
    try:
        if jnp.dtype(linop.operand_dtype()) != jnp.float32:
            return "f32"
        m, n = int(linop.out_shape[0]), int(linop.in_shape[0])
        shards = linop.row_shards() if hasattr(linop, "row_shards") else 1
    except (AttributeError, TypeError):
        return "f32"
    ctx = {"tol": float(opts.tol)}
    A = getattr(linop, "A", None)
    if hasattr(A, "mesh") and hasattr(A, "row_axes"):
        from repro.launch import mesh as _mesh
        ctx["axes"] = _mesh.axis_sizes(A.mesh, A.row_axes)
    from repro.launch import planner as _planner
    p = _planner.plan("grad", {"m": max(m // max(shards, 1), 1), "n": n},
                      "float32", context=ctx)
    return p.precision or "f32"


class TfocsState(NamedTuple):
    x: Array
    Ax: Array
    z: Array
    Az: Array
    theta: Array
    L: Array
    k: Array
    hist: Array                  # objective per outer iteration
    done: Array
    n_backtracks: Array
    n_restarts: Array


class _Attempt(NamedTuple):
    L: Array
    theta: Array
    x: Array
    Ax: Array
    z: Array
    Az: Array
    fy: Array
    gy: Array                    # data-space gradient at y
    Ay: Array
    ok: Array
    tries: Array


class _FusedState(NamedTuple):
    # No image cache: the backtracking test collapses to x-space and the
    # kernel returns A x⁺ fresh each attempt, so (unlike TfocsState) no
    # (m,)-vector rides the loop carry.
    x: Array
    f: Array                     # smooth value at x (carried, no recompute)
    g: Array                     # x-space gradient at x (carried)
    L: Array
    k: Array
    hist: Array
    done: Array
    n_backtracks: Array
    # Compressed-psum error-feedback residual (None → exact f32 wire).
    # None is an empty pytree node, so the while_loop carry stays legal
    # either way.
    res: object = None


class _FusedAttempt(NamedTuple):
    L: Array
    x: Array
    f: Array
    g: Array
    ok: Array
    tries: Array
    res: object = None


def _tfocs_fused(smooth, linop, prox, x0: Array, opts: TfocsOptions,
                 sep, residual=None) -> tuple[Array, dict]:
    """Non-accelerated engine over the fused single-pass gradient.

    With θ ≡ 1 the candidate point x⁺ = prox(x − g/L) is also the next
    gradient point, so `linop.fused_grad(x⁺)` — one streaming pass over A —
    yields everything an attempt needs: f(Ax⁺) for the backtracking test
    (⟨∇f(Ay), A x⁺ − A y⟩ collapses to the x-space ⟨g, x⁺ − x⟩), the next
    gradient, and the image A x⁺.  Exactly ONE A-pass per backtracking
    attempt, against apply + adjoint = two on the unfused path; the math is
    identical, so the iterates match the unfused engine to float tolerance.

    `residual` (the planner's "psum8" pick; see linop.init_psum_residual)
    threads the compressed-wire error-feedback state through the loop:
    every fused pass ships an int8 gradient payload and returns the
    updated residual.  A failed backtracking attempt recomputes from the
    pre-step residual, so no quantization error is double-counted.
    """
    backtracking = opts.backtracking and opts.Lexact is None
    L_init = jnp.asarray(opts.Lexact if opts.Lexact is not None else opts.L0,
                         jnp.float32)
    use8 = residual is not None

    def fg(x, res):
        """One fused A-pass; compressed wire iff an EF residual rides."""
        if use8:
            f, g, _, nres = linop.fused_grad(x, sep, residual=res)
            return f, g, nres
        f, g, _ = linop.fused_grad(x, sep)
        return f, g, res

    def attempt_once(a: _FusedAttempt, state: _FusedState) -> _FusedAttempt:
        step = 1.0 / a.L
        x_new = prox.prox(state.x - step * state.g, step)
        f_new, g_new, res_new = fg(x_new, state.res)         # ← ONE A-pass
        dx = x_new - state.x
        xy_sq = jnp.vdot(dx, dx)
        rhs = state.f + jnp.vdot(state.g, dx) + 0.5 * a.L * xy_sq
        ok = _backtrack_ok(f_new, state.f, rhs, jnp.vdot(g_new - state.g, dx),
                           a.L, xy_sq)
        return a._replace(x=x_new, f=f_new, g=g_new, ok=ok,
                          tries=a.tries + 1, res=res_new)

    def outer(state: _FusedState) -> _FusedState:
        L0k = state.L * (opts.beta if backtracking else 1.0)
        init = _FusedAttempt(L=L0k, x=state.x, f=state.f,
                             g=state.g, ok=jnp.asarray(False),
                             tries=jnp.int32(0), res=state.res)
        first = attempt_once(init, state)

        if backtracking:
            def bt_cond(a: _FusedAttempt):
                return (~a.ok) & (a.tries < opts.max_backtracks)

            def bt_body(a: _FusedAttempt):
                return attempt_once(a._replace(L=a.L * opts.alpha), state)

            acc = jax.lax.while_loop(bt_cond, bt_body, first)
        else:
            acc = first

        obj = acc.f + prox.value(acc.x)
        hist = state.hist.at[state.k].set(obj)
        dx = acc.x - state.x
        rel = jnp.linalg.norm(dx) / jnp.maximum(1.0, jnp.linalg.norm(acc.x))
        return _FusedState(
            x=acc.x, f=acc.f, g=acc.g, L=acc.L,
            k=state.k + 1, hist=hist, done=rel < opts.tol,
            n_backtracks=state.n_backtracks + acc.tries - 1, res=acc.res)

    def cond(state: _FusedState):
        return (~state.done) & (state.k < opts.max_iters)

    f0, g0, res0 = fg(x0, residual)                  # ← ONE A-pass to seed
    init = _FusedState(
        x=x0, f=f0, g=g0, L=L_init, k=jnp.int32(0),
        hist=jnp.full((opts.max_iters,), jnp.nan, jnp.float32),
        done=jnp.asarray(False), n_backtracks=jnp.int32(0), res=res0)
    final = jax.lax.while_loop(cond, outer, init)
    # Standardized info keys (iterations / a_passes / converged / plan) plus
    # solver-specific detail; "fused" is a deprecated alias of plan=="fused"
    # kept for one release.  a_passes: seed + one per attempt (iteration +
    # extra backtracks), each exactly one streaming read of A.
    info = {"iterations": final.k,
            "a_passes": 1 + final.k + final.n_backtracks,
            "converged": final.done, "plan": "fused",
            "history": final.hist,
            "n_backtracks": final.n_backtracks,
            "n_restarts": jnp.int32(0), "fused": True,
            "objective": final.hist[jnp.maximum(final.k - 1, 0)]}
    return final.x, info


class _AccFusedState(NamedTuple):
    # The cached-image carries of TfocsState plus the x-space u-vectors
    # u_v = Aᵀ(w∘A v) that make the quadratic gradient affine.
    x: Array
    Ax: Array
    ux: Array
    z: Array
    Az: Array
    uz: Array
    theta: Array
    L: Array
    k: Array
    hist: Array
    done: Array
    n_backtracks: Array
    n_restarts: Array


class _AccFusedAttempt(NamedTuple):
    L: Array
    theta: Array
    x: Array
    Ax: Array
    ux: Array
    z: Array
    Az: Array
    uz: Array
    gy: Array                    # data-space gradient at y (restart test)
    ok: Array
    tries: Array


def _tfocs_fused_accel(smooth, linop, prox, x0: Array, opts: TfocsOptions,
                       sep) -> tuple[Array, dict]:
    """Accelerated engine over the fused single-pass gradient — quadratic
    row-separable smooths only.

    With f(z) = Σ wᵢ·½(zᵢ−bᵢ)² the x-space gradient at any point v is
    Aᵀ∇f(A v) = u_v − u_b where u_v = Aᵀ(w∘A v): *affine* in u.  The
    iterates x̄, z therefore carry u_x, u_z alongside their cached images,
    and the momentum point's gradient  u_y − u_b = (1−θ)u_x + θu_z − u_b
    costs nothing.  One `linop.fused_grad(z⁺)` per attempt refreshes
    (f(Az⁺), u_z⁺ − u_b, Az⁺) in a single streaming read of A; x̄⁺ updates
    affinely.  The math reproduces the cached engine's iterates to float
    tolerance at HALF the passes: a_passes = 2 (seed: u_b then x0) +
    iterations + extra backtracks."""
    backtracking = opts.backtracking and opts.Lexact is None
    L_init = jnp.asarray(opts.Lexact if opts.Lexact is not None else opts.L0,
                         jnp.float32)

    # Seed: u_b from a fused pass at 0 (g(0) = Aᵀ(w∘(0−b)) = −u_b), then
    # the starting iterate's image and u_x.  Two passes, done once.
    _, g_zero, _ = linop.fused_grad(jnp.zeros_like(x0), sep)
    ub = -g_zero
    _, gx0, Ax0 = linop.fused_grad(x0, sep)
    ux0 = gx0 + ub

    def theta_next(theta, L_ratio):
        return 2.0 / (1.0 + jnp.sqrt(1.0 + 4.0 * L_ratio / (theta * theta)))

    def attempt_once(a: _AccFusedAttempt,
                     state: _AccFusedState) -> _AccFusedAttempt:
        Ay = (1 - a.theta) * state.Ax + a.theta * state.Az
        fy = smooth.value(Ay)
        gy = smooth.grad(Ay)                        # data-space, no A pass
        g = (1 - a.theta) * state.ux + a.theta * state.uz - ub  # affine!
        step = 1.0 / (a.L * a.theta)
        z_new = prox.prox(state.z - step * g, step)
        _, gz, Az_new = linop.fused_grad(z_new, sep)  # ← the ONE A-pass
        uz_new = gz + ub
        x_new = (1 - a.theta) * state.x + a.theta * z_new
        Ax_new = (1 - a.theta) * state.Ax + a.theta * Az_new
        ux_new = (1 - a.theta) * state.ux + a.theta * uz_new
        f_new = smooth.value(Ax_new)
        dx = a.theta * (z_new - state.z)            # = x_new − y
        rhs = fy + jnp.vdot(gy, Ax_new - Ay) + 0.5 * a.L * jnp.vdot(dx, dx)
        ok = f_new <= rhs + 1e-12 * jnp.abs(fy)
        return a._replace(x=x_new, Ax=Ax_new, ux=ux_new, z=z_new,
                          Az=Az_new, uz=uz_new, gy=gy, ok=ok,
                          tries=a.tries + 1)

    def outer(state: _AccFusedState) -> _AccFusedState:
        L0k = state.L * (opts.beta if backtracking else 1.0)
        theta0 = theta_next(state.theta, L0k / state.L)
        init = _AccFusedAttempt(
            L=L0k, theta=theta0, x=state.x, Ax=state.Ax, ux=state.ux,
            z=state.z, Az=state.Az, uz=state.uz,
            gy=jnp.zeros_like(state.Ax), ok=jnp.asarray(False),
            tries=jnp.int32(0))
        first = attempt_once(init, state)

        if backtracking:
            def bt_cond(a: _AccFusedAttempt):
                return (~a.ok) & (a.tries < opts.max_backtracks)

            def bt_body(a: _AccFusedAttempt):
                L_new = a.L * opts.alpha
                theta_new = theta_next(state.theta, L_new / state.L)
                return attempt_once(a._replace(L=L_new, theta=theta_new),
                                    state)

            acc = jax.lax.while_loop(bt_cond, bt_body, first)
        else:
            acc = first

        # Gradient-test restart (O'Donoghue–Candès), exactly the cached
        # engine's test; resetting momentum also resets u_z to u_x.
        if opts.restart:
            uphill = jnp.vdot(acc.gy, acc.Ax - state.Ax) > 0
            theta_out = jnp.where(uphill, 1.0, acc.theta)
            z_out = jnp.where(uphill, acc.x, acc.z)
            Az_out = jnp.where(uphill, acc.Ax, acc.Az)
            uz_out = jnp.where(uphill, acc.ux, acc.uz)
            n_restarts = state.n_restarts + uphill.astype(jnp.int32)
        else:
            theta_out, z_out, Az_out, uz_out = (acc.theta, acc.z, acc.Az,
                                                acc.uz)
            n_restarts = state.n_restarts

        obj = smooth.value(acc.Ax) + prox.value(acc.x)
        hist = state.hist.at[state.k].set(obj)
        dx = acc.x - state.x
        rel = jnp.linalg.norm(dx) / jnp.maximum(1.0, jnp.linalg.norm(acc.x))
        return _AccFusedState(
            x=acc.x, Ax=acc.Ax, ux=acc.ux, z=z_out, Az=Az_out, uz=uz_out,
            theta=theta_out, L=acc.L, k=state.k + 1, hist=hist,
            done=rel < opts.tol,
            n_backtracks=state.n_backtracks + acc.tries - 1,
            n_restarts=n_restarts)

    def cond(state: _AccFusedState):
        return (~state.done) & (state.k < opts.max_iters)

    init = _AccFusedState(
        x=x0, Ax=Ax0, ux=ux0, z=x0, Az=Ax0, uz=ux0,
        theta=jnp.asarray(1.0, jnp.float32), L=L_init, k=jnp.int32(0),
        hist=jnp.full((opts.max_iters,), jnp.nan, jnp.float32),
        done=jnp.asarray(False),
        n_backtracks=jnp.int32(0), n_restarts=jnp.int32(0))
    final = jax.lax.while_loop(cond, outer, init)
    info = {"iterations": final.k,
            "a_passes": 2 + final.k + final.n_backtracks,
            "converged": final.done, "plan": "fused_affine",
            "history": final.hist,
            "n_backtracks": final.n_backtracks,
            "n_restarts": final.n_restarts, "fused": True,
            "objective": final.hist[jnp.maximum(final.k - 1, 0)]}
    return final.x, info


def tfocs(smooth, linop, prox, x0: Array,
          opts: TfocsOptions = TfocsOptions()) -> tuple[Array, dict]:
    """Run the solver; returns (x*, info dict with per-iteration history).
    info["precision"] reports the resolved compute/wire precision (see
    TfocsOptions.precision).  The call into the chosen engine is a
    ``solve.loop`` span: the eager seed pass, building and dispatching the
    while_loop; it closes before the device finishes."""
    prec = resolve_precision(linop, opts)
    if prec == "bf16":
        if hasattr(linop, "astype_store"):
            linop = linop.astype_store(jnp.bfloat16)
        else:
            prec = "f32"
    if fused_gradient_enabled(smooth, linop, opts.fused,
                              needs_theta_one=True, accel=opts.accel):
        residual = None
        if prec == "psum8":
            residual = linop.init_psum_residual() \
                if hasattr(linop, "init_psum_residual") else None
            if residual is None:
                prec = "f32"     # local operand: no wire to compress
        with _telemetry.current().span("solve.loop"):
            x, info = _tfocs_fused(smooth, linop, prox, x0, opts,
                                   row_separable(smooth), residual=residual)
        info["precision"] = prec
        return x, info
    if prec == "psum8":
        prec = "f32"             # EF wire needs the θ ≡ 1 fused engine
    sep = row_separable(smooth)
    if (opts.accel and sep is not None and sep.kind == "quad"
            and _fused_capable(linop)
            and fused_gradient_enabled(smooth, linop, opts.fused)):
        with _telemetry.current().span("solve.loop"):
            x, info = _tfocs_fused_accel(smooth, linop, prox, x0, opts, sep)
        info["precision"] = prec
        return x, info
    backtracking = opts.backtracking and opts.Lexact is None
    L_init = jnp.asarray(opts.Lexact if opts.Lexact is not None else opts.L0,
                         jnp.float32)

    def theta_next(theta, L_ratio):
        """TFOCS θ update; with backtracking the ratio L⁺/L rescales the
        accumulated momentum."""
        if not opts.accel:
            return jnp.asarray(1.0, jnp.float32)
        return 2.0 / (1.0 + jnp.sqrt(1.0 + 4.0 * L_ratio / (theta * theta)))

    def attempt_once(a: _Attempt) -> _Attempt:
        """One candidate step at the current (L, θ); θ is recomputed by the
        caller whenever L changes (backtracking rescales the momentum)."""
        y = (1 - a.theta) * a.x + a.theta * a.z
        Ay = (1 - a.theta) * a.Ax + a.theta * a.Az
        fy = smooth.value(Ay)
        gy = smooth.grad(Ay)
        g = linop.adjoint(gy)                       # ← ONE adjoint
        step = 1.0 / (a.L * a.theta)
        z_new = prox.prox(a.z - step * g, step)
        Az_new = linop.apply(z_new)                 # ← ONE apply
        x_new = (1 - a.theta) * a.x + a.theta * z_new
        Ax_new = (1 - a.theta) * a.Ax + a.theta * Az_new
        f_new = smooth.value(Ax_new)
        dx = x_new - y
        xy_sq = jnp.vdot(dx, dx)
        rhs = fy + jnp.vdot(g, dx) + 0.5 * a.L * xy_sq
        ok = _backtrack_ok(
            f_new, fy, rhs,
            jnp.vdot(smooth.grad(Ax_new) - gy, Ax_new - Ay), a.L, xy_sq)
        return a._replace(x=x_new, Ax=Ax_new, z=z_new, Az=Az_new,
                          fy=fy, gy=gy, Ay=Ay, ok=ok, tries=a.tries + 1)

    def outer(state: TfocsState) -> TfocsState:
        L0k = state.L * (opts.beta if backtracking else 1.0)
        theta0 = theta_next(state.theta, L0k / state.L)

        init = _Attempt(L=L0k, theta=theta0,
                        x=state.x, Ax=state.Ax, z=state.z, Az=state.Az,
                        fy=jnp.float32(0), gy=jnp.zeros_like(state.Ax),
                        Ay=state.Ax, ok=jnp.asarray(False),
                        tries=jnp.int32(0))
        first = attempt_once(init)

        if backtracking:
            def bt_cond(a: _Attempt):
                return (~a.ok) & (a.tries < opts.max_backtracks)

            def bt_body(a: _Attempt):
                L_new = a.L * opts.alpha
                theta_new = theta_next(state.theta, L_new / state.L)
                return attempt_once(a._replace(
                    L=L_new, theta=theta_new,
                    x=state.x, Ax=state.Ax, z=state.z, Az=state.Az))

            acc = jax.lax.while_loop(bt_cond, bt_body, first)
        else:
            acc = first

        # Gradient-test restart: momentum points uphill → reset it.
        if opts.restart and opts.accel:
            uphill = jnp.vdot(acc.gy, acc.Ax - state.Ax) > 0
            theta_out = jnp.where(uphill, 1.0, acc.theta)
            z_out = jnp.where(uphill, acc.x, acc.z)
            Az_out = jnp.where(uphill, acc.Ax, acc.Az)
            n_restarts = state.n_restarts + uphill.astype(jnp.int32)
        else:
            theta_out, z_out, Az_out = acc.theta, acc.z, acc.Az
            n_restarts = state.n_restarts

        obj = smooth.value(acc.Ax) + prox.value(acc.x)
        hist = state.hist.at[state.k].set(obj)
        dx = acc.x - state.x
        rel = jnp.linalg.norm(dx) / jnp.maximum(1.0, jnp.linalg.norm(acc.x))
        return TfocsState(
            x=acc.x, Ax=acc.Ax, z=z_out, Az=Az_out,
            theta=theta_out, L=acc.L, k=state.k + 1, hist=hist,
            done=rel < opts.tol,
            n_backtracks=state.n_backtracks + acc.tries - 1,
            n_restarts=n_restarts)

    def cond(state: TfocsState):
        return (~state.done) & (state.k < opts.max_iters)

    with _telemetry.current().span("solve.loop"):
        Ax0 = linop.apply(x0)
        init = TfocsState(
            x=x0, Ax=Ax0, z=x0, Az=Ax0,
            theta=jnp.asarray(1.0, jnp.float32), L=L_init,
            k=jnp.int32(0),
            hist=jnp.full((opts.max_iters,), jnp.nan, jnp.float32),
            done=jnp.asarray(False),
            n_backtracks=jnp.int32(0), n_restarts=jnp.int32(0))
        final = jax.lax.while_loop(cond, outer, init)
    # Standardized keys as in _tfocs_fused; the cached accelerated scheme
    # pays apply + adjoint (two passes) per attempt, plus the seed apply.
    info = {"iterations": final.k,
            "a_passes": 1 + 2 * (final.k + final.n_backtracks),
            "converged": final.done, "plan": "cached",
            "history": final.hist,
            "n_backtracks": final.n_backtracks,
            "n_restarts": final.n_restarts, "fused": False,
            "objective": final.hist[jnp.maximum(final.k - 1, 0)],
            "precision": prec}
    return final.x, info
