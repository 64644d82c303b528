"""repro.api — the uniform request/result surface for the matrix suite.

One set of request dataclasses drives BOTH entry paths:

  * the direct call path — `solve(SolveRequest(...))`,
    `svd(SvdRequest(...))`, `similarities(SimilarityRequest(...))` run the
    job immediately and return a `Result`;
  * the serving path — `launch/serve.SolverServer.submit(...)` enqueues the
    SAME objects, groups solve requests that share a design matrix, and
    answers each group with one fused A-pass per iteration.

`minimize()`, `compute_svd()` and `column_similarities()` are thin wrappers
over the request objects, kept signature-compatible with their historical
homes (core.optim.api.minimize, core.linalg.svd.compute_svd, and the
distmat methods).

Every `Result.info` carries the standardized keys

  iterations — outer iterations (restarts for Lanczos, q for randomized)
  a_passes   — streaming passes over A consumed (the paper's cost unit)
  converged  — whether the stopping test fired before the iteration cap
  plan       — which execution plan answered it ("fused", "cached",
               "gram", "randomized", "lanczos", ...)
  degraded   — None for a full-quality answer, else why it was cut short
               ("deadline", "max_iterations", "fault", "overloaded")

plus solver-native detail; pre-existing solver-specific keys ("fused",
"n_evals", "mode", "passes_over_A", ...) remain as deprecated aliases for
one release.
"""
from __future__ import annotations

import itertools
import math
import time
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.distmat.rowmatrix import RowMatrix
from repro.core.distmat.sparserow import SparseRowMatrix
from repro.core.linalg.svd import compute_svd as _compute_svd
from repro.core.optim.api import minimize as _minimize
from repro.core.optim.problems import Problem
from repro.core.tfocs.linop import LinopMatrix
from repro.core.tfocs.prox import ProxL1, ProxL2Sq, ProxZero
from repro.core.tfocs.smooth import (SmoothHuber, SmoothLogLoss,
                                     SmoothPoisson, SmoothQuad)
from repro.kernels import autotune as _autotune
from repro.kernels.fusedgrad import LOSSES
from repro.launch import telemetry as _telemetry

Array = jax.Array

REGS = ("none", "l1", "l2")
_ids = itertools.count()


def _next_id(prefix: str) -> str:
    return f"{prefix}-{next(_ids)}"


def _check_scalar(name: str, value, *, minimum=None,
                  exclusive: bool = False, optional: bool = False):
    """Shared typed validation for request scalars: finite, and bounded
    below when asked.  Rejecting NaN/negative knobs at construction keeps
    both entry paths (direct call and serving queue) from discovering a
    bad deadline or tolerance mid-solve."""
    if value is None:
        if optional:
            return
        raise ValueError(f"{name} must be set")
    v = float(value)
    if math.isnan(v) or math.isinf(v):
        raise ValueError(f"{name} must be finite, got {value!r}")
    if minimum is not None:
        if exclusive and not v > minimum:
            raise ValueError(f"{name} must be > {minimum}, got {value!r}")
        if not exclusive and not v >= minimum:
            raise ValueError(f"{name} must be >= {minimum}, got {value!r}")


@dataclass
class SolveRequest:
    """minimize f(Ax) + h(x): the work unit of both solve paths.

    The common case names a design matrix `A` (RowMatrix, SparseRowMatrix
    or a local array), a target `b` and a row-separable `loss` — exactly
    the shape the serving queue can batch (requests sharing A, loss and
    reg kind form one fused group).  `problem` / `smooth` / `prox` are
    escape hatches for prebuilt composites (those run the direct path but
    are served one-per-group)."""
    A: Any = None                 # RowMatrix | SparseRowMatrix | Array
    b: Any = None                 # (m,) target / labels / counts
    loss: str = "quad"            # quad | logistic | huber | poisson
    param: float = 1.0            # static loss scalar (huber δ)
    reg: str = "none"             # none | l1 | l2
    lam: float = 0.0              # regularizer weight
    method: str = "gra"           # gra | acc | acc_r | acc_b | acc_rb | lbfgs
    tol: float = 1e-8
    max_iters: int = 200
    L0: float = 1.0               # initial Lipschitz estimate (1/step)
    x0: Any = None
    # Compute/wire precision: "auto" lets the planner's precision sweep
    # pick {f32, bf16 storage, int8-compressed psum} with `tol` as the
    # error guard (see TfocsOptions.precision); "f32"/"bf16"/"psum8"
    # force the choice.  Result.info["precision"] reports what ran.
    precision: str = "auto"
    # fault tolerance / resumability (see core.optim.elastic):
    deadline_s: float | None = None     # wall budget; past it → best iterate
    checkpoint_dir: str | None = None   # periodic resumable snapshots
    checkpoint_every: int = 10          # iterations between snapshots
    resume: bool = False                # restore from checkpoint_dir first
    # escape hatches (direct path; served without cross-request batching):
    problem: Problem | None = None
    smooth: Any = None
    prox: Any = None
    # observability (launch/telemetry.py): True for a fresh recorder, or a
    # telemetry.Recorder to accumulate across requests.  Off by default
    # (near-zero overhead).  When set, the solve runs under
    # telemetry.recording() and Result.info["trace"] carries the span /
    # plan-vs-actual summary.
    telemetry: Any = None
    request_id: str = field(default_factory=lambda: _next_id("solve"))

    def __post_init__(self):
        if self.problem is None and self.smooth is None:
            if self.loss not in LOSSES:
                raise ValueError(f"loss must be one of {LOSSES}, "
                                 f"got {self.loss!r}")
            if self.reg not in REGS:
                raise ValueError(f"reg must be one of {REGS}, "
                                 f"got {self.reg!r}")
            if self.A is None or self.b is None:
                raise ValueError("SolveRequest needs (A, b) or a "
                                 "problem/smooth escape hatch")
        _check_scalar("tol", self.tol, minimum=0.0)
        _check_scalar("lam", self.lam, minimum=0.0)
        _check_scalar("L0", self.L0, minimum=0.0, exclusive=True)
        _check_scalar("param", self.param)
        _check_scalar("max_iters", self.max_iters, minimum=0,
                      exclusive=True)
        _check_scalar("deadline_s", self.deadline_s, minimum=0.0,
                      exclusive=True, optional=True)
        _check_scalar("checkpoint_every", self.checkpoint_every, minimum=0,
                      exclusive=True)
        if self.precision not in ("auto", "f32", "bf16", "psum8"):
            raise ValueError("precision must be auto | f32 | bf16 | psum8, "
                             f"got {self.precision!r}")
        if self.checkpoint_dir is not None:
            if self.problem is not None or self.smooth is not None \
                    or self.prox is not None:
                raise ValueError("checkpoint_dir needs the (A, b) request "
                                 "form (escape hatches aren't resumable)")
            if self.method not in ("gra", "lbfgs"):
                raise ValueError("checkpoint_dir needs method 'gra' or "
                                 f"'lbfgs', got {self.method!r}")
        if self.resume and self.checkpoint_dir is None:
            raise ValueError("resume=True needs checkpoint_dir")


@dataclass
class SvdRequest:
    """Truncated SVD of a distributed matrix (core.linalg.compute_svd)."""
    A: Any
    k: int
    compute_u: bool = True
    mode: str = "auto"            # auto | gram | lanczos | randomized
    options: dict = field(default_factory=dict)   # extra compute_svd kwargs
    deadline_s: float | None = None
    telemetry: Any = None         # True | telemetry.Recorder (see SolveRequest)
    request_id: str = field(default_factory=lambda: _next_id("svd"))

    def __post_init__(self):
        _check_scalar("k", self.k, minimum=0, exclusive=True)
        _check_scalar("deadline_s", self.deadline_s, minimum=0.0,
                      exclusive=True, optional=True)


@dataclass
class SimilarityRequest:
    """DIMSUM column similarities (exact at threshold=0, sampled above)."""
    A: Any
    threshold: float = 0.0
    gamma: float | None = None
    seed: int = 0
    deadline_s: float | None = None
    telemetry: Any = None         # True | telemetry.Recorder (see SolveRequest)
    request_id: str = field(default_factory=lambda: _next_id("sim"))

    def __post_init__(self):
        _check_scalar("threshold", self.threshold, minimum=0.0)
        _check_scalar("deadline_s", self.deadline_s, minimum=0.0,
                      exclusive=True, optional=True)


@dataclass
class Result:
    """Uniform answer envelope: `x` for solves, `factors` for SVD
    ((U, s, V)) and similarities ((sim,)), `info` with the standardized
    keys (iterations / a_passes / converged / plan, plus `degraded` — None
    for a full-quality answer, else why it was cut short: "deadline",
    "max_iterations", "fault", "overloaded")."""
    x: Array | None = None
    factors: tuple | None = None
    info: dict = field(default_factory=dict)
    request_id: str = ""


@dataclass
class Overloaded(Result):
    """Typed load-shed answer: the server refused the request at submit
    because its admission budget/queue bound was exhausted — carries no
    solution, only `info["degraded"] == "overloaded"`.  A typed result
    (instead of unbounded queueing or an exception mid-drain) lets clients
    distinguish "retry later" from "failed"."""

    def __post_init__(self):
        self.info.setdefault("degraded", "overloaded")
        self.info.setdefault("iterations", 0)
        self.info.setdefault("a_passes", 0)
        self.info.setdefault("converged", False)
        self.info.setdefault("plan", "rejected")


# -- request construction helpers (shared with launch/serve) ------------------

def solve_linop(req: SolveRequest) -> LinopMatrix:
    if req.problem is not None:
        return req.problem.linop
    A = req.A
    if isinstance(A, (RowMatrix, SparseRowMatrix)):
        return LinopMatrix(A)
    return LinopMatrix(jnp.asarray(A))


def solve_smooth(req: SolveRequest, linop: LinopMatrix):
    """The row-separable smooth for a request, padded to the linop's data
    space with padding rows weighted 0."""
    if req.problem is not None:
        return req.problem.smooth
    if req.smooth is not None:
        return req.smooth
    b = linop.pad_data(jnp.asarray(req.b, jnp.float32))
    w = linop.row_weights()
    if req.loss == "quad":
        return SmoothQuad(b=b, weights=w)
    if req.loss == "logistic":
        return SmoothLogLoss(y=b, weights=w)
    if req.loss == "huber":
        return SmoothHuber(b=b, delta=req.param, weights=w)
    return SmoothPoisson(y=b, weights=w)


def solve_prox(req: SolveRequest):
    if req.problem is not None:
        return req.problem.prox
    if req.prox is not None:
        return req.prox
    if req.reg == "l1":
        return ProxL1(req.lam)
    if req.reg == "l2":
        return ProxL2Sq(req.lam)
    return ProxZero()


# -- direct call path ---------------------------------------------------------

# Every job computes in float32, at the kernels' own precision: XLA's
# default on TPU multiplies f32 matrices in one bf16 pass, which the Lanczos
# reorthogonalization, the solvers' inner products and the dense matvecs
# cannot afford.
F32_MATMULS = _autotune.HIGHEST.name.lower()


def _traced(req, kind: str, run) -> Result:
    """Run one job with f32 matmuls, inside an ``api.<kind>`` span with
    the request's id (on the profiler's clock on every call).  The
    ``telemetry=`` escape hatch: when the request asks for it, run the job
    under a scoped recorder (every instrumented component — elastic
    iterations, checkpoints, stragglers — resolves it via
    telemetry.current()) and attach the compact summary as
    ``Result.info["trace"]``.  Off (the default), the span is a profiler
    annotation and nothing else."""
    with jax.default_matmul_precision(F32_MATMULS):
        if not req.telemetry:
            with _telemetry.current().span("api." + kind,
                                           request_id=req.request_id):
                return run()
        rec = req.telemetry \
            if isinstance(req.telemetry, _telemetry.Recorder) \
            else _telemetry.Recorder()
        with _telemetry.recording(rec):
            with rec.span("api." + kind, request_id=req.request_id):
                res = run()
    res.info["trace"] = rec.summary()
    return res


def _solve_elastic(req: SolveRequest) -> Result:
    """Host-driven resumable/deadline-aware path (core.optim.elastic):
    taken when a direct-form gra/lbfgs request asks for a checkpoint or a
    wall deadline — the lax.while_loop solvers can't be interrupted or
    snapshotted mid-flight, the per-iteration driver can."""
    from repro.core.optim import elastic as _elastic
    ckpt = None
    if req.checkpoint_dir is not None:
        ckpt = _elastic.SolveCheckpoint(req.checkpoint_dir,
                                        every=req.checkpoint_every)
    cfg = _elastic.ElasticConfig(checkpoint=ckpt)
    x, info = _elastic.solve_elastic(
        solve_linop(req), req.loss, req.b, param=req.param, reg=req.reg,
        lam=req.lam, method=req.method, tol=req.tol,
        max_iters=req.max_iters, L0=req.L0, x0=req.x0,
        deadline_s=req.deadline_s, resume=req.resume, elastic=cfg)
    return Result(x=x, info=info, request_id=req.request_id)


def solve(req: SolveRequest, *, fused: bool | str = "auto") -> Result:
    """Run one SolveRequest immediately (no queue, no batching)."""
    return _traced(req, "solve", lambda: _solve(req, fused=fused))


def _solve(req: SolveRequest, *, fused: bool | str = "auto") -> Result:
    if req.problem is not None:
        x, info = _minimize(req.problem, req.method,
                            max_iters=req.max_iters, tol=req.tol,
                            fused=fused)
        info = dict(info)
        info.setdefault("degraded", None)
        return Result(x=x, info=info, request_id=req.request_id)
    if (req.checkpoint_dir is not None
            or (req.deadline_s is not None
                and req.method in ("gra", "lbfgs")
                and req.smooth is None and req.prox is None)):
        return _solve_elastic(req)

    from repro.core.optim.first_order import minimize_first_order
    from repro.core.tfocs.solver import TfocsOptions
    with _telemetry.current().span("solve.setup"):
        linop = solve_linop(req)
        smooth = solve_smooth(req, linop)
        prox = solve_prox(req)
        x0 = jnp.zeros(linop.in_shape, jnp.float32) if req.x0 is None \
            else jnp.asarray(req.x0, jnp.float32)
    opts = TfocsOptions(max_iters=req.max_iters, tol=req.tol, L0=req.L0,
                        fused=fused, precision=req.precision)
    if req.method == "lbfgs" and not isinstance(prox, ProxZero):
        raise ValueError("method='lbfgs' needs reg='none' (fold the "
                         "regularizer into a smooth loss)")
    t0 = time.perf_counter()
    x, info = minimize_first_order(req.method, smooth, linop, prox,
                                   x0=x0, opts=opts)
    info = dict(info)
    info.setdefault("degraded", None)
    if req.deadline_s is not None \
            and time.perf_counter() - t0 > req.deadline_s:
        # The accelerated while_loop variants can't stop mid-flight; the
        # overrun is reported post-hoc so callers still learn the budget
        # was blown.
        info["degraded"] = "deadline"
    return Result(x=x, info=info, request_id=req.request_id)


def svd(req: SvdRequest) -> Result:
    return _traced(req, "svd", lambda: _svd(req))


def _svd(req: SvdRequest) -> Result:
    t0 = time.perf_counter()
    res = _compute_svd(req.A, req.k, compute_u=req.compute_u,
                       mode=req.mode, **req.options)
    info = dict(res.info or {})
    info.setdefault("converged", True)
    info.setdefault("degraded", None)
    if req.deadline_s is not None \
            and time.perf_counter() - t0 > req.deadline_s:
        info["degraded"] = "deadline"
    return Result(factors=(res.U, res.s, res.V), info=info,
                  request_id=req.request_id)


def similarities(req: SimilarityRequest) -> Result:
    return _traced(req, "similarities", lambda: _similarities(req))


def _similarities(req: SimilarityRequest) -> Result:
    sim, info = req.A.column_similarities(
        req.threshold, gamma=req.gamma, seed=req.seed, return_info=True)
    info = dict(info or {})
    # DIMSUM is a single Gram-style reduction: one pass over A, no
    # iteration, deterministic completion.
    info.setdefault("iterations", 0)
    info.setdefault("a_passes", 1)
    info.setdefault("converged", True)
    info.setdefault("plan", "dimsum" if req.threshold > 0 else "gram")
    info.setdefault("degraded", None)
    return Result(factors=(sim,), info=info, request_id=req.request_id)


# -- thin signature-compatible wrappers ---------------------------------------

def minimize(problem: Problem, method: str, *, max_iters: int = 200,
             step_size: float | None = None, tol: float = 1e-10,
             fused: bool | str = "auto"):
    """Thin wrapper: a Problem-shaped SolveRequest through the same path
    the server drives.  Returns (x, info) like core.optim.minimize."""
    if step_size is not None:
        # Problem-based requests resolve L0 inside core.optim.api.minimize.
        return _minimize(problem, method, max_iters=max_iters,
                         step_size=step_size, tol=tol, fused=fused)
    res = solve(SolveRequest(problem=problem, method=method, tol=tol,
                             max_iters=max_iters), fused=fused)
    return res.x, res.info


def compute_svd(A, k: int, *, compute_u: bool = True, mode: str = "auto",
                **options):
    """Thin wrapper: an SvdRequest through the request path.  Returns the
    SVDResult-compatible (U, s, V, info) unpacked from the Result."""
    res = svd(SvdRequest(A=A, k=k, compute_u=compute_u, mode=mode,
                         options=options))
    U, s, V = res.factors
    return U, s, V, res.info


def column_similarities(A, threshold: float = 0.0, *,
                        gamma: float | None = None, seed: int = 0):
    """Thin wrapper: a SimilarityRequest through the request path.
    Returns (sim, info)."""
    res = similarities(SimilarityRequest(A=A, threshold=threshold,
                                         gamma=gamma, seed=seed))
    return res.factors[0], res.info
