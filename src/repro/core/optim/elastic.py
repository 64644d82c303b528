"""Elastic, fault-tolerant executor for the batched solver engines.

The TFOCS/L-BFGS one-shot solvers are `lax.while_loop`s — one traced
program, no host between iterations, nowhere to notice a straggler or
write a checkpoint.  The serving frontend already drives the batched
engines (core/optim/batched) one iteration at a time from the host; this
module extracts that driver into ``ElasticGroup`` and makes the
host-visible gap between iterations do the fault-tolerance work:

  * straggler mitigation — per-iteration, per-shard timing telemetry feeds
    train.straggler.ShardMonitor; when it names a slow shard, the group
    re-shards the distributed matrix onto the survivor mesh
    (train.elastic.remesh_linop / survivor_mesh) MID-SOLVE: iterate,
    gradient and history state live on the driver and never move, only the
    matrix does, so the iteration counter stays monotone and no completed
    iteration is re-run (one re-seed A-pass refreshes F/G in the new
    reduction order);
  * transient faults — a failed pass (TransientShardError) or a non-finite
    smooth value rolls back to the pre-step state and retries with bounded
    exponential backoff; DeviceLostError re-meshes like a monitor trip;
  * resumable solves — ``SolveCheckpoint`` (train.checkpoint underneath)
    snapshots the complete optimizer state (iterates, gradients, L-BFGS
    memory, iteration counters, slot masks) every N iterations and
    restores it bit-compatibly, so a killed solve resumed from its last
    checkpoint reaches the same convergence state as an undisturbed run.

``solve_elastic`` drives a 1-slot group for the direct call path
(`api.SolveRequest(checkpoint_dir=..., resume=True)` routes here);
launch/serve.GroupRunner wraps a many-slot group for the serving path.
Fault behavior is entirely opt-in: with ``elastic=None`` the group runs
the exact op sequence the serving frontend always ran — bit-for-bit.

The injection side of the contract (``fault_hook``/``on_remesh``) is
implemented by train.faults.FaultyLinop; see the "fault tolerance &
resumable solves" section of examples/quickstart.py for the wiring.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.optim import batched as _batched
from repro.launch import telemetry as _tel
from repro.train import checkpoint as _ckpt
from repro.train.straggler import ShardMonitor

Array = jax.Array

GROUP_METHODS = ("gra", "acc", "acc_rb", "lbfgs")
# The accelerated members batch via the affine u-vector trick
# (batched.make_acc_group) — quadratic losses only; acc_rb adds
# backtracking + gradient-test restarts.
ACC_METHODS = ("acc", "acc_rb")


class TransientShardError(RuntimeError):
    """One pass over one shard failed but the shard is alive (dropped
    collective, preempt notice, corrupted reduction) — roll back the
    iteration and retry with backoff."""


class DeviceLostError(RuntimeError):
    """A shard's device is gone for good — re-mesh onto the survivors."""

    def __init__(self, shard: int):
        super().__init__(f"device backing shard {shard} lost")
        self.shard = shard


# -- resumable solver state ---------------------------------------------------

class SolveCheckpoint:
    """Periodic snapshots of batched solver state, restored bit-compatibly.

    The snapshot is mesh-INDEPENDENT by construction: the engines keep
    every optimizer array replicated on the driver (X/F/G, L-BFGS S/Y/rho
    memory, per-slot k/done/obj, the active mask), and the data-space
    arrays (padded targets/weights) are rebuilt from the request on
    restore — so a checkpoint written on an 8-shard mesh resumes on 1
    shard and vice versa.  Storage is train.checkpoint: atomic .tmp→rename
    commit, fsync'd LATEST pointer, and (by default) the async writer so
    the solve blocks only for the host transfer."""

    def __init__(self, ckpt_dir, *, every: int = 10, async_save: bool = True):
        self.ckpt_dir = ckpt_dir
        self.every = int(every)
        self.saves = 0
        self._async = _ckpt.AsyncCheckpointer(ckpt_dir) if async_save \
            else None

    def save(self, step: int, state, active, *, extra: dict | None = None):
        tree = {"state": state, "active": np.asarray(active)}
        extra = dict(extra or {})
        extra["iteration"] = int(step)
        if self._async is not None:
            self._async.save_async(step, tree, extra=extra)
        else:
            _ckpt.save(self.ckpt_dir, step, tree, extra=extra)
        self.saves += 1

    def maybe_save(self, step: int, state, active, *,
                   extra: dict | None = None) -> bool:
        if self.every <= 0 or step <= 0 or step % self.every:
            return False
        self.save(step, state, active, extra=extra)
        return True

    def latest(self) -> int | None:
        return _ckpt.latest_step(self.ckpt_dir)

    def restore(self, state_like, active_like, *, step: int | None = None):
        """(state, active, extra) from the newest committed snapshot, or
        None when the directory holds no complete checkpoint."""
        if self.latest() is None:
            return None
        tree, extra = _ckpt.restore(
            self.ckpt_dir,
            {"state": state_like, "active": np.asarray(active_like)},
            step=step)
        active = np.asarray(tree["active"]).astype(bool)
        return tree["state"], active, extra

    def wait(self) -> None:
        """Block until the in-flight async write commits (and re-raise its
        error, if any) — call before treating a checkpoint as durable."""
        if self._async is not None:
            self._async.wait()


@dataclasses.dataclass
class ElasticConfig:
    """Fault-tolerance policy for an ElasticGroup.  All parts optional:
    monitor without remesh_to only observes; checkpoint alone gives
    resumability with no straggler handling.  `sleep` is injectable so
    tests measure backoff schedules without wall time."""
    monitor: ShardMonitor | None = None
    remesh_to: Callable[[int | None], Any] | None = None   # shard -> Mesh
    checkpoint: SolveCheckpoint | None = None
    max_retries: int = 3
    backoff_s: float = 0.05
    sleep: Callable[[float], None] = time.sleep


# Module-level jitted slot writers: admission/retirement edit one row of
# the batched state between iterations, and doing the dozen scatters
# eagerly costs more host dispatch than a whole solver step — jit folds
# each into one program, cached by array shape across ALL groups.
@jax.jit
def _write_slot_gra(state, T, W, lam, tol, i, t, w, lamv, tolv, x0, L0):
    state = state._replace(
        X=state.X.at[i].set(x0), F=state.F.at[i].set(0.0),
        G=state.G.at[i].set(0.0), L=state.L.at[i].set(L0),
        k=state.k.at[i].set(0), done=state.done.at[i].set(False),
        obj=state.obj.at[i].set(jnp.nan), bt=state.bt.at[i].set(0))
    return (state, T.at[i].set(t), W.at[i].set(w), lam.at[i].set(lamv),
            tol.at[i].set(tolv))


@jax.jit
def _write_slot_lbfgs(state, T, W, lam, tol, i, t, w, lamv, tolv, x0, L0):
    state = state._replace(
        X=state.X.at[i].set(x0), F=state.F.at[i].set(0.0),
        G=state.G.at[i].set(0.0), S_=state.S_.at[i].set(0.0),
        Y=state.Y.at[i].set(0.0), rho=state.rho.at[i].set(0.0),
        idx=state.idx.at[i].set(0), filled=state.filled.at[i].set(0),
        k=state.k.at[i].set(0), done=state.done.at[i].set(False),
        obj=state.obj.at[i].set(jnp.nan))
    return (state, T.at[i].set(t), W.at[i].set(w), lam.at[i].set(lamv),
            tol.at[i].set(tolv))


@jax.jit
def _write_slot_acc(state, T, W, lam, tol, i, t, w, lamv, tolv, x0, L0):
    # Data-space caches (AX/AZ) and u-vectors are zeroed; the next seed
    # pass recomputes them for the whole group.
    state = state._replace(
        X=state.X.at[i].set(x0), AX=state.AX.at[i].set(0.0),
        UX=state.UX.at[i].set(0.0), Z=state.Z.at[i].set(x0),
        AZ=state.AZ.at[i].set(0.0), UZ=state.UZ.at[i].set(0.0),
        UB=state.UB.at[i].set(0.0), F=state.F.at[i].set(0.0),
        theta=state.theta.at[i].set(1.0), L=state.L.at[i].set(L0),
        k=state.k.at[i].set(0), done=state.done.at[i].set(False),
        obj=state.obj.at[i].set(jnp.nan), bt=state.bt.at[i].set(0),
        rs=state.rs.at[i].set(0))
    return (state, T.at[i].set(t), W.at[i].set(w), lam.at[i].set(lamv),
            tol.at[i].set(tolv))


_SLOT_WRITERS = {"gra": _write_slot_gra, "lbfgs": _write_slot_lbfgs,
                 "acc": _write_slot_acc, "acc_rb": _write_slot_acc}


@jax.jit
def _bind_slot(T, W, lam, tol, i, t, w, lamv, tolv):
    # Resume path: rebind the data-space rows around RESTORED solver state
    # (the restored X/F/G/k must survive untouched).
    return (T.at[i].set(t), W.at[i].set(w), lam.at[i].set(lamv),
            tol.at[i].set(tolv))


@jax.jit
def _clear_row(W, i):
    return W.at[i].set(0.0)


def _find_hook(linop):
    """Innermost wrapper exposing the fault_hook protocol (train.faults)."""
    obj = linop
    while obj is not None:
        if hasattr(obj, "fault_hook"):
            return obj
        obj = getattr(obj, "base", None)
    return None


def _with_matrix(linop, A):
    """`linop` with its matrix operand replaced by `A`, through any chain
    of delegating wrappers (dataclasses whose `base` is the wrapped
    operator)."""
    if hasattr(linop, "base"):
        return dataclasses.replace(linop, base=_with_matrix(linop.base, A))
    return dataclasses.replace(linop, A=A)


class ElasticGroup:
    """Host-driven executor for one batched solver group, one iteration at
    a time — the state-holder behind launch/serve.GroupRunner and
    ``solve_elastic``.

    Owns `slots` lanes of batched engine state over a shared linop plus
    the data-space rows (targets T, weights W, per-slot lam/tol) and the
    host-side active mask.  ``admit_slot`` writes a problem into a free
    lane; ``step_iteration`` advances every active lane by one engine step
    (ONE fused group A-pass plus shared backtracking attempts) and, when
    an ElasticConfig is present, runs the recovery ladder around it:

      retry    — TransientShardError / non-finite smooth → roll back to
                 the pre-step state, exponential backoff, bounded retries;
      re-mesh  — DeviceLostError or a ShardMonitor trip → rebuild the
                 linop on config.remesh_to(shard)'s mesh, re-pad T/W for
                 the new shard count, re-seed F/G in one pass; driver-side
                 state is untouched, so `k` stays monotone;
      resume   — config.checkpoint snapshots (state, active) every N
                 iterations.

    With ``elastic=None`` every branch above is skipped and the op
    sequence is exactly the legacy serving loop's.

    Observability: every iteration phase is wrapped in a telemetry span
    (``solver.iteration`` > seed_pass / fused_pass / validate /
    checkpoint / remesh — see launch/telemetry.py), and when a recorder
    is live each engine step emits a plan-vs-actual record pricing the
    fused A-pass against the planner's model.  `telemetry=None` (the
    default) resolves the module-level recorder at call time — a no-op
    unless ``telemetry.enable()`` / ``api.*Request(telemetry=...)`` is in
    effect, so the untraced path costs nothing."""

    def __init__(self, linop, kind: str, param: float = 1.0, *,
                 reg: str = "none", method: str = "gra", slots: int = 8,
                 mem: int = 10, elastic: ElasticConfig | None = None,
                 telemetry: _tel.Recorder | None = None):
        if method not in GROUP_METHODS:
            raise ValueError(f"method must be one of {GROUP_METHODS}")
        if method == "lbfgs" and reg != "none":
            raise ValueError("lbfgs groups need reg='none'")
        if method in ACC_METHODS and kind != "quad":
            raise ValueError("accelerated groups batch via the affine "
                             "u-vector trick — loss='quad' only, got "
                             f"{kind!r}")
        self.linop, self.kind, self.param = linop, kind, param
        self.reg, self.method, self.slots = reg, method, slots
        self.mem = mem
        self.elastic = elastic
        self.n = linop.in_shape[0]
        self.m_pad = linop.out_shape[0]
        if method == "gra":
            self.state = _batched.gra_group_init(slots, self.n)
        elif method in ACC_METHODS:
            self.state = _batched.acc_group_init(slots, self.n, self.m_pad)
        else:
            self.state = _batched.lbfgs_group_init(slots, self.n, mem=mem)
        self._build_engines()
        self.T = jnp.zeros((slots, self.m_pad), jnp.float32)
        self.W = jnp.zeros((slots, self.m_pad), jnp.float32)
        self.lam = jnp.zeros((slots,), jnp.float32)
        self.tol = jnp.full((slots,), 1e-8, jnp.float32)
        self.active = np.zeros(slots, bool)          # host-side slot map
        self._slot_b: list = [None] * slots          # raw targets (remesh)
        self.a_passes = 0          # lifetime group passes (the shared cost)
        self._dirty = False        # admissions since the last seed pass
        self.iteration = 0         # global monotone iteration counter
        self.retries = 0
        self.remeshes = 0
        self.checkpoint_saves = 0
        self._telemetry = telemetry
        self._fused_plan_cache = None   # invalidated on remesh
        self.monitor = elastic.monitor if elastic is not None else None
        if self.monitor is not None \
                and self.monitor.nshards != linop.row_shards():
            self.monitor.reset(linop.row_shards())

    @property
    def tel(self) -> _tel.Recorder:
        """The group's recorder: the one passed in, else the module-level
        ``telemetry.current()`` (a no-op unless enabled)."""
        return self._telemetry if self._telemetry is not None \
            else _tel.current()

    def _fused_plan(self):
        """Lazily-priced ExecutionPlan for this group's fused A-pass (the
        per-step unit of plan-vs-actual); re-priced after a remesh."""
        if self._fused_plan_cache is None:
            from repro.launch import planner
            self._fused_plan_cache = planner.plan(
                "fusedgrad", {"m": self.m_pad, "n": self.n})
        return self._fused_plan_cache

    def _engines(self, linop):
        if self.method == "gra":
            return _batched.make_gra_group(linop, self.kind, self.param,
                                           reg=self.reg)
        if self.method in ACC_METHODS:
            rb = self.method == "acc_rb"
            return _batched.make_acc_group(
                linop, self.kind, self.param, reg=self.reg,
                backtracking=rb, restart=rb)
        return _batched.make_lbfgs_group(linop, self.kind, self.param)

    def _build_engines(self) -> None:
        # The matrix is a jit argument: closed over, it would be baked into
        # each executable as a constant.
        def engine(i):
            return jax.jit(lambda A, *a: self._engines(
                _with_matrix(self.linop, A))[i](*a))
        self._seed = functools.partial(engine(0), self.linop.A)
        self._step = functools.partial(engine(1), self.linop.A)

    # -- slot management ------------------------------------------------------

    def free_slots(self) -> int:
        return int(self.slots - self.active.sum())

    def busy(self) -> bool:
        return bool(self.active.any())

    def admit_slot(self, b, *, lam: float = 0.0, tol: float = 1e-8,
                   x0=None, L0: float = 1.0,
                   reset_state: bool = True) -> int:
        """Write a problem into a free slot; costs no pass by itself (the
        next step's seed recomputes F/G for the whole group in one).
        `reset_state=False` binds only the data-space rows, for restoring
        checkpointed solver state into the lane afterwards."""
        i = int(np.flatnonzero(~self.active)[0])
        b = jnp.asarray(b, jnp.float32)
        x0 = jnp.zeros((self.n,), jnp.float32) if x0 is None \
            else jnp.asarray(x0, jnp.float32)
        if reset_state:
            write = _SLOT_WRITERS[self.method]
            self.state, self.T, self.W, self.lam, self.tol = write(
                self.state, self.T, self.W, self.lam, self.tol, i,
                self.linop.pad_data(b), self.linop.row_weights(),
                float(lam), float(tol), x0, float(L0))
            self._dirty = True
        else:
            self.T, self.W, self.lam, self.tol = _bind_slot(
                self.T, self.W, self.lam, self.tol, i,
                self.linop.pad_data(b), self.linop.row_weights(),
                float(lam), float(tol))
        self.active[i] = True
        self._slot_b[i] = b
        return i

    def clear_slot(self, i: int) -> None:
        """Retire lane `i`: zero its weight row so it contributes nothing
        to subsequent group passes (state rows reset on the next admit)."""
        self.W = _clear_row(self.W, i)
        self.active[i] = False
        self._slot_b[i] = None

    # -- the iteration --------------------------------------------------------

    def _seed_if_dirty(self) -> int:
        if not self._dirty:
            return 0
        with self.tel.span("solver.seed_pass",
                           active=int(self.active.sum())) as sp:
            if self.method == "lbfgs":
                self.state, p = self._seed(self.state, self.T, self.W)
            else:
                self.state, p = self._seed(self.state, self.T, self.W,
                                           self.lam)
            sp.sync_on(self.state.F)
        self._dirty = False
        self.a_passes += int(p)
        return int(p)

    def _engine_step(self, act):
        if self.method == "lbfgs":
            return self._step(self.state, self.T, self.W, self.tol, act)
        return self._step(self.state, self.T, self.W, self.lam,
                          self.tol, act)

    def step_iteration(self) -> int:
        """One solver iteration for every active slot; returns the group
        A-passes consumed (including re-seeds, retries, and re-meshes).
        Raises TransientShardError when a fault outlives max_retries, and
        DeviceLostError when a device dies with no remesh_to policy."""
        if not self.busy():
            return 0
        tel = self.tel
        passes = 0
        failures = 0
        with tel.span("solver.iteration", iteration=self.iteration,
                      active=int(self.active.sum())):
            while True:
                passes += self._seed_if_dirty()
                act = jnp.asarray(self.active)
                t0 = time.monotonic()
                with tel.span("solver.fused_pass") as psp:
                    new_state, tries = self._engine_step(act)
                    dt = time.monotonic() - t0
                    psp.sync_on(new_state.F)
                    psp.annotate(tries=int(tries))
                passes += int(tries)
                self.a_passes += int(tries)
                if tel.enabled:
                    tel.record_plan_actual(
                        self._fused_plan(), psp.dur_s / max(int(tries), 1),
                        iteration=self.iteration, tries=int(tries))
                if self.elastic is None:
                    self.state = new_state
                    return passes
                telemetry = None
                try:
                    with tel.span("solver.validate"):
                        hook = _find_hook(self.linop)
                        if hook is not None:
                            new_state, telemetry = hook.fault_hook(
                                self.iteration, new_state, dt)
                        if not bool(jnp.all(jnp.isfinite(
                                jnp.where(act, new_state.F, 0.0)))):
                            raise TransientShardError(
                                "non-finite smooth value after step")
                except DeviceLostError as e:
                    if self.elastic.remesh_to is None:
                        raise
                    # Pre-step state is intact (rollback is free: new_state
                    # was never committed) — re-mesh, re-run the iteration.
                    self.remesh(self.elastic.remesh_to(e.shard),
                                dropped=e.shard)
                    failures = 0
                    continue
                except TransientShardError:
                    failures += 1
                    self.retries += 1
                    tel.counter("solver.retries").inc()
                    if failures > self.elastic.max_retries:
                        raise
                    self.elastic.sleep(self.elastic.backoff_s
                                       * (2 ** (failures - 1)))
                    continue                   # rollback + bounded retry
                self.state = new_state
                self.iteration += 1
                if telemetry is not None and self.monitor is not None:
                    verdict = self.monitor.observe(telemetry["shard_times"])
                    if verdict["tripped"] \
                            and self.elastic.remesh_to is not None:
                        self.remesh(self.elastic.remesh_to(verdict["shard"]),
                                    dropped=verdict["shard"])
                ck = self.elastic.checkpoint
                if ck is not None and ck.every > 0 \
                        and self.iteration % ck.every == 0:
                    with tel.span("solver.checkpoint",
                                  iteration=self.iteration):
                        if ck.maybe_save(self.iteration, self.state,
                                         self.active,
                                         extra={"a_passes": self.a_passes}):
                            self.checkpoint_saves += 1
                return passes

    # -- mid-solve re-mesh ----------------------------------------------------

    def remesh(self, new_mesh, dropped: int | None = None) -> None:
        """Move the MATRIX to `new_mesh` mid-solve; driver-side solver
        state is mesh-independent and stays put.  The data-space rows are
        re-padded for the new shard count from the stored raw targets, and
        the next step re-seeds F/G in one group pass — `k` is untouched,
        so no completed iteration is re-run."""
        from repro.train import elastic as _train_elastic
        tel = self.tel
        with tel.span("solver.remesh", dropped=dropped,
                      iteration=self.iteration):
            self._remesh_inner(_train_elastic, new_mesh, dropped, tel)
        tel.counter("solver.remeshes").inc()

    def _remesh_inner(self, _train_elastic, new_mesh, dropped, tel) -> None:
        self.linop = _train_elastic.remesh_linop(self.linop, new_mesh)
        obj = self.linop
        while obj is not None:                 # tell injection wrappers
            if hasattr(obj, "on_remesh"):
                obj.on_remesh(dropped)
            obj = getattr(obj, "base", None)
        self.m_pad = self.linop.out_shape[0]
        self._fused_plan_cache = None          # re-price plan-vs-actual
        with tel.span("solver.rejit"):
            self._build_engines()
        # Solver state is logically driver-side, but its arrays are still
        # committed to the OLD device set (they were produced by jits over
        # the old mesh).  Re-home them as uncommitted host-backed arrays so
        # the next jit can co-locate them with the re-meshed operands.
        self.state, self.lam, self.tol = jax.tree_util.tree_map(
            lambda a: jnp.asarray(np.asarray(jax.device_get(a))),
            (self.state, self.lam, self.tol))
        if self.method in ACC_METHODS:
            # The accelerated state caches data-space images at the OLD
            # padded row count; re-size them and let the dirty re-seed
            # (3 group passes) rebuild AX/AZ and the u-vectors.
            z = jnp.zeros((self.slots, self.m_pad), jnp.float32)
            self.state = self.state._replace(AX=z, AZ=z)
        T = jnp.zeros((self.slots, self.m_pad), jnp.float32)
        W = jnp.zeros_like(T)
        w = self.linop.row_weights()
        for i in range(self.slots):
            if self.active[i] and self._slot_b[i] is not None:
                T = T.at[i].set(self.linop.pad_data(self._slot_b[i]))
                W = W.at[i].set(w)
        self.T, self.W = T, W
        self._dirty = True                     # one re-seed pass next step
        if self.monitor is not None:
            self.monitor.reset(self.linop.row_shards())
        self.remeshes += 1


# -- the direct resumable path ------------------------------------------------

def solve_elastic(linop, kind: str, b, *, param: float = 1.0,
                  reg: str = "none", lam: float = 0.0, method: str = "gra",
                  tol: float = 1e-8, max_iters: int = 200, L0: float = 1.0,
                  x0=None, deadline_s: float | None = None,
                  resume: bool = False,
                  elastic: ElasticConfig | None = None):
    """Drive a 1-slot ElasticGroup to convergence: the fault-tolerant,
    resumable, deadline-aware twin of the one-shot solvers (and the path
    `api.solve` takes when a request carries checkpoint_dir/deadline_s).
    Returns (x, info) with the standardized info keys plus the recovery
    counters (degraded / retries / remeshes / checkpoint_saves /
    resumed_from)."""
    if elastic is None:
        elastic = ElasticConfig()
    grp = ElasticGroup(linop, kind, param, reg=reg, method=method, slots=1,
                       elastic=elastic)
    ck = elastic.checkpoint
    resumed_from = None
    if resume and ck is not None and ck.latest() is not None:
        grp.admit_slot(b, lam=lam, tol=tol, x0=x0, L0=L0,
                       reset_state=False)
        state, active, extra = ck.restore(grp.state, grp.active)
        grp.state = state
        grp.active = active
        grp.iteration = int(extra.get("iteration", 0))
        grp.a_passes = int(extra.get("a_passes", 0))
        grp._dirty = False          # F/G restored bit-exactly — no re-seed
        resumed_from = grp.iteration
    else:
        grp.admit_slot(b, lam=lam, tol=tol, x0=x0, L0=L0)

    deadline_at = time.monotonic() + deadline_s if deadline_s else None
    degraded = None
    while True:
        k = int(grp.state.k[0])
        if bool(grp.state.done[0]) or k >= max_iters:
            break
        if deadline_at is not None and time.monotonic() > deadline_at:
            degraded = "deadline"   # return the best iterate, don't block
            break
        grp.step_iteration()
    if ck is not None:
        ck.wait()                   # surface any lost background write
    k = int(grp.state.k[0])
    converged = bool(grp.state.done[0])
    if degraded is None and not converged and k >= max_iters:
        degraded = "max_iterations"
    info = {"iterations": k, "a_passes": grp.a_passes,
            "converged": converged, "plan": "elastic",
            "objective": float(grp.state.obj[0]),
            "degraded": degraded, "retries": grp.retries,
            "remeshes": grp.remeshes,
            "checkpoint_saves": grp.checkpoint_saves,
            "resumed_from": resumed_from}
    if deadline_s is not None:
        info["deadline_s"] = float(deadline_s)
    return jnp.asarray(grp.state.X[0]), info
