"""Solver serving frontend — concurrent matrix jobs, one A-pass per group.

    PYTHONPATH=src python -m repro.launch.serve --requests 16 --m 512 --n 64

The paper prices every iterative method in streaming passes over the
distributed matrix.  A serving deployment amortizes them: when several
clients solve against the SAME design matrix A (multi-user regression,
per-target least squares, one-vs-rest logistic), their iterations can share
each pass.  This module is that frontend:

  * ``SolverServer.submit`` enqueues the ``repro.api`` request objects
    (SolveRequest / SvdRequest / SimilarityRequest) — the exact dataclasses
    the direct call path uses;
  * solve requests sharing (A, loss, param, reg, engine) form a GROUP
    served by one ``GroupRunner``: per-request TFOCS/L-BFGS state is
    batched over the request axis and every solver iteration is ONE fused
    multi-RHS A-pass (kernels/fusedgrad via core/optim/batched), so a
    group of k requests costs the same passes per iteration as one;
  * the serving loop is continuous batching (the vLLM idiom, transplanted
    to solvers): a fixed number of slots per group, requests admitted and
    retired BETWEEN solver iterations by editing slot rows, inactive slots
    frozen by the engines' per-slot masks — no tail latency from waiting
    for the slowest request in a static batch;
  * admission control is planner-priced: ``launch/planner.plan`` prices a
    group's per-iteration device time on the calibrated machine model, and
    the scheduler packs groups into a per-step device-time budget.
    Joining an already-active group is FREE (the same pass serves one more
    right-hand side) — only opening a new group consumes budget.  The
    queue is strictly FIFO: a request that cannot be admitted (budget or
    slots) blocks those behind it, so overload degrades in arrival order.

Batched engines cover the whole Figure-1 family: ``gra`` and ``lbfgs``
share passes directly, and the accelerated variants (``acc``/``acc_rb``)
batch for quadratic losses via the affine u-vector trick (per-slot
u-vectors make the momentum point's gradient free — see
core/optim/batched.make_acc_group).  SVD / similarity requests and
non-batchable solves (escape-hatch problems, non-quadratic accelerated
requests) run as one-shot jobs through the same FIFO queue and budget,
via the same ``repro.api`` executors.

The frontend is hardened for real fleets (see the "fault tolerance &
resumable solves" section of examples/quickstart.py):

  * every GroupRunner drives core/optim/elastic.ElasticGroup, so a server
    built with an ElasticConfig gets straggler detection, mid-solve
    re-meshing and bounded retry-with-backoff per group — and the planner
    re-prices the group on its new shard shape after a re-mesh;
  * per-request ``deadline_s`` / ``max_iters`` degrade gracefully: an
    expired resident is retired with its best iterate, ``converged=False``
    and ``info["degraded"]`` naming the reason, instead of blocking the
    group;
  * ``max_pending`` sheds load at submit with a typed ``api.Overloaded``
    result instead of queueing without bound.

Every answer is a ``repro.api.Result`` whose info carries the standardized
keys; for served solves ``a_passes`` is the number of GROUP passes consumed
while the request was resident — the amortized cost the batching buys down.

Observability (launch/telemetry.py): the server's metrics are ALWAYS live —
typed counters behind the ``stats`` view (including the per-reason
``stats["degraded"]`` breakdown that separates shed/overloaded from
fault-retired from deadline-expired requests), plus ``serve.queue_wait_s``
and ``serve.latency_s`` histograms with real p50/p99.  Scheduler-action
spans (admit / oneshot / retire / shed / recover) and the solver's
per-iteration spans are recorded when the server is constructed while
``telemetry.enable()`` is in effect (or given an explicit ``telemetry=``
recorder); export them with ``server.tel.export_jsonl(path)``.  Every span
is also a ``repro.*`` annotation on the JAX profiler's clock, recorder or
not: run the server under ``jax.profiler.trace(dir)`` for a timeline of
the scheduler actions beside the device ops.  See the "observability"
section of examples/quickstart.py.
"""
from __future__ import annotations

import argparse
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.core.optim import elastic as _elastic
from repro.launch import planner as _planner
from repro.launch import telemetry as _tel

Array = jax.Array

# Engines the group runner batches; everything else is served one-shot.
GROUP_METHODS = _elastic.GROUP_METHODS

# The server's aggregate counters (rendered by SolverServer.stats).
_STAT_KEYS = ("steps", "a_passes", "admitted", "oneshot", "deferred_steps",
              "shed", "expired", "remeshes")


def group_key(req: api.SolveRequest):
    """Requests with equal keys can share fused A-passes: same matrix
    object, same row-separable loss, same static loss scalar, same reg
    KIND (per-slot lam rides in the batched prox), same engine."""
    return (id(req.A), req.loss, float(req.param), req.reg, req.method)


def batchable(req: Any) -> bool:
    # Checkpointed solves run one-shot through the resumable elastic path:
    # their snapshots capture a single request's state, not a shared
    # group's.  Accelerated groups batch via the affine u-vector trick,
    # which only exists for quadratic losses — non-quad acc requests run
    # one-shot.
    return (isinstance(req, api.SolveRequest) and req.problem is None
            and req.smooth is None and req.prox is None
            and req.method in GROUP_METHODS
            and (req.loss == "quad"
                 or req.method not in _elastic.ACC_METHODS)
            and req.checkpoint_dir is None)


class GroupRunner:
    """Continuous-batching executor for one request group.

    Owns `slots` lanes of batched solver state (core/optim/batched) over a
    shared linop; `admit` writes a request into a free lane, `step` runs
    one solver iteration for every active lane in ONE fused group A-pass
    (plus shared backtracking attempts) and returns the lanes that
    finished as `api.Result`s.  Slots freed by retirement are reusable on
    the next admit — the engines freeze inactive lanes bit-for-bit, so
    residents never observe their neighbours churning.
    """

    def __init__(self, linop, kind: str, param: float = 1.0, *,
                 reg: str = "none", method: str = "gra", slots: int = 8,
                 mem: int = 10,
                 elastic: _elastic.ElasticConfig | None = None,
                 telemetry: _tel.Recorder | None = None):
        # All solver state lives in the elastic executor; the runner adds
        # the serving concerns on top (request metadata, deadlines,
        # retirement into api.Results, planner price cache).
        self.tel = telemetry if telemetry is not None else _tel.NULL
        self._eg = _elastic.ElasticGroup(linop, kind, param, reg=reg,
                                         method=method, slots=slots,
                                         mem=mem, elastic=elastic,
                                         telemetry=telemetry)
        self.kind, self.param = kind, param
        self.reg, self.method, self.slots = reg, method, slots
        self.meta: list[dict | None] = [None] * slots
        self._price_cache = 0.0    # planner-modeled seconds per iteration
        self._priced_remeshes = 0  # re-price when the shard shape changes

    # -- delegated solver state (the executor owns it) ------------------------

    @property
    def linop(self):
        return self._eg.linop

    @property
    def state(self):
        return self._eg.state

    @property
    def active(self):
        return self._eg.active

    @property
    def a_passes(self) -> int:
        return self._eg.a_passes

    @property
    def remeshes(self) -> int:
        return self._eg.remeshes

    # -- slot management ------------------------------------------------------

    def free_slots(self) -> int:
        return self._eg.free_slots()

    def busy(self) -> bool:
        return self._eg.busy()

    def admit(self, req: api.SolveRequest) -> int:
        """Write `req` into a free slot; costs no pass by itself (the next
        step's seed recomputes F/G for the whole group in one)."""
        i = self._eg.admit_slot(req.b, lam=float(req.lam),
                                tol=float(req.tol), x0=req.x0,
                                L0=float(req.L0))
        self.meta[i] = {"req": req, "admit_passes": self.a_passes,
                        "deadline_at": (time.monotonic() + req.deadline_s
                                        if req.deadline_s else None)}
        return i

    # -- the iteration --------------------------------------------------------

    def step(self) -> list[api.Result]:
        """One solver iteration for every active slot (one group A-pass plus
        shared backtracking/line-search attempts); returns retired lanes."""
        if not self.busy():
            return []
        out = self._expire_deadlines()
        if not self.busy():
            return out
        try:
            self._eg.step_iteration()
        except (_elastic.TransientShardError,
                _elastic.DeviceLostError) as e:
            # Recovery exhausted (or no re-mesh policy): fail the resident
            # requests gracefully with their best iterates rather than
            # poisoning the serving loop.
            with self.tel.span("serve.recover", error=str(e)):
                for i in range(self.slots):
                    if self.active[i]:
                        out.append(self._retire(i, False, degraded="fault",
                                                error=str(e)))
            return out
        done = np.asarray(self.state.done)
        k = np.asarray(self.state.k)
        for i in range(self.slots):
            if self.active[i] and (
                    done[i] or k[i] >= self.meta[i]["req"].max_iters):
                out.append(self._retire(i, bool(done[i])))
        return out

    def _expire_deadlines(self) -> list[api.Result]:
        """Retire residents whose wall deadline passed — best iterate,
        converged=False, degraded="deadline" — so one slow request cannot
        hold its slot (or block the group) past its budget."""
        if not any(m is not None and m["deadline_at"] is not None
                   for m in self.meta):
            return []
        now = time.monotonic()
        out = []
        for i in range(self.slots):
            m = self.meta[i]
            if self.active[i] and m is not None \
                    and m["deadline_at"] is not None \
                    and now > m["deadline_at"]:
                out.append(self._retire(i, False, degraded="deadline"))
        return out

    def _retire(self, i: int, converged: bool, *,
                degraded: str | None = None,
                error: str | None = None) -> api.Result:
        meta = self.meta[i]
        req = meta["req"]
        if degraded is None and not converged:
            degraded = "max_iterations"
        with self.tel.span("serve.retire", slot=i, converged=converged,
                           degraded=degraded,
                           request_id=req.request_id):
            info = {"iterations": int(self.state.k[i]),
                    # Group passes consumed while resident: the amortized
                    # cost (each pass also served every co-resident
                    # request).
                    "a_passes": self.a_passes - meta["admit_passes"],
                    "converged": converged, "plan": "fused-group",
                    "objective": float(self.state.obj[i]),
                    "slot": i, "degraded": degraded}
            if error is not None:
                info["error"] = error
            # Zero the weight row so the retired lane contributes nothing
            # to subsequent group passes; state rows are reset on the next
            # admit.
            self._eg.clear_slot(i)
            self.meta[i] = None
            return api.Result(x=jnp.asarray(self.state.X[i]), info=info,
                              request_id=req.request_id)


class SolverServer:
    """FIFO request queue + planner-priced admission + continuous batching.

    ``submit`` enqueues any repro.api request; ``step`` admits what the
    per-step device-time budget allows, runs one solver iteration per
    active group, and returns the requests that finished.  ``run`` drives
    steps until the queue and all groups drain.
    """

    def __init__(self, *, slots: int = 8, budget_s: float | None = None,
                 backend: str | None = None,
                 max_pending: int | None = None,
                 elastic_factory=None,
                 telemetry: _tel.Recorder | None = None):
        self.slots = slots
        self.budget_s = budget_s
        self.backend = backend
        # Load-shedding bound: submits past this queue depth are refused
        # with a typed api.Overloaded result instead of queueing unboundedly.
        self.max_pending = max_pending
        # () -> core.optim.elastic.ElasticConfig, called once per group so
        # each runner gets its own monitor/checkpoint instances.
        self.elastic_factory = elastic_factory
        # Metrics are always on (a private spanless recorder renders the
        # `stats` view); spans ride along when the server is built under
        # telemetry.enable() or given an explicit recorder.
        if telemetry is not None:
            self.tel = telemetry
        else:
            cur = _tel.current()
            self.tel = cur if cur.enabled else _tel.Recorder(spans=False)
        self._c = {k: self.tel.counter("serve." + k) for k in _STAT_KEYS}
        self._h_wait = self.tel.histogram("serve.queue_wait_s")
        self._h_latency = self.tel.histogram("serve.latency_s")
        self._queue: list[Any] = []
        self._runners: dict[Any, GroupRunner] = {}
        self._results: dict[str, api.Result] = {}
        self._submit_t: dict[str, float] = {}
        self._events: list[tuple[str, float, float]] = []

    @property
    def stats(self) -> dict:
        """Aggregate server statistics, rendered from the typed telemetry
        counters (same keys the old ad-hoc dict carried), plus the
        per-reason ``degraded`` breakdown that distinguishes
        shed/overloaded from fault-retired from deadline-expired requests
        — previously all invisible in aggregate."""
        s = {k: c.value for k, c in self._c.items()}
        s["degraded"] = {
            lbl.split("=", 1)[1]: v
            for lbl, v in self.tel.counters("serve.degraded").items()
            if "=" in lbl}
        return s

    # -- queue ----------------------------------------------------------------

    def submit(self, req) -> str:
        if isinstance(req, api.SolveRequest) and req.problem is None \
                and req.smooth is None and req.method == "lbfgs" \
                and req.reg != "none":
            raise ValueError("method='lbfgs' needs reg='none'")
        if self.max_pending is not None \
                and len(self._queue) >= self.max_pending:
            with self.tel.span("serve.shed", request_id=req.request_id,
                               pending=len(self._queue)):
                self._submit_t[req.request_id] = time.perf_counter()
                self._finish(api.Overloaded(request_id=req.request_id))
                self._c["shed"].inc()
            return req.request_id
        self._queue.append(req)
        self._submit_t[req.request_id] = time.perf_counter()
        return req.request_id

    def pending(self) -> int:
        return len(self._queue)

    def result(self, request_id: str) -> api.Result | None:
        return self._results.get(request_id)

    def latencies(self) -> list[float]:
        """Per-request submit→finish wall seconds, in completion order."""
        return [t1 - t0 for _, t0, t1 in self._events]

    # -- planner pricing ------------------------------------------------------

    def _price(self, req) -> float:
        """Modeled device-seconds: per-ITERATION for a group (one fused
        pass — independent of how many requests share it), whole-job for
        one-shots."""
        if isinstance(req, api.SolveRequest):
            m, n = (req.problem.linop.out_shape[0],
                    req.problem.linop.in_shape[0]) if req.problem is not None \
                else req.A.shape
            return _planner.plan("fusedgrad", {"m": int(m), "n": int(n)},
                                 backend=self.backend).cost_s
        if isinstance(req, api.SvdRequest):
            m, n = req.A.shape
            return _planner.plan("svd", {"m": int(m), "n": int(n),
                                         "k": int(req.k)},
                                 backend=self.backend).cost_s
        # Similarity: the Gram pass is the whole job — price it as the
        # gram-mode SVD of the same matrix minus nothing material.
        m, n = req.A.shape
        return _planner.plan("svd", {"m": int(m), "n": int(n), "k": 1},
                             backend=self.backend).cost_s

    def _active_cost(self) -> float:
        return sum(r._price_cache for r in self._runners.values()
                   if r.busy())

    # -- scheduling -----------------------------------------------------------

    def _admit(self) -> list[api.Result]:
        """FIFO admission under the device-time budget.  Joining an active
        group is free; opening a group (or running a one-shot) consumes
        budget.  The head of the queue blocks everything behind it — strict
        arrival-order degradation under overload.  When nothing is spending
        budget the head is always admitted, so a budget smaller than one
        group's iteration cannot deadlock the queue.  Returns the results
        of any one-shot jobs it ran."""
        done = []
        spent = self._active_cost()
        while self._queue:
            req = self._queue[0]
            expired = self._expire_queued(req)
            if expired is not None:
                self._queue.pop(0)
                self._finish(expired)
                done.append(expired)
                continue
            if batchable(req):
                key = group_key(req)
                runner = self._runners.get(key)
                if runner is not None and runner.busy():
                    if runner.free_slots() == 0:
                        break                      # group full → wait
                    with self.tel.span("serve.admit", mode="join",
                                       request_id=req.request_id):
                        runner.admit(req)          # marginal cost: zero
                else:
                    cost = self._price(req)
                    if self.budget_s is not None and spent > 0 \
                            and spent + cost > self.budget_s:
                        break                      # no budget → wait
                    with self.tel.span("serve.admit", mode="open",
                                       request_id=req.request_id):
                        if runner is None:
                            runner = GroupRunner(
                                api.solve_linop(req), req.loss, req.param,
                                reg=req.reg, method=req.method,
                                slots=self.slots,
                                elastic=(self.elastic_factory()
                                         if self.elastic_factory else None),
                                telemetry=self.tel)
                            runner._price_cache = cost
                            self._runners[key] = runner
                        runner.admit(req)
                    spent += cost
                self._c["admitted"].inc()
                self._observe_wait(req)
                self._queue.pop(0)
            else:
                cost = self._price(req)
                if self.budget_s is not None and spent > 0 \
                        and spent + cost > self.budget_s:
                    break
                self._queue.pop(0)
                self._observe_wait(req)
                with self.tel.span("serve.oneshot",
                                   request_id=req.request_id):
                    res = self._run_oneshot(req)
                self._finish(res)
                done.append(res)
                spent += cost
                self._c["oneshot"].inc()
        return done

    def _observe_wait(self, req) -> None:
        """Queue-wait histogram: submit→dequeue, observed at admission."""
        t0 = self._submit_t.get(req.request_id)
        if t0 is not None:
            self._h_wait.observe(time.perf_counter() - t0)

    def _expire_queued(self, req) -> api.Result | None:
        """Dequeue-time deadline check for one-shot jobs: a request whose
        wall budget was burnt WAITING in the queue is answered degraded
        immediately instead of spending device time on an answer its
        client has already abandoned."""
        deadline = getattr(req, "deadline_s", None)
        if deadline is None:
            return None
        t0 = self._submit_t.get(req.request_id)
        if t0 is None or time.perf_counter() - t0 <= deadline:
            return None
        self._c["expired"].inc()
        return api.Result(
            x=None, info={"iterations": 0, "a_passes": 0,
                          "converged": False, "plan": "expired",
                          "degraded": "deadline"},
            request_id=req.request_id)

    def _run_oneshot(self, req) -> api.Result:
        if isinstance(req, api.SolveRequest):
            return api.solve(req)
        if isinstance(req, api.SvdRequest):
            return api.svd(req)
        return api.similarities(req)

    def _finish(self, res: api.Result) -> None:
        self._results[res.request_id] = res
        t0 = self._submit_t.get(res.request_id, time.perf_counter())
        t1 = time.perf_counter()
        self._events.append((res.request_id, t0, t1))
        self._h_latency.observe(t1 - t0)
        reason = res.info.get("degraded") \
            if isinstance(res.info, dict) else None
        if reason:
            # Per-reason retirement accounting: "overloaded" (shed),
            # "fault", "deadline" and "max_iterations" each count apart,
            # so aggregate stats can tell load-shedding from failures.
            self.tel.counter("serve.degraded", reason=reason).inc()

    # -- the serving loop -----------------------------------------------------

    def step(self) -> list[api.Result]:
        """One scheduler tick: admit, then one solver iteration per active
        group; returns the requests that completed this tick."""
        with jax.default_matmul_precision(api.F32_MATMULS):
            return self._step()

    def _step(self) -> list[api.Result]:
        self._c["steps"].inc()
        out = self._admit()
        if self._queue:
            self._c["deferred_steps"].inc()
        for runner in self._runners.values():
            if runner.busy():
                before = runner.a_passes
                out.extend(runner.step())
                self._c["a_passes"].inc(runner.a_passes - before)
                if runner.remeshes != runner._priced_remeshes:
                    # A mid-solve re-mesh changed the shard shape (and the
                    # padded row count with it): re-price the group so the
                    # admission budget sees the post-failure cost.
                    self._c["remeshes"].inc(runner.remeshes
                                            - runner._priced_remeshes)
                    runner._priced_remeshes = runner.remeshes
                    runner._price_cache = _planner.plan(
                        "fusedgrad", {"m": int(runner._eg.m_pad),
                                      "n": int(runner._eg.n)},
                        backend=self.backend).cost_s
        for res in out:
            self._finish(res)
        return out

    def busy(self) -> bool:
        return bool(self._queue) or any(r.busy()
                                        for r in self._runners.values())

    def run(self, max_steps: int = 100_000) -> list[api.Result]:
        out = []
        while self.busy() and self._c["steps"].value < max_steps:
            out.extend(self.step())
        return out


# -- demo CLI -----------------------------------------------------------------

def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--m", type=int, default=512)
    ap.add_argument("--n", type=int, default=64)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--budget-us", type=float, default=None,
                    help="per-step device-time budget (modeled µs)")
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    A = rng.normal(size=(args.m, args.n)).astype(np.float32)
    server = SolverServer(
        slots=args.slots,
        budget_s=args.budget_us * 1e-6 if args.budget_us else None)
    t0 = time.perf_counter()
    ids = [server.submit(api.SolveRequest(
        A=A, b=(A @ rng.normal(size=args.n)).astype(np.float32),
        loss="quad", method="gra", tol=1e-6, max_iters=200))
        for _ in range(args.requests)]
    results = server.run()
    wall = time.perf_counter() - t0
    lats = sorted(server.latencies())
    print(f"served {len(results)} requests in {wall:.3f}s "
          f"({len(results) / wall:.1f} req/s)")
    print(f"group A-passes: {server.stats['a_passes']} "
          f"(scheduler steps: {server.stats['steps']})")
    print(f"latency p50 {lats[len(lats) // 2] * 1e3:.1f}ms  "
          f"p99 {lats[int(len(lats) * 0.99)] * 1e3:.1f}ms")
    for rid in ids[:3]:
        info = server.result(rid).info
        print(f"  {rid}: iters={info['iterations']} "
              f"a_passes={info['a_passes']} converged={info['converged']}")


if __name__ == "__main__":
    main()
