"""The general load generator: one class per job kind, each read from a
traffic file.

A traffic file names its `job` ("solve" or "svd") and the parameters of
its mix.  Each makes its inputs from the seed in
`setup` (which also runs one warm job of each kind the mix sends), drives
the entry point of the program in `run` for the measured window, and
compares a seeded sample of what the window produced with the plain
references in `check`.  `control` puts the reference, at a lower matmul
precision, or the program's own lower-precision path, in the program's
place on the same sample; the benchmark's runs never call it.

The program is reached only through `repro.api`.  Everything else about
the matrix comes from the object that the configuration's
`build(cfg, key)` returns, through these names alone:

- every job: `shape` (m, n); `program`, the matrix the entry point is
  given; `work()`, the sizes the per-layer readers count work from;
  `lowprec_program()`, the program's own lower-precision storage of the
  same matrix (the `program_bf16` control only).
- `job: "solve"`: `data`, the matrix the references read, and
  `ops(prec)`, the plain operators (mv, rmv) over it at matmul precision
  `prec`: `mv(data, X)` = A·X, `rmv(data, U)` = Aᵀ·U.
- `job: "svd"`: `gram_top(k)`, the top-k eigenvalues of AᵀA in float64,
  largest first (σ's reference is their square root, formed once a run);
  `gram_times(V)`, AᵀA·V in float64 for V (n, k); `times(V, prec)`, A·V
  (m, k) at matmul precision `prec`; and `svd_at(prec, k)`, the
  reference's own (U, σ, V) at matmul precision `prec` (the control).
  None of them need form an n × n array."""
from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from refs import solvers, spectral

HIGHEST = "highest"


def span(name):
    """A host span in the profiler's trace (free when no trace runs)."""
    return jax.profiler.TraceAnnotation(name)


def ready(*xs):
    for x in xs:
        jax.block_until_ready(x)


class Jobs:
    """Shared bookkeeping: the seed's generators and the window's record."""

    def __init__(self, mat, traffic, seed, key):
        self.mat, self.traffic, self.seed = mat, traffic, seed
        self.key = key
        self.rng = np.random.default_rng(seed)
        self.records: list[dict] = []
        self.counters: dict = {}

    def failed(self) -> int:
        return sum(1 for r in self.records if not r["ok"])

    def sample(self, pool, size):
        """A seeded sample of indices into `pool` (a list of records)."""
        rng = np.random.default_rng([self.seed, 1])
        size = min(size, len(pool))
        return sorted(rng.choice(len(pool), size, replace=False).tolist())


# -- closed loop of api.solve jobs ---------------------------------------------

class SolveJobs(Jobs):
    """Back-to-back `api.solve` jobs, one family after another in a seeded
    cycle, each on a label vector from a pool made in set-up, every family
    on every label in turn."""

    metric = "solve_s"

    def setup(self):
        from repro import api
        self.api = api
        t = self.traffic
        m, n = self.mat.shape
        self.mv, self.rmv = self.mat.ops(HIGHEST)
        data = self.mat.data
        # The planted solutions, and so λ and L0 (params), are the same
        # for every seed; A and the label noise are not.
        kx, ks = jax.random.split(jax.random.PRNGKey(0))
        pool = t["label_pool"]
        X = jax.random.normal(kx, (n, pool), jnp.float32) * (
            jax.random.uniform(ks, (n, pool)) < t["planted_density"]) \
            / math.sqrt(n * t["planted_density"])
        Z = jax.jit(self.mv)(data, X)
        noise = jax.random.normal(self.key, (m, pool), jnp.float32)
        x_inf = np.max(np.abs(np.asarray(X, np.float64)), axis=0)
        x_two = np.linalg.norm(np.asarray(X, np.float64), axis=0)
        self.cols, self.lam_l1 = {}, {}
        for loss in sorted({f["loss"] for f in t["families"]}):
            s = t["label_noise"][loss]
            B = Z + s * noise if loss == "quad" else jnp.sign(Z + s * noise)
            self.cols[loss] = [B[:, i] for i in range(pool)]
            ready(*self.cols[loss])
            self.lam_l1[loss] = t["l1_weight"] * float(np.median(
                grad0_inf(loss, m, x_inf, x_two, s)))
        del Z, noise, B
        self.sq = (math.sqrt(m) + math.sqrt(n)) ** 2
        fams = t["families"]
        self.cycle = [fams[i] for i in self.rng.permutation(len(fams))]
        self.pool_order = self.rng.permutation(pool)
        for fam in fams:                                # warm each family
            self.job(fam, int(self.pool_order[0]), record=False)

    def params(self, fam, label):
        """λ and L0 of a job, one pair for each family and the same for
        every seed: the program compiles its solver anew for each value of
        λ and L0, so values drawn from the data made each new seed compile
        inside the window where a repeated seed found its programs cached
        (PERF.md).  ‖A‖₂² is taken as (√m + √n)², its expected value for a
        standard normal A, and L1's λ from the expected ‖∇f(0)‖∞."""
        loss, reg = fam["loss"], fam["reg"]
        lam = (self.lam_l1[loss] if reg == "l1"
               else self.traffic["l2_weight"] * self.sq if reg == "l2"
               else 0.0)
        return lam, solvers.lipschitz(loss, self.sq)

    def job(self, fam, label, *, record=True, matrix=None, precision="f32"):
        t = self.traffic
        lam, L = self.params(fam, label)
        name = f"job.solve.{fam['loss']}_{fam['reg']}"
        with span(name):
            t0 = time.perf_counter()
            res = self.api.solve(self.api.SolveRequest(
                A=self.mat.program if matrix is None else matrix,
                b=self.cols[fam["loss"]][label], loss=fam["loss"],
                reg=fam["reg"], lam=lam, L0=L, tol=t["tol"],
                max_iters=t["max_iters"], precision=precision))
            ready(res.x)
            info = res.info
            rec = {"family": fam, "label": label, "lam": lam, "L": L,
                   "x": res.x, "seconds": time.perf_counter() - t0,
                   "iterations": int(info["iterations"]),
                   "a_passes": int(info["a_passes"]),
                   "objective": float(info["objective"]),
                   "converged": bool(info["converged"]),
                   "degraded": info["degraded"], "plan": info["plan"],
                   "precision": info.get("precision")}
        rec["ok"] = (rec["converged"] and rec["degraded"] is None
                     and rec["precision"] == precision)
        if record:
            self.records.append(rec)
        return rec

    def run(self, seconds):
        """Whole cycles of the families until the window has passed.  The
        label moves on once a cycle, so every family meets every label of
        the pool once in each pass of len(cycle) × pool jobs: every seed
        sends the same problems, in another order.  (Moving the label on
        with every job paired each family with a quarter of the pool, a
        different quarter for each seed, and so changed the work.)"""
        t0 = time.perf_counter()
        j = 0
        while True:
            fam = self.cycle[j % len(self.cycle)]
            label = int(self.pool_order[
                j // len(self.cycle) % len(self.pool_order)])
            self.job(fam, label)
            j += 1
            if j % len(self.cycle) == 0 \
                    and time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        self.counters.update(
            jobs=j, window_s=elapsed,
            a_passes=sum(r["a_passes"] for r in self.records),
            iterations=sum(r["iterations"] for r in self.records))
        return {self.metric: elapsed / j}

    def checked(self):
        """One job of each family, drawn from the window by the seed."""
        out = []
        for fam in self.traffic["families"]:
            pool = [r for r in self.records if r["family"] == fam]
            out += [pool[i] for i in self.sample(pool, 1)]
        return out

    def problem(self, r):
        """A record's target column (m, 1), weight and step bound."""
        fam = r["family"]
        return (self.cols[fam["loss"]][r["label"]][:, None],
                np.array([r["lam"]]), np.array([r["L"]]))

    def reference(self, recs, prec, *, tol=None, max_iters=None,
                  restart=False):
        """FISTA at `prec` on each record's problem: (x, iterations).  By
        default it stops like the program, at the traffic's tol."""
        n = self.mat.shape[1]
        mv, rmv = self.mat.ops(prec)
        out = []
        for r in recs:
            fam = r["family"]
            b, lam, L = self.problem(r)
            with span("reference.fista"):
                X, it = solvers.fista(
                    mv, rmv, self.mat.data, b, fam["loss"], fam["reg"],
                    lam, L, n=n, tol=tol or self.traffic["tol"],
                    max_iters=max_iters or self.traffic["max_iters"],
                    restart=restart)
            out.append((X[:, 0], int(it[0])))
        return out

    def optimum(self, recs):
        """Each record's optimum x*: FISTA with restarts at highest, run
        far past the traffic's tol."""
        t = self.traffic
        return self.reference(recs, HIGHEST, tol=t["optimum_tol"],
                              max_iters=t["optimum_iters"], restart=True)

    def objective(self, rec, x, prec):
        fam = rec["family"]
        mv, _ = self.mat.ops(prec)
        b, lam, _ = self.problem(rec)
        return float(solvers.objective(
            mv, self.mat.data, b, fam["loss"], fam["reg"], lam,
            x[:, None])[0])

    def step_at(self, rec, x):
        """The reference's relative proximal gradient step from x."""
        fam = rec["family"]
        mv, rmv = self.mat.ops(HIGHEST)
        b, lam, L = self.problem(rec)
        with span("reference.step"):
            return float(solvers.prox_step(
                mv, rmv, self.mat.data, b, fam["loss"], fam["reg"], lam, L,
                x[:, None])[0])

    def compare(self, recs, xs, objs, *, diagnose=False):
        """The numbers compared: the relative proximal gradient step that
        the reference takes from the solution (`prox_step`, 0 at the
        optimum), and the reported objective's gap to the reference
        objective at that same solution (`obj_gap`); each the worst over
        the records.  With `diagnose`, also per family: the distance to
        FISTA stopped at the traffic's tol (`x_gap`) and to the optimum
        (`x_err`), and the optimum's own step (`step_opt`)."""
        prox_step = obj_gap = 0.0
        per = {}
        for r, x, obj in zip(recs, xs, objs):
            prox_step = max(prox_step, self.step_at(r, x))
            o_ref = self.objective(r, x, HIGHEST)
            obj_gap = max(obj_gap, abs(obj - o_ref) / abs(o_ref))
            if diagnose:
                per[f"{r['family']['loss']}_{r['family']['reg']}"] = \
                    self.diagnose(r, x)
        out = {"prox_step": prox_step, "obj_gap": obj_gap}
        return dict(out, per_family=per) if diagnose else out

    def diagnose(self, r, x):
        (x_tol, _), = self.reference([r], HIGHEST)
        (x_opt, it), = self.optimum([r])
        return {"step": self.step_at(r, x),
                "x_gap": rel(x, x_tol), "x_err": rel(x, x_opt),
                "step_opt": self.step_at(r, x_opt), "optimum_iters": it,
                "iterations": r.get("iterations")}

    def check(self, *, diagnose=False):
        recs = self.checked()
        return self.compare(recs, [r["x"] for r in recs],
                            [r["objective"] for r in recs],
                            diagnose=diagnose)

    def control(self, kind, *, diagnose=False):
        """The same comparison with the program's own bfloat16 storage
        path (`program_bf16`) or the reference at a lower matmul precision
        (`kind`, e.g. "high") in the program's place."""
        recs = self.checked()
        if kind == "program_bf16":
            outs = [self.job(r["family"], r["label"], record=False,
                             precision="bf16") for r in recs]
            return self.compare(recs, [o["x"] for o in outs],
                                [o["objective"] for o in outs],
                                diagnose=diagnose)
        xs = [x for x, _ in self.reference(recs, kind)]
        objs = [self.objective(r, x, kind) for r, x in zip(recs, xs)]
        return self.compare(recs, xs, objs, diagnose=diagnose)


# -- closed loop of api.svd jobs -----------------------------------------------

class SvdJobs(Jobs):
    """Back-to-back `api.svd` jobs on the resident matrix."""

    metric = "svd_s"

    def setup(self):
        from repro import api
        self.api = api
        self.sample_size = self.traffic["check_sample"]
        self.ref = None
        self.job(record=False)                          # warm

    def job(self, *, record=True, matrix=None):
        t = self.traffic
        with span(f"job.svd.{t['mode']}"):
            t0 = time.perf_counter()
            res = self.api.svd(self.api.SvdRequest(
                A=self.mat.program if matrix is None else matrix, k=t["k"],
                mode=t["mode"]))
            U, s, V = res.factors
            ready(U.rows, s, V)
            info = res.info
            rec = {"s": np.asarray(s, np.float64), "V": V, "U": U.rows,
                   "seconds": time.perf_counter() - t0,
                   "iterations": int(info["iterations"]),
                   "a_passes": int(info["a_passes"]),
                   "converged": bool(info["converged"]),
                   "degraded": info["degraded"], "plan": info["plan"]}
        rec["ok"] = (rec["converged"] and rec["degraded"] is None
                     and rec["plan"] == t["expect_plan"])
        if record:
            self.keep(rec)
        return rec

    def keep(self, rec):
        """Record the job; keep U only for a seeded reservoir sample."""
        self.records.append(rec)
        j = len(self.records) - 1
        held = [i for i, r in enumerate(self.records) if r["U"] is not None]
        if len(held) > self.sample_size:
            rng = np.random.default_rng([self.seed, 2, j])
            slot = int(rng.integers(0, j + 1))
            drop = held[slot] if slot < self.sample_size else j
            self.records[drop]["U"] = None

    def run(self, seconds):
        t0 = time.perf_counter()
        while True:
            self.job()
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        jobs = len(self.records)
        self.counters.update(
            jobs=jobs, window_s=elapsed,
            a_passes=sum(r["a_passes"] for r in self.records))
        return {self.metric: elapsed / jobs}

    def reference(self):
        """σ's reference, formed once: the square roots of AᵀA's top-k
        eigenvalues in float64."""
        if self.ref is None:
            with span("reference.gram"):
                self.ref = np.sqrt(self.mat.gram_top(self.traffic["k"]))
        return self.ref

    def compare(self, triples):
        """σ against the reference's; ‖A·V − U·Σ‖/‖A·V‖ with A·V from the
        reference; and ‖G·V − V·Σ²‖/‖V·Σ²‖ with G·V = AᵀA·V from the
        reference in float64; each the worst over the triples."""
        s_ref = self.reference()
        sigma_gap = u_resid = v_resid = 0.0
        for U, s, V in triples:
            sigma_gap = max(sigma_gap, spectral.rel_gaps(s, s_ref))
            with span("reference.av"):
                AV = self.mat.times(V, HIGHEST)
            u_resid = max(u_resid, spectral.factor_residual(AV, U, s))
            v_resid = max(v_resid, spectral.eigen_residual(
                self.mat.gram_times(V), V, s))
        return {"sigma_gap": sigma_gap, "u_resid": u_resid,
                "v_resid": v_resid}

    def check(self, *, diagnose=False):
        recs = [r for r in self.records if r["U"] is not None]
        return self.compare([(r["U"], r["s"], r["V"]) for r in recs])

    def control(self, kind, *, diagnose=False):
        """The same comparison with the program's own bfloat16 storage
        path (`program_bf16`), or with the reference's own triplets at
        matmul precision `kind` in the program's place."""
        if kind == "program_bf16":
            rec = self.job(record=False, matrix=self.mat.lowprec_program())
            return self.compare([(rec["U"], rec["s"], rec["V"])])
        return self.compare([self.mat.svd_at(kind, self.traffic["k"])])


def grad0_inf(loss, m, x_inf, x_two, noise):
    """The expected ‖∇f(0)‖∞ of each label for a standard normal A (m
    rows) and planted x: ‖E Aᵀb‖∞ = m‖x‖∞ for least squares, and
    ½·m·√(2/π)·‖x‖∞/√(‖x‖² + noise²) for logistic labels sign(Ax + noise)."""
    if loss == "quad":
        return m * x_inf
    return 0.5 * m * math.sqrt(2 / math.pi) * x_inf / np.sqrt(
        x_two ** 2 + noise ** 2)


def rel(a, b):
    """‖a − b‖ / ‖b‖ in float64."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


KINDS = {"solve": SolveJobs, "svd": SvdJobs}
