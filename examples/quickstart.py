"""Quickstart: the paper's core API in 60 lines.

    PYTHONPATH=src python examples/quickstart.py

Distributed matrices, SVD via the driver/cluster split, and a LASSO solve
with the TFOCS port — all on whatever devices are available.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distmat import RowMatrix, CoordinateMatrix, SparseRowMatrix
from repro.core.linalg import compute_svd, tsqr
from repro.core.tfocs import solve_lasso, TfocsOptions
from repro import compile_cache

compile_cache.enable()

rng = np.random.default_rng(0)

# --- RowMatrix: tall-skinny data, distributed by rows --------------------
A = rng.normal(size=(10_000, 64)).astype(np.float32)
rm = RowMatrix.create(A)                     # row-sharded across the mesh
print("column means:", np.asarray(rm.column_stats()["mean"])[:4], "...")

# --- SVD: matrix ops on the cluster, vector ops on the driver ------------
res = compute_svd(rm, k=5)                   # gram path (n is small)
print("top-5 singular values:", np.asarray(res.s))
print("vs numpy:            ", np.linalg.svd(A, compute_uv=False)[:5])

# --- Square & sparse: the ARPACK-analogue matrix-free Lanczos path -------
m = n = 2000
nnz = 40_000
ri, ci = rng.integers(0, m, nnz), rng.integers(0, n, nnz)
va = rng.normal(size=nnz).astype(np.float32)
cm = CoordinateMatrix.create(jnp.asarray(ri), jnp.asarray(ci),
                             jnp.asarray(va), (m, n))
res2 = compute_svd(cm, k=3, mode="lanczos", tol=1e-5)
print("sparse top-3 σ:", np.asarray(res2.s),
      f"(Lanczos restarts: {int(res2.info['restarts'])})")

# --- Sparse distributed matrices: block-sparse rows on the MXU -----------
# SparseRowMatrix shards block-rows across devices; each shard is a BlockELL
# whose multiplies run the Pallas BSR kernels, with a density-aware fallback
# to dense GEMM when the shard is too dense for block-sparse to pay off.
bs = 64
mask = rng.random((4096 // bs, 512 // bs)) < 0.05          # 5% block density
S = (np.kron(mask, np.ones((bs, bs)))
     * rng.normal(size=(4096, 512))).astype(np.float32)
srm = SparseRowMatrix.from_dense(S, bs=bs)                 # or bs="auto"
print(f"SparseRowMatrix: bs={srm.bs} ell={srm.ell} "
      f"block_density={srm.block_density():.3f}")

# The whole SVD loop (matrix on the cluster, vectors on the driver) runs
# against block-sparse storage — Lanczos only ever calls matvec/rmatvec.
res3 = compute_svd(srm, k=3, tol=1e-6)
print("sparse-row top-3 σ:", np.asarray(res3.s))
print("vs numpy:          ", np.linalg.svd(S, compute_uv=False)[:3])

# Sampled DIMSUM column similarities: threshold=0 is exact; larger
# thresholds sample entries with the paper's oversampling probability
# min(1, γ/‖cᵢ‖‖cⱼ‖), trading accuracy below the threshold for flops.
sim = srm.column_similarities(threshold=0.25)
print("DIMSUM(0.25) sample:", np.asarray(sim)[0, :4])

# Conversions are shuffle-free: COO → block-sparse bins entries into
# blocks in one vectorized pass, densify stays on-shard.
cm2 = cm.to_sparse_row_matrix(bs="auto")
print("COO → SparseRowMatrix:", cm2.shape, f"bs={cm2.bs}")

# --- TSQR -----------------------------------------------------------------
Q, R = tsqr(rm)
print("TSQR ‖QᵀQ − I‖:",
      float(jnp.linalg.norm(jnp.asarray(Q.to_local()).T
                            @ jnp.asarray(Q.to_local()) - jnp.eye(64))))

# --- LASSO via the TFOCS port ---------------------------------------------
xt = np.zeros(64, np.float32)
xt[:6] = rng.normal(size=6) * 3
b = (A @ xt + 0.1 * rng.normal(size=10_000)).astype(np.float32)
x, info = solve_lasso(rm, jnp.asarray(b), lam=2.0,
                      opts=TfocsOptions(max_iters=200, restart=True))
print(f"LASSO: {int(info['iterations'])} iters, "
      f"{int(info['n_restarts'])} restarts; "
      f"recovered support: {np.nonzero(np.abs(np.asarray(x)) > 0.1)[0]}")

# --- Fused single-pass gradients ------------------------------------------
# Row-separable losses (least squares, logistic) let the optimizer hot loop
# compute f(Ax), the gradient Aᵀ∇f(Ax), AND the image Ax in ONE streaming
# pass over the distributed matrix (kernels/fusedgrad) instead of the two
# passes of apply + adjoint.  Proximal gradient (`gra`) and L-BFGS take the
# fused path automatically whenever the roofline dispatch prices it ahead
# (on HBM-bound shards that is ~2× less matrix traffic per iteration).
# Accelerated variants over a QUADRATIC loss get their own one-pass engine
# (plan="fused_affine"): the gradient is affine in cached u = Aᵀ(w∘A·)
# vectors, so acc/acc_b/acc_rb also pay a single A-pass per backtracking
# attempt; non-quadratic acc* keep the cached two-pass scheme.  Opt out
# with fused=False (solve_* / minimize / TfocsOptions all accept it).
from repro.core.tfocs import SmoothQuad, LinopMatrix, ProxZero, tfocs

linop = LinopMatrix(rm)
quad = SmoothQuad(b=linop.pad_data(jnp.asarray(b)),
                  weights=linop.row_weights())
xg, info_g = tfocs(quad, linop, ProxZero(), jnp.zeros(64),
                   TfocsOptions(max_iters=100, accel=False,
                                backtracking=True))     # fused="auto"
print(f"fused gra: {int(info_g['iterations'])} iters "
      f"(fused path: {bool(info_g['fused'])}, "
      f"one A-pass per backtracking attempt)")

# --- Low-precision compute: bytes are the bottleneck ----------------------
# The A-stream dominates every kernel above, so moving fewer bytes is the
# one optimization that compounds: RowMatrix can STORE its shards in bf16
# (or fp8) while every kernel upcasts tiles on-chip and accumulates in f32;
# SparseRowMatrix can quantize BlockELL data to int8 with per-block scales;
# and the fused-gradient psum can ship int8 payloads with error feedback
# ("psum8"), so nothing is lost across iterations.  Measured on the
# benchmark shapes (PYTHONPATH=src python -m benchmarks.run --only
# precision):
#
#   format      bytes moved      modeled speedup   solution error vs f32
#   bf16 store  2x fewer         1.86x (V5E)       ~5e-4   (at tol 1e-5)
#   psum8 wire  ~4x fewer/pass   comm-bound wins   ~1e-7   (EF-corrected)
#   int8 BSR    4.0x fewer       bandwidth-bound   ~7e-3   (operator quant)
#
# The solver front door prices this per-solve: precision="auto" (the
# default) asks the planner, which only admits a format when its guard is
# below the requested tolerance (bf16 needs tol ≥ 1e-5, int8 ≥ 1e-3,
# psum8 ≥ 1e-6) AND the modeled byte savings clear a floor.  Every solve
# reports what actually ran:
from repro import api

L0 = float(np.linalg.norm(A, 2) ** 2)
r32 = api.solve(api.SolveRequest(A=rm, b=b, loss="quad", method="gra",
                                 tol=1e-9, max_iters=300, L0=L0))
rlo = api.solve(api.SolveRequest(A=rm, b=b, loss="quad", method="gra",
                                 tol=1e-4, max_iters=300, L0=L0,
                                 precision="bf16"))   # or "auto"/"psum8"
drift = float(jnp.linalg.norm(rlo.x - r32.x)
              / jnp.linalg.norm(r32.x))
print(f"\nprecision: tol=1e-9 ran {r32.info['precision']}, "
      f"forced bf16 ran {rlo.info['precision']} "
      f"(drift vs f32: {drift:.1e})")

# store_dtype=f32 is BIT-identical to the unquantized path, so flipping
# precision off is always safe; rm.astype_store(jnp.bfloat16) converts a
# live matrix.  The planner exposes the same decision offline — pass the
# solve tolerance in the context and explain() prints the admitted
# formats, the modeled bytes of each, and what the pick saved:
#
#     p = planner.plan("grad", {"m": 8192, "n": 2048}, machine=machine.V5E,
#                      context={"tol": 1e-4, "axes": (8,)})
#     p.precision        -> "bf16"
#     p.explain()        -> "... precision: bf16 (saved 33554432 modeled
#                            bytes vs f32)"

# --- Planning & calibration -----------------------------------------------
# Every dispatch decision above — kernel block configs, BSR-vs-dense,
# fused-vs-unfused, the SVD mode — went through ONE code path: the
# execution planner (launch/planner.py), pricing alternatives against one
# MachineModel (launch/machine.py).  plan() answers "what would run, and
# why" for any shape without running anything:
from repro.launch import planner

p = planner.plan("sparse_matmul",
                 {"m": 4096, "n": 2048, "nx": 1, "ell": 2, "bs": 128})
print(f"\nsparse shard -> {p.choice}  (modeled {p.cost_s * 1e6:.1f} us)")
print(p.explain())                       # roofline terms + alternatives

p = planner.plan("svd", {"m": 100_000, "n": 4096, "k": 32},
                 context={"kind": "row"})
print(p.explain())                       # why gram beats lanczos here

# Calibration closes the loop: benchmark sweeps record measured timings,
# MachineModel.calibrate() regresses effective MXU/HBM efficiencies per
# backend+dtype from them (least squares on the roofline terms), and the
# fit persists next to the autotune config cache, where every later
# plan() prefers it:
#
#     PYTHONPATH=src python -m benchmarks.bench_planner
#
# emits BENCH json with modeled-vs-measured error before/after (the
# "tightened" line), writes machine.json, and re-plans a golden shape to
# show `calibrated: true`.  `python -m benchmarks.run --only planner`
# runs the same thing inside the benchmark harness.

# --- Multi-host execution: pricing the collectives ------------------------
# On one host the psum at the end of gram/fused_grad/rmatvec is free; on a
# pod it dominates.  Passing the mesh topology to plan() prices the
# collective end-to-end — ring vs tree reduction chosen by payload and
# axis sizes, and a chunk count scheduled when splitting the shard into
# column segments lets segment k's partial psum overlap segment k+1's
# compute:
from repro.launch import machine

p = planner.plan("gram", {"m": 1_000_000 // 64, "n": 1024},
                 machine=machine.V5E, context={"axes": (64,)})
print(f"\ngram on 64 devices -> {p.choice} "
      f"(chunks={p.blocks['chunks']})")
print(p.explain())        # the "comm:" line shows the modeled psum share

# The distmat methods consult the same plan: gram()/fused_grad() default
# to chunks="auto" (eager single-dispatch whenever the modeled psum is not
# worth hiding — always on one device) and accept an explicit chunk count.
# Chunked and eager results are BIT-identical; only the dispatch schedule
# changes.  telemetry spans around each collective feed plan-vs-actual
# records, so MachineModel.calibrate() can fit link efficiencies from
# production traces or from the sweep in:
#
#     PYTHONPATH=src python -m benchmarks.run --only collectives
#
# (modeled-vs-measured psum time by payload size and device count, plus a
# link_eff fit demo; CI uploads the BENCH json as a workflow artifact.)
_ = rm.gram(chunks=4)     # forced overlap: same bits as rm.gram(chunks=1)

# --- Serving: many users, one A-pass --------------------------------------
# launch/serve.py turns the solver into a frontend.  Requests that share a
# design matrix are grouped, and the WHOLE group advances with ONE fused
# multi-RHS A-pass per solver iteration — three users below cost the same
# matrix traffic per iteration as one.  The queue is continuously batched
# (requests join/leave between iterations, not between solves) and
# admission is planner-priced: plan() prices each request, the scheduler
# packs a device-time budget per step, joining an active group is free.
from repro import api
from repro.launch.serve import SolverServer

server = SolverServer(slots=8)
b1, b2, b3 = (jnp.asarray((A @ rng.normal(size=64)).astype(np.float32))
              for _ in range(3))
ids = [server.submit(api.SolveRequest(A=A, b=bi, loss="quad",
                                      method="gra", tol=1e-6))
       for bi in (b1, b2, b3)]
server.run()
infos = [server.result(i).info for i in ids]
print(f"\nserved {len(ids)} requests in one group "
      f"(plan: {infos[0]['plan']}); amortized A-passes per request: "
      f"{[int(i['a_passes']) for i in infos]} — one fused pass per "
      f"iteration covers the whole group")

# Benchmark it as a service (requests/sec, p50/p99 latency, batched-vs-
# serial throughput under a shared-matrix trace):
#
#     PYTHONPATH=src python -m benchmarks.run --only serve

# --- Fault tolerance & resumable solves ------------------------------------
# The elastic executor (core/optim/elastic.py) runs group solves one
# jitted iteration at a time on the host, which is what makes them
# interruptible: between iterations it can checkpoint, retry a transient
# failure (rollback is free — the step is only committed after it
# validates), or re-mesh the matrix off a straggling/lost shard detected
# by train/straggler.py's ShardMonitor.  Solver state lives on the
# driver, so a re-mesh moves only the matrix and the iteration counter
# never rewinds.  train/faults.py injects all three fault kinds
# deterministically for tests and benchmarks.

# Resumable solves: checkpoint_dir snapshots optimizer state every
# `checkpoint_every` iterations (async, fsync'd, torn-write-safe);
# resume=True restores the latest snapshot bit-compatibly and continues.
import tempfile

ckdir = tempfile.mkdtemp()
r1 = api.solve(api.SolveRequest(A=A, b=jnp.asarray(b), loss="quad",
                                tol=0.0, max_iters=10,
                                checkpoint_dir=ckdir, checkpoint_every=5))
r2 = api.solve(api.SolveRequest(A=A, b=jnp.asarray(b), loss="quad",
                                tol=0.0, max_iters=20,
                                checkpoint_dir=ckdir, resume=True))
print(f"\nresumable solve: run 1 stopped at {r1.info['iterations']} "
      f"({r1.info['checkpoint_saves']} checkpoints); run 2 resumed from "
      f"{r2.info['resumed_from']} and reached {r2.info['iterations']} — "
      f"bit-identical to an uninterrupted run")

# Serving degrades gracefully instead of failing: per-request deadline_s
# and max_iters return the best iterate with converged=False and a typed
# info["degraded"] reason ("deadline" / "max_iterations" / "fault");
# a full queue sheds load with an api.Overloaded result instead of
# growing without bound.
r3 = api.solve(api.SolveRequest(A=A, b=jnp.asarray(b), loss="quad",
                                tol=0.0, max_iters=5, deadline_s=30.0))
print(f"degraded solve: converged={r3.info['converged']} "
      f"(reason: {r3.info['degraded']}) — best iterate still returned")

# The fault-injection suite (tests/test_fault_tolerance.py, marker
# `fault`) exercises straggler→re-mesh→parity, kill→resume→bit-equality
# and deadline retirement on 1- and 8-device meshes; the recovery
# overhead (throughput under 0/1/2 injected stragglers, straggler-onset→
# re-mesh latency) is benchmarked by the serve_recovery BENCH line of
#
#     PYTHONPATH=src python -m benchmarks.run --only serve

# --- Observability: spans, metrics, plan-vs-actual -------------------------
# Every solve can be traced (launch/telemetry.py): telemetry=True runs the
# request under a fresh Recorder and attaches info["trace"] — per-phase
# span timings (iteration / fused A-pass / checkpoint / re-mesh), server
# queue-wait and latency histograms, and one plan-vs-actual record per
# engine step tying the planner's modeled cost to the measured wall time.
# Off by default: the disabled path is shared no-op singletons.
from repro.launch import telemetry

rec = telemetry.Recorder()
rt = api.solve(api.SolveRequest(A=A, b=jnp.asarray(b), loss="quad",
                                tol=0.0, max_iters=10,
                                checkpoint_dir=ckdir, telemetry=rec))
trace = rt.info["trace"]
print(f"\ntraced solve: {trace['spans']} spans; per-phase totals:",
      {k: round(v["total_s"], 4) for k, v in trace["phases"].items()})

# The same recorder scopes a whole serving session: build the server under
# telemetry.recording() and its scheduler actions (admit / retire / shed)
# are spanned, queue-wait/latency histograms filled, and degraded
# retirements counted per reason (server.stats["degraded"]).
with telemetry.recording(rec):
    traced_srv = SolverServer(slots=2)
    tid = traced_srv.submit(api.SolveRequest(A=A, b=jnp.asarray(b1),
                                             loss="quad", tol=1e-6))
    traced_srv.run()
lat = traced_srv.tel.histogram("serve.latency_s")
print(f"served p50 latency: {lat.percentile(0.5) * 1e3:.1f} ms "
      f"(stats: {traced_srv.stats})")

# Exports: rec.export_jsonl(path) writes one JSON event per line.  For a
# timeline, run the work under jax.profiler.trace(dir): every span is a
# "repro.<name>" annotation there, beside the device ops, with or without
# a recorder (open the directory in TensorBoard or at
# https://ui.perfetto.dev).  rec.calibration_records() feeds
# planner.calibrate() so the cost model learns from production traces —
# the same loop benchmarks/bench_serve.py --traced-demo packages for CI.
