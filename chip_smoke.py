#!/usr/bin/env python3
"""Smoke test of the main path on a TPU: api -> planner -> distmat -> kernel.

    python3 chip_smoke.py [--seed 0]     # one chip, every phase below
    python3 chip_smoke.py --chips 4      # the row-sharded path on a (4, 1) mesh

One chip runs four phases at data sizes users run:

  dense_solve   api.solve on a 1,048,576 x 1024 f32 RowMatrix (4 GiB):
                logistic + L1 and quad + L2, against plain FISTA
  dense_svd     api.svd(k=10, mode="auto") on that matrix (Gram mode) and
                on a 262,144 x 4096 one (4 GiB), against eigh(AᵀA)
  sparse        a 1,048,576 x 65,536 SparseRowMatrix of ~64M nonzeros in
                randomly placed dense 128 x 128 tiles: api.solve
                (logistic + L1) against FISTA over a segment_sum matvec of
                the COO entries, api.svd(k=5, mode="lanczos") against a
                plain Lanczos over the same matvec
  serving       one SolverServer(slots=8) answering 8 quad + L1 requests
                that share the dense matrix, each against its own api.solve

--chips 4 runs only the collective path, at the one-chip sizes on each of
four chips: a RowMatrix — gram eager and chunked, fused_grad, one
api.solve — and a SparseRowMatrix of ~64M nonzeros per chip, fused_grad.
Each is checked against the same data streamed shard by shard and reduced
on one device (the solve against FISTA over those per-shard products).

All data is drawn on the device from --seed.  References use jnp at
precision HIGHEST and nothing of repro.kernels.  Each phase prints one JSON
line: seconds with compile time apart, the error against the reference and
its tolerance, the Pallas kernels in the phase's lowered programs (each
phase must contain the kernels it exists to run), where its block configs
came from, and the device's peak memory.  The last line is
{"ok": true, "device": {...}}; a failed check raises instead, and the script
exits non-zero, printing no such line, when JAX finds no TPU or when the
repository's src/ is not next to this file.
"""
from __future__ import annotations

import argparse
import json
import re
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
HI = "highest"     # reference matmul precision

# f32 tolerances, relative, per check.
TOL_X = 1e-3       # solution vs reference solution
TOL_OBJ = 1e-4     # objective vs reference objective
TOL_SIGMA = 1e-4   # Gram-mode singular values vs eigh(AᵀA)
TOL_LANCZOS = 1e-3 # Lanczos singular values vs the reference Lanczos
TOL_U = 1e-4       # ‖A·V − U·Σ‖ / ‖A·V‖ of the returned factors
TOL_SHARDED = 1e-4 # 4-chip Gram / gradient vs the one-device reference
SOLVE_TOL = 1e-6   # solver stopping tolerance (reference runs to the same)


class Phase:
    """Times one phase, splits off compile time, collects the Pallas
    kernels of the programs it lowered, and prints its line."""

    def __init__(self, name, ctx, required):
        self.name, self.ctx, self.required = name, ctx, set(required)
        self.fields = {}

    def __enter__(self):
        self.ctx["compile_s"] = 0.0
        self.seen = set(self.ctx["irdir"].iterdir())
        self.stats0 = dict(self.ctx["autotune"].stats)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            return False
        wall = time.perf_counter() - self.t0
        kernels = set()
        for f in set(self.ctx["irdir"].iterdir()) - self.seen:
            kernels |= set(re.findall(r"repro_[a-z_]+", f.read_text(
                errors="replace")))
        stats = {k: v - self.stats0.get(k, 0)
                 for k, v in self.ctx["autotune"].stats.items()}
        dev = self.ctx["device"]
        line = {"phase": self.name, "seconds": round(wall, 3),
                "compile_s": round(self.ctx["compile_s"], 3),
                "run_s": round(wall - self.ctx["compile_s"], 3),
                **self.fields, "kernels": sorted(kernels),
                "block_configs": {
                    "autotune_stats": stats,
                    "user_cache": self.ctx["user_cache"],
                    "machine_json": self.ctx["machine_json"]},
                "peak_bytes_in_use": (dev.memory_stats() or {}).get(
                    "peak_bytes_in_use")}
        print(json.dumps(line), flush=True)
        missing = self.required - kernels
        if missing:
            raise AssertionError(f"{self.name}: lowered programs lack the "
                                 f"Pallas kernels {sorted(missing)}")
        return False

    def check(self, label, err, tol):
        self.fields[label] = {"err": float(err), "tol": tol}
        if not err <= tol:
            raise AssertionError(f"{self.name}/{label}: error {err:.3e} "
                                 f"exceeds {tol:.0e}")


def free(*arrays):
    """Release device buffers now rather than at garbage collection."""
    for a in arrays:
        if a is not None and not a.is_deleted():
            a.delete()


# -- plain references (jnp, HIGHEST; no repro code) --------------------------

def refs():
    import jax
    import jax.numpy as jnp

    def loss_grad(kind, z, b):
        if kind == "logistic":
            return jnp.sum(jnp.logaddexp(0.0, -b * z)), -b * jax.nn.sigmoid(
                -b * z)
        r = z - b
        return 0.5 * jnp.sum(r * r), r

    def prox(kind, x, t, lam):
        if kind == "l1":
            return jnp.sign(x) * jnp.maximum(jnp.abs(x) - t * lam, 0.0)
        return x / (1.0 + t * lam)

    def reg_value(kind, x, lam):
        if kind == "l1":
            return lam * jnp.sum(jnp.abs(x))
        return 0.5 * lam * jnp.sum(x * x)

    def fista(mv, rmv, data, b, loss, reg, lam, L, n, max_iters=5000):
        """FISTA with step 1/L, stopped like the repo's solvers: relative
        step ‖Δx‖ / max(1, ‖x‖) below SOLVE_TOL."""
        @jax.jit
        def run(data, b, lam, L):
            def body(s):
                k, x, y, t, _ = s
                _, gz = loss_grad(loss, mv(data, y), b)
                x1 = prox(reg, y - rmv(data, gz) / L, 1.0 / L, lam)
                t1 = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
                y1 = x1 + ((t - 1.0) / t1) * (x1 - x)
                rel = jnp.linalg.norm(x1 - x) / jnp.maximum(
                    1.0, jnp.linalg.norm(x1))
                return k + 1, x1, y1, t1, rel < SOLVE_TOL

            x0 = jnp.zeros((n,), jnp.float32)
            init = (0, x0, x0, jnp.float32(1.0), False)
            k, x, *_ = jax.lax.while_loop(
                lambda s: (~s[4]) & (s[0] < max_iters), body, init)
            f, _ = loss_grad(loss, mv(data, x), b)
            return x, f + reg_value(reg, x, lam), k
        return run(data, b, lam, L)

    def sq_norm(mv, rmv, data, n, iters=40):
        """‖A‖₂² by power iteration on AᵀA."""
        @jax.jit
        def run(data):
            def body(_, v):
                w = rmv(data, mv(data, v))
                return w / jnp.linalg.norm(w)
            v = jax.random.normal(jax.random.PRNGKey(1), (n,), jnp.float32)
            v = jax.lax.fori_loop(0, iters, body, v / jnp.linalg.norm(v))
            return jnp.linalg.norm(mv(data, v)) ** 2
        return float(run(data))

    def objective(mv, data, b, loss, reg, lam, x):
        f, _ = loss_grad(loss, mv(data, x), b)
        return f + reg_value(reg, x, lam)

    return loss_grad, fista, sq_norm, objective


def dense_ops():
    import jax.numpy as jnp
    return (lambda A, x: jnp.dot(A, x, precision=HI),
            lambda A, u: jnp.dot(u, A, precision=HI))


def coo_ops(m, n, tile):
    """Ax and Aᵀu as segment sums over the COO entries (rows, cols, vals).
    The generator emits them tile by tile, row-major, so inside a tile
    each run of `tile` consecutive entries shares its row and entries
    `tile` apart share their column: the operand is gathered once per
    tile row (column), the products summed along the run, and the sums
    segment-summed into their row (column)."""
    import jax

    def tiled(d):
        rows, cols, vals = d
        return (rows.reshape(-1, tile, tile)[:, :, 0],     # (tiles, tile)
                cols.reshape(-1, tile, tile)[:, 0, :],     # (tiles, tile)
                vals.reshape(-1, tile, tile))

    def mv(d, x):
        r, c, v = tiled(d)
        part = (v * x[c][:, None, :]).sum(2)
        return jax.ops.segment_sum(part.reshape(-1), r.reshape(-1), m)

    def rmv(d, u):
        r, c, v = tiled(d)
        part = (v * u[r][:, :, None]).sum(1)
        return jax.ops.segment_sum(part.reshape(-1), c.reshape(-1), n)

    return mv, rmv


def rel(a, b):
    import jax.numpy as jnp
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def factor_residual(AV, U, s):
    """‖A·V − U·Σ‖_F / ‖A·V‖_F, with A·V from the reference: a wrong U (the
    distributed multiply's kernel) shows here, whatever σ says."""
    import jax.numpy as jnp
    return rel(U.rows[:AV.shape[0]] * jnp.asarray(s)[None, :], AV)


def rel_scalar(a, b):
    return abs(float(a) - float(b)) / abs(float(b))


def lipschitz(loss, sq):
    """The smooth part's gradient Lipschitz bound, with 5% headroom over
    the power-iteration estimate of ‖A‖₂²."""
    return 1.05 * sq * (0.25 if loss == "logistic" else 1.0)


def l1_weight(rmv, data, b, loss, loss_grad):
    """λ at a tenth of ‖∇f(0)‖∞: a sparse but non-trivial solution."""
    import jax.numpy as jnp
    z0 = jnp.zeros_like(b)
    _, g0 = loss_grad(loss, z0, b)
    return float(0.1 * jnp.max(jnp.abs(rmv(data, g0))))


# -- data (drawn on the device) ----------------------------------------------

def dense_data(key, m, n):
    """A standard normal (m x n) matrix and a 20%-dense planted x."""
    import jax
    import jax.numpy as jnp
    ka, kx, ks = jax.random.split(key, 3)
    A = jax.jit(lambda k: jax.random.normal(k, (m, n), jnp.float32))(ka)
    x_true = jax.random.normal(kx, (n,), jnp.float32) * (
        jax.random.uniform(ks, (n,)) < 0.2) / jnp.sqrt(n * 0.2)
    return A, x_true


def tile_entries(key, m, n, tiles, tile):
    """COO entries of `tiles` dense tile x tile blocks at random distinct
    block-aligned positions (row-major inside each tile)."""
    import jax
    import jax.numpy as jnp
    kp, kv = jax.random.split(key)
    gr, gc = m // tile, n // tile

    @jax.jit
    def draw(kp, kv):
        pos = jax.random.choice(kp, gr * gc, (tiles,), replace=False)
        bi, bj = pos // gc, pos % gc
        r = jnp.arange(tile)
        rows = (bi[:, None, None] * tile + r[None, :, None]) \
            + jnp.zeros((1, 1, tile), jnp.int32)
        cols = (bj[:, None, None] * tile + r[None, None, :]) \
            + jnp.zeros((1, tile, 1), jnp.int32)
        vals = jax.random.normal(kv, (tiles, tile, tile), jnp.float32)
        return (rows.reshape(-1).astype(jnp.int32),
                cols.reshape(-1).astype(jnp.int32), vals.reshape(-1))
    return draw(kp, kv)


# -- one-chip phases ---------------------------------------------------------

def phase_dense_solve(ctx, A, rm, x_true, key):
    import jax
    import jax.numpy as jnp
    from repro import api
    loss_grad, fista, sq_norm, _ = refs()
    mv, rmv = dense_ops()
    m, n = A.shape
    with Phase("dense_solve", ctx, {"repro_fused_grad"}) as ph:
        sq = sq_norm(mv, rmv, A, n)
        z = mv(A, x_true)
        noise = jax.random.normal(key, (m,), jnp.float32)
        for loss, reg, b in (
                ("logistic", "l1", jnp.sign(z + 0.5 * noise)),
                ("quad", "l2", z + 0.1 * noise)):
            L = lipschitz(loss, sq)
            lam = l1_weight(rmv, A, b, loss, loss_grad) if reg == "l1" \
                else 1e-3 * sq
            res = api.solve(api.SolveRequest(
                A=rm, b=b, loss=loss, reg=reg, lam=lam, L0=L,
                tol=SOLVE_TOL, max_iters=5000))
            info = res.info
            if not (bool(info["converged"]) and info["degraded"] is None
                    and info["plan"] == "fused"):
                raise AssertionError(f"dense_solve {loss}: {info}")
            x_ref, obj_ref, k_ref = fista(mv, rmv, A, b, loss, reg, lam, L, n)
            ph.check(f"{loss}_{reg}_x", rel(res.x, x_ref), TOL_X)
            ph.check(f"{loss}_{reg}_objective",
                     rel_scalar(info["objective"], obj_ref), TOL_OBJ)
            ph.fields[f"{loss}_{reg}_iterations"] = [int(info["iterations"]),
                                                     int(k_ref)]
            free(b)
        free(z, noise)


def phase_dense_svd(ctx, key, A, rm, sizes):
    import jax.numpy as jnp
    import numpy as np
    from repro import api
    from repro.core.distmat import RowMatrix
    with Phase("dense_svd", ctx, {"repro_tsgram", "repro_gemm"}) as ph:
        k = 10
        for _ in range(2):
            if A is None:
                A, _ = dense_data(key, sizes["wide_m"], sizes["wide_n"])
                rm = RowMatrix.create(A)
            label = "x".join(map(str, A.shape))
            res = api.svd(api.SvdRequest(A=rm, k=k, mode="auto"))
            U, s, V = res.factors
            if res.info["plan"] != "gram" or U.shape != A.shape[:1] + (k,):
                raise AssertionError(f"dense_svd {label}: {res.info}")
            s_ref = np.sqrt(host_eigvalsh(jnp.dot(A.T, A, precision=HI))[:k])
            ph.check(f"{label}_sigma",
                     float(np.max(np.abs(np.asarray(s) - s_ref) / s_ref)),
                     TOL_SIGMA)
            AV = jnp.dot(A, jnp.asarray(V)[:A.shape[1]], precision=HI)
            ph.check(f"{label}_u", factor_residual(AV, U, s), TOL_U)
            free(U.rows, rm.rows, A, AV)
            A = rm = None


def host_eigvalsh(M):
    """Eigenvalues of a symmetric device matrix, largest first: LAPACK in
    float64 on the host (the TPU's own eigh compiles for minutes at n in
    the thousands)."""
    import numpy as np
    return np.linalg.eigvalsh(np.asarray(M, np.float64))[::-1]


def ref_lanczos(op, data, n, k, steps, key):
    """Top-k eigenvalues of the PSD operator v -> op(data, v): plain
    Lanczos with full reorthogonalization, Ritz values of the
    tridiagonal."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(data, key):
        v = jax.random.normal(key, (n,), jnp.float32)
        V = jnp.zeros((steps + 1, n), jnp.float32).at[0].set(
            v / jnp.linalg.norm(v))

        def body(j, s):
            V, alpha, beta = s
            w = op(data, V[j])
            for _ in range(2):
                w = w - jnp.dot(V.T, jnp.dot(V, w, precision=HI),
                                precision=HI)
            a = jnp.dot(V[j], op(data, V[j]), precision=HI)
            bnorm = jnp.linalg.norm(w)
            return (V.at[j + 1].set(w / bnorm), alpha.at[j].set(a),
                    beta.at[j].set(bnorm))

        V, alpha, beta = jax.lax.fori_loop(
            0, steps, body, (V, jnp.zeros(steps), jnp.zeros(steps)))
        return jnp.diag(alpha) + jnp.diag(beta[:-1], 1) \
            + jnp.diag(beta[:-1], -1)
    return host_eigvalsh(run(data, key))[:k]


def phase_sparse(ctx, key, sizes):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import api
    from repro.core.distmat import SparseRowMatrix
    loss_grad, fista, sq_norm, _ = refs()
    m, n = sizes["sparse_m"], sizes["sparse_n"]
    kt, kx, kn, kl = jax.random.split(key, 4)
    required = {"repro_fused_grad_bsr", "repro_bsr_matvec",
                "repro_bsr_rmatmul"}
    with Phase("sparse", ctx, required) as ph:
        coo = tile_entries(kt, m, n, sizes["tiles"], sizes["tile"])
        t0 = time.perf_counter()
        A = SparseRowMatrix.from_entries(*coo, (m, n))
        ph.fields.update(build_s=round(time.perf_counter() - t0, 3),
                         nnz=A.nnz, bs=A.bs, ell=A.ell,
                         stored_bytes=A.data.nbytes)
        mv, rmv = coo_ops(m, n, sizes["tile"])
        sq = sq_norm(mv, rmv, coo, n)
        x_true = jax.random.normal(kx, (n,), jnp.float32) / jnp.sqrt(
            sizes["tile"] * 8.0)
        z = mv(coo, x_true)
        b = jnp.sign(z + 0.5 * jax.random.normal(kn, (m,), jnp.float32))
        L = lipschitz("logistic", sq)
        lam = l1_weight(rmv, coo, b, "logistic", loss_grad)
        res = api.solve(api.SolveRequest(
            A=A, b=b, loss="logistic", reg="l1", lam=lam, L0=L,
            tol=SOLVE_TOL, max_iters=5000))
        info = res.info
        if not (bool(info["converged"]) and info["degraded"] is None
                and info["plan"] == "fused"):
            raise AssertionError(f"sparse solve: {info}")
        x_ref, obj_ref, k_ref = fista(mv, rmv, coo, b, "logistic", "l1",
                                      lam, L, n)
        ph.check("logistic_l1_x", rel(res.x, x_ref), TOL_X)
        ph.check("logistic_l1_objective",
                 rel_scalar(info["objective"], obj_ref), TOL_OBJ)
        ph.fields["logistic_l1_iterations"] = [int(info["iterations"]),
                                               int(k_ref)]
        k = 5
        res = api.svd(api.SvdRequest(A=A, k=k, mode="lanczos"))
        U, s, V = res.factors
        if not bool(res.info["converged"]):
            raise AssertionError(f"sparse svd: {res.info}")
        s_ref = np.sqrt(ref_lanczos(lambda d, v: rmv(d, mv(d, v)), coo, n, k,
                                    160, kl))
        ph.check("lanczos_sigma",
                 float(np.max(np.abs(np.asarray(s) - s_ref) / s_ref)),
                 TOL_LANCZOS)
        V = jnp.asarray(V)[:n]
        AV = jnp.stack([mv(coo, V[:, i]) for i in range(k)], axis=1)
        ph.check("lanczos_u", factor_residual(AV, U, s), TOL_U)
        free(U.rows, A.data, A.cols, z, b, AV, *coo)


def phase_serving(ctx, A, rm, key):
    import jax
    import jax.numpy as jnp
    from repro import api
    from repro.launch.serve import SolverServer
    loss_grad, _, sq_norm, _ = refs()
    mv, rmv = dense_ops()
    m, n = A.shape
    slots = 8
    with Phase("serving", ctx, {"repro_fused_grad_multi"}) as ph:
        L = lipschitz("quad", sq_norm(mv, rmv, A, n))
        reqs = []
        for i, k in enumerate(jax.random.split(key, slots)):
            kx, kn = jax.random.split(k)
            x_i = jax.random.normal(kx, (n,), jnp.float32) * (
                jax.random.uniform(kx, (n,)) < 0.2)
            b = mv(A, x_i) + 0.1 * jax.random.normal(kn, (m,), jnp.float32)
            reqs.append(dict(A=rm, b=b, loss="quad", reg="l1",
                             lam=l1_weight(rmv, A, b, "quad", loss_grad),
                             L0=L, tol=SOLVE_TOL, max_iters=5000))
        server = SolverServer(slots=slots)
        ids = [server.submit(api.SolveRequest(**r)) for r in reqs]
        server.run()
        worst = 0.0
        for rid, r in zip(ids, reqs):
            got = server.result(rid)
            if got is None or not bool(got.info["converged"]) \
                    or got.info.get("degraded"):
                raise AssertionError(f"serving {rid}: "
                                     f"{None if got is None else got.info}")
            single = api.solve(api.SolveRequest(**r))
            worst = max(worst, rel(got.x, single.x))
        ph.check("served_vs_single_x", worst, TOL_X)
        ph.fields["a_passes"] = server.stats["a_passes"]


# -- four chips --------------------------------------------------------------

def phase_sharded(ctx, key, sizes):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import api
    from repro.core.distmat import RowMatrix, SparseRowMatrix
    from repro.core.distmat.types import make_mesh
    from repro.core.tfocs import LinopMatrix, SmoothLogLoss, row_separable
    chips = 4
    devs = jax.devices()
    if len(devs) < chips:
        raise RuntimeError(f"--chips {chips} needs {chips} devices, "
                           f"JAX sees {len(devs)}")
    mesh = make_mesh((chips, 1), ("data", "model"), devices=devs[:chips])
    dev0 = devs[0]
    m, n = chips * sizes["dense_m"], sizes["dense_n"]
    loss_grad, fista, sq_norm, objective = refs()

    def on_devices(arr):
        return {s.device for s in arr.addressable_shards}

    def shards(arr):
        """Each shard's rows, in row order, moved to device 0 in turn (the
        loops below drop each copy before the next is made; the shard that
        lives on device 0 is not copied)."""
        for s in sorted(arr.addressable_shards, key=lambda s: s.index[0].start
                        or 0):
            yield jax.device_put(s.data, dev0)

    required = {"repro_tsgram", "repro_fused_grad", "repro_fused_grad_bsr"}
    with Phase("sharded", ctx, required) as ph:
        ka, kx, kn, ks = jax.random.split(key, 4)
        rows = jax.jit(lambda k: jax.random.normal(k, (m, n), jnp.float32),
                       out_shardings=NamedSharding(mesh, P("data", None)))(ka)
        rm = RowMatrix.create(rows, mesh)
        if len(on_devices(rm.rows)) != chips:
            raise AssertionError(f"RowMatrix on {on_devices(rm.rows)}")
        G_ref = None
        for blk in shards(rm.rows):
            part = jnp.dot(blk.T, blk, precision=HI)
            G_ref = part if G_ref is None else G_ref + part
            del blk
        for c in (1, 2):
            G = jax.device_put(rm.gram(chunks=c), dev0)
            ph.check(f"gram_chunks{c}", rel(G, G_ref), TOL_SHARDED)
        free(G_ref)
        # fused_grad (logistic) against per-shard products summed on dev0
        # Inputs stay uncommitted so the repo may place them on its mesh;
        # they live on device 0, where the reference runs.
        x = jax.random.normal(kx, (n,), jnp.float32) / np.sqrt(n)
        y = jnp.sign(jax.random.normal(kn, (m,), jnp.float32))
        linop = LinopMatrix(rm)
        sep = row_separable(SmoothLogLoss(y=linop.pad_data(y),
                                          weights=linop.row_weights()))
        f, g, _ = rm.fused_grad(x, sep)
        f_ref, g_ref, off = 0.0, 0.0, 0
        for blk in shards(rm.rows):
            fb, gz = loss_grad("logistic", jnp.dot(blk, x, precision=HI),
                               y[off:off + blk.shape[0]])
            f_ref, g_ref = f_ref + fb, g_ref + jnp.dot(gz, blk, precision=HI)
            off += blk.shape[0]
            del blk
        ph.check("fused_grad_f", rel_scalar(f, f_ref), TOL_SHARDED)
        ph.check("fused_grad_g", rel(jax.device_put(g, dev0), g_ref),
                 TOL_SHARDED)

        # one api.solve (quad + L2) against FISTA over per-shard products
        local = [s.data for s in sorted(rm.rows.addressable_shards,
                                        key=lambda s: s.index[0].start or 0)]

        def mv(data, v):
            return jnp.concatenate([jax.device_put(
                jnp.dot(a, jax.device_put(v, a.device), precision=HI), dev0)
                for a in data])

        def rmv(data, u):
            out, off = 0.0, 0
            for a in data:
                uu = jax.device_put(u[off:off + a.shape[0]], a.device)
                out = out + jax.device_put(jnp.dot(uu, a, precision=HI), dev0)
                off += a.shape[0]
            return out

        b = np.asarray(mv(local, x) + 0.1 * jax.random.normal(
            ks, (m,), jnp.float32))
        sq = power_iteration(mv, rmv, local, n, dev0)
        L, lam = 1.05 * sq, 1e-3 * sq
        res = api.solve(api.SolveRequest(A=rm, b=b, loss="quad", reg="l2",
                                         lam=lam, L0=L, tol=SOLVE_TOL,
                                         max_iters=5000))
        if not (bool(res.info["converged"]) and res.info["plan"] == "fused"):
            raise AssertionError(f"sharded solve: {res.info}")
        x_ref = fista_host(mv, rmv, local, jnp.asarray(b), lam, L, n, dev0)
        ph.check("solve_quad_l2_x", rel(jax.device_put(res.x, dev0), x_ref),
                 TOL_X)
        free(rm.rows, rows)

        # SparseRowMatrix on the same mesh, each chip's rows holding the
        # one-chip phase's tiles: drawn shard by shard on the shard's own
        # device, where its part of the reference runs too.
        per, sn, tile = sizes["sparse_m"], sizes["sparse_n"], sizes["tile"]
        sm = chips * per
        parts = [tile_entries(jax.device_put(kc, d), per, sn, sizes["tiles"],
                              tile)
                 for kc, d in zip(jax.random.split(ks, chips), devs[:chips])]
        coo = (np.concatenate([np.asarray(p[0]) + c * per
                               for c, p in enumerate(parts)]),
               *(np.concatenate([np.asarray(p[i]) for p in parts])
                 for i in (1, 2)))
        t0 = time.perf_counter()
        S = SparseRowMatrix.from_entries(*coo, (sm, sn), bs=tile, mesh=mesh)
        build_s = time.perf_counter() - t0
        del coo
        if len(on_devices(S.data)) != chips:
            raise AssertionError(f"SparseRowMatrix on {on_devices(S.data)}")
        ys = jnp.sign(jax.random.normal(kn, (sm,), jnp.float32))
        xs = jax.random.normal(kx, (sn,), jnp.float32) / np.sqrt(1024.0)
        sl = LinopMatrix(S)
        seps = row_separable(SmoothLogLoss(y=sl.pad_data(ys),
                                           weights=sl.row_weights()))
        f, g, _ = S.fused_grad(xs, seps)
        cmv, crmv = coo_ops(per, sn, tile)
        f_ref, g_ref = 0.0, 0.0
        for c, p in enumerate(parts):
            d = p[0].device
            fc, gz = loss_grad("logistic",
                               cmv(p, jax.device_put(xs, d)),
                               jax.device_put(ys[c * per:(c + 1) * per], d))
            f_ref = f_ref + jax.device_put(fc, dev0)
            g_ref = g_ref + jax.device_put(crmv(p, gz), dev0)
        ph.check("sparse_fused_grad_f", rel_scalar(f, f_ref), TOL_SHARDED)
        ph.check("sparse_fused_grad_g", rel(jax.device_put(g, dev0), g_ref),
                 TOL_SHARDED)
        ph.fields.update(rows=m, sparse_rows=sm, nnz=S.nnz, bs=S.bs,
                         ell=S.ell, build_s=round(build_s, 3))
        free(S.data, S.cols, *(a for p in parts for a in p))


def power_iteration(mv, rmv, data, n, dev, iters=40):
    """‖A‖₂² by power iteration on AᵀA, one operator call at a time."""
    import jax
    import jax.numpy as jnp
    v = jax.device_put(jax.random.normal(jax.random.PRNGKey(1), (n,),
                                         jnp.float32), dev)
    for _ in range(iters):
        w = rmv(data, mv(data, v / jnp.linalg.norm(v)))
        v = w
    v = v / jnp.linalg.norm(v)
    return float(jnp.linalg.norm(mv(data, v)) ** 2)


def fista_host(mv, rmv, data, b, lam, L, n, dev, max_iters=5000):
    """Quad + L2 FISTA driven from the host (the operator spans devices)."""
    import jax.numpy as jnp
    import jax
    x = y = jax.device_put(jnp.zeros((n,), jnp.float32), dev)
    t = 1.0
    for _ in range(max_iters):
        g = rmv(data, mv(data, y) - b)
        x1 = (y - g / L) / (1.0 + lam / L)
        t1 = 0.5 * (1.0 + (1.0 + 4.0 * t * t) ** 0.5)
        y = x1 + ((t - 1.0) / t1) * (x1 - x)
        done = float(jnp.linalg.norm(x1 - x) / jnp.maximum(
            1.0, jnp.linalg.norm(x1))) < SOLVE_TOL
        x, t = x1, t1
        if done:
            break
    return x


# -- main --------------------------------------------------------------------

SIZES = {"dense_m": 1 << 20, "dense_n": 1024,
         "wide_m": 1 << 18, "wide_n": 4096,
         "sparse_m": 1 << 20, "sparse_n": 1 << 16, "tiles": 4096,
         "tile": 128}


def run(seed: int, chips: int, sizes=SIZES) -> dict:
    import jax
    from repro import compile_cache
    from repro.core.distmat import RowMatrix
    from repro.kernels import autotune
    from repro.launch import machine

    compile_cache.enable()
    dev = jax.devices()[0]
    irdir = Path(tempfile.mkdtemp(prefix=".smoke_ir_", dir=ROOT))
    jax.config.update("jax_dump_ir_to", str(irdir))
    ctx = {"irdir": irdir, "device": dev, "autotune": autotune,
           "compile_s": 0.0,
           "user_cache": str(autotune.user_cache_path())
           if autotune.user_cache_path().exists() else None,
           "machine_json": str(machine.calibration_path())
           if machine.calibration_path().exists() else None}

    def on_compile(event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            ctx["compile_s"] += duration

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    key = jax.random.PRNGKey(seed)
    k_dense, k_solve, k_wide, k_sparse, k_serve = jax.random.split(key, 5)
    try:
        if chips == 4:
            phase_sharded(ctx, key, sizes)
        else:
            A, x_true = dense_data(k_dense, sizes["dense_m"],
                                   sizes["dense_n"])
            rm = RowMatrix.create(A)
            phase_dense_solve(ctx, A, rm, x_true, k_solve)
            phase_serving(ctx, A, rm, k_serve)
            phase_dense_svd(ctx, k_wide, A, rm, sizes)
            phase_sparse(ctx, k_sparse, sizes)
    finally:
        jax.config.update("jax_dump_ir_to", "")
        shutil.rmtree(irdir, ignore_errors=True)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke.py: the repository's src/repro is not next to "
              "this file", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"chip_smoke.py: JAX found no TPU (platform {platform!r})",
              file=sys.stderr)
        return 2
    device = run(args.seed, args.chips)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
