"""Serving-frontend benchmark — requests/sec and tail latency under a
synthetic shared-matrix trace.

The trace is the multi-user regime the solver server exists for: K solve
requests against the SAME design matrix (distinct right-hand sides).  Two
deployments answer it:

  * ``serial``  — a 1-slot server: requests run one at a time, each paying
    its own A-passes (the no-batching baseline);
  * ``batched`` — a K-slot server: the group shares ONE fused multi-RHS
    A-pass per solver iteration (continuous batching, launch/serve).

Emits one ``BENCH {json}`` line per config with requests/sec for both,
p50/p99 submit→finish latency, the batched:serial throughput ratio, and
the counted group A-passes (grouped ≪ serial — the pass sharing is where
the throughput comes from).  Wired into ``run.py --only serve``; the
perf-smoke serving canary asserts the structural half (grouped A-passes <
serial A-passes) without timing anything.

A second ``BENCH`` line (suite ``serve_recovery``) measures the
fault-tolerance overhead: the same k-request group solved under 0, 1 and
2 injected straggler episodes (train.faults.FaultyLinop), each detected
by the ShardMonitor and healed by a mid-solve re-mesh.  It reports
requests/sec per straggler count and the recovery latency — wall seconds
from straggler onset to the completed re-mesh, re-JIT included.
"""
from __future__ import annotations

import json
import time

import numpy as np


def _trace(m: int, n: int, k: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    A = rng.normal(size=(m, n)).astype(np.float32)
    bs = [(A @ rng.normal(size=n) + 0.01 * rng.normal(size=m))
          .astype(np.float32) for _ in range(k)]
    return A, bs


def _serve(server, A, bs, *, tol: float = 1e-6, max_iters: int = 200):
    """Run the trace through `server`; returns (wall_s, latencies,
    group_a_passes, results).  Timing a LONG-LIVED server is the point:
    the first trace through a server compiles the group step closures, so
    callers warm the same server with the same matrix before timing (a
    serving deployment answers a stream, not a cold start)."""
    from repro import api
    passes0 = server.stats["a_passes"]
    events0 = len(server._events)
    t0 = time.perf_counter()
    ids = [server.submit(api.SolveRequest(A=A, b=b, loss="quad",
                                          method="gra", tol=tol,
                                          max_iters=max_iters))
           for b in bs]
    server.run()
    wall = time.perf_counter() - t0
    res = [server.result(i) for i in ids]
    assert all(r is not None for r in res)
    lats = sorted(t1 - t0_ for _, t0_, t1 in server._events[events0:])
    return wall, lats, server.stats["a_passes"] - passes0, res


def group_pass_counts(m: int = 200, n: int = 32, k: int = 4,
                      iters: int = 10) -> dict:
    """Structural A-pass comparison, no timing: a k-request group run to a
    fixed iteration count vs k sequential single-request runs on the same
    engine.  Deterministic — the perf-smoke serving canary asserts
    grouped < serial on these numbers."""
    import jax.numpy as jnp
    from repro import api
    from repro.core.tfocs import CountingLinop
    from repro.core.tfocs.linop import LinopMatrix
    from repro.launch.serve import GroupRunner

    A, bs = _trace(m, n, k, seed=1)

    def run(reqs_per_group):
        lin = CountingLinop(LinopMatrix(jnp.asarray(A)))
        runner = GroupRunner(lin, "quad", slots=max(reqs_per_group, 1))
        passes = 0
        for start in range(0, k, reqs_per_group):
            for b in bs[start:start + reqs_per_group]:
                runner.admit(api.SolveRequest(A=A, b=b, loss="quad",
                                              tol=0.0, max_iters=iters))
            while runner.busy():
                runner.step()
        return runner.a_passes, dict(lin.counts)

    grouped, gcounts = run(k)
    serial, scounts = run(1)
    return {"k": k, "iters": iters, "grouped_a_passes": grouped,
            "serial_a_passes": serial,
            "grouped_trace_counts": gcounts,
            "serial_trace_counts": scounts,
            "a_pass_ratio": serial / max(grouped, 1)}


def recovery_overhead(m: int = 256, n: int = 32, k: int = 4,
                      max_iters: int = 300, delay_s: float = 0.02,
                      straggler_counts: tuple[int, ...] = (0, 1, 2)) -> dict:
    """Throughput of a k-request elastic group under injected straggler
    episodes.  Each episode arms a delay on shard 0 a few iterations
    ahead; the ShardMonitor trips on the telemetry, the executor
    re-meshes mid-solve (clearing the delay with the dropped shard), and
    the next episode is armed.  Recovery latency is measured from the
    first delayed iteration to the completed re-mesh — so it prices
    detection, the matrix move AND the engine re-JIT."""
    import jax
    import jax.numpy as jnp
    from repro.core.distmat import RowMatrix
    from repro.core.distmat.types import make_mesh
    from repro.core.optim.elastic import ElasticConfig, ElasticGroup
    from repro.core.tfocs.linop import LinopMatrix
    from repro.train.faults import FaultPlan, FaultyLinop, FaultyMesh
    from repro.train.straggler import ShardMonitor, StragglerConfig

    A, bs = _trace(m, n, k, seed=7)
    out = {"suite": "serve_recovery", "m": m, "n": n, "requests": k,
           "delay_s": delay_s, "stragglers": {}}
    for count in straggler_counts:
        mesh = make_mesh((jax.device_count(), 1), ("data", "model"))
        fm = FaultyMesh(mesh)
        lin = FaultyLinop(LinopMatrix(RowMatrix.create(jnp.asarray(A),
                                                       mesh)),
                          FaultPlan())
        cfg = ElasticConfig(
            monitor=ShardMonitor(lin.row_shards(),
                                 StragglerConfig(warmup_steps=2,
                                                 threshold=2.0,
                                                 trip_limit=2)),
            remesh_to=fm.drop)
        grp = ElasticGroup(lin, "quad", slots=k, elastic=cfg)
        # Warm pass: compile the group step closure at full width so the
        # timed trace prices steady-state iterations, not the cold start
        # (re-JIT after a re-mesh IS billed — that is recovery cost).
        for b in bs:
            grp.admit_slot(b, tol=0.0, x0=None)
        while grp.iteration < 2:
            grp.step_iteration()
        for i in range(k):
            grp.clear_slot(i)

        def arm(step_from):
            # Mutate the SHARED dict/plan in place: after a re-mesh the
            # live wrapper is a dataclasses.replace copy that aliases
            # them — rebinding `lin.delays` would arm a dead instance.
            lin.delays[0] = delay_s
            lin.plan.delay_from = step_from
            return step_from

        for b in bs:
            grp.admit_slot(b, tol=1e-6)
        episodes_left = count
        armed_from = arm(grp.iteration + 2) if episodes_left else None
        onset = None
        recov = []
        it_cap = grp.iteration + max_iters
        t0 = time.perf_counter()
        while grp.busy() and grp.iteration < it_cap:
            if armed_from is not None and onset is None \
                    and grp.iteration >= armed_from:
                onset = time.perf_counter()
            seen = grp.remeshes
            grp.step_iteration()
            if grp.remeshes > seen and onset is not None:
                recov.append(time.perf_counter() - onset)
                onset = None
                episodes_left -= 1
                armed_from = arm(grp.iteration + 2) if episodes_left \
                    else None
            done = np.asarray(grp.state.done)
            if bool(done[grp.active].all()):
                break
        wall = time.perf_counter() - t0
        out["stragglers"][str(count)] = {
            "wall_s": round(wall, 4),
            "requests_per_s": round(k / wall, 2),
            "iterations": grp.iteration,
            "remeshes": grp.remeshes,
            "recovery_latency_s": [round(r, 4) for r in recov],
        }
    clean = out["stragglers"].get("0")
    if clean is not None:
        for rec in out["stragglers"].values():
            rec["throughput_vs_clean"] = round(
                rec["requests_per_s"] / max(clean["requests_per_s"],
                                            1e-12), 3)
    return out


def traced_demo(out_dir: str = "bench-artifacts",
                m: int = 256, n: int = 32, k: int = 4,
                delay_s: float = 0.02) -> dict:
    """End-to-end traced episode for the CI trace artifact: a batched
    served solve plus an elastic fault episode (straggler → trip →
    checkpoint → re-mesh) recorded under one telemetry Recorder and the
    JAX profiler, exported as JSONL events and a profiler trace (its
    ``repro.*`` spans beside the device ops; open the directory in
    TensorBoard or Perfetto).  Returns the summary so the caller (and CI
    log) can see the span-tree phase coverage."""
    import pathlib
    import tempfile

    import jax
    import jax.numpy as jnp
    from repro.core.distmat import RowMatrix
    from repro.core.distmat.types import make_mesh
    from repro.core.optim.elastic import (ElasticConfig, ElasticGroup,
                                          SolveCheckpoint)
    from repro.core.tfocs.linop import LinopMatrix
    from repro.launch import telemetry
    from repro.launch.serve import SolverServer
    from repro.train.faults import FaultPlan, FaultyLinop, FaultyMesh
    from repro.train.straggler import ShardMonitor, StragglerConfig

    rec = telemetry.Recorder()
    A, bs = _trace(m, n, k, seed=3)
    out = pathlib.Path(out_dir)
    profile_dir = out / "profile"
    with telemetry.recording(rec), jax.profiler.trace(str(profile_dir)):
        # -- served group solve: admit/queue-wait/latency/retire spans ---
        server = SolverServer(slots=k)
        _serve(server, A, bs, max_iters=60)

        # -- elastic fault episode: iterate/checkpoint/re-mesh spans -----
        mesh = make_mesh((jax.device_count(), 1), ("data", "model"))
        fm = FaultyMesh(mesh)
        lin = FaultyLinop(LinopMatrix(RowMatrix.create(jnp.asarray(A),
                                                       mesh)),
                          FaultPlan())
        with tempfile.TemporaryDirectory() as ckdir:
            cfg = ElasticConfig(
                monitor=ShardMonitor(lin.row_shards(),
                                     StragglerConfig(warmup_steps=2,
                                                     threshold=2.0,
                                                     trip_limit=2)),
                remesh_to=fm.drop,
                checkpoint=SolveCheckpoint(ckdir, every=5,
                                           async_save=False))
            grp = ElasticGroup(lin, "quad", slots=k, elastic=cfg)
            for b in bs:
                grp.admit_slot(b, tol=1e-6)
            lin.delays[0] = delay_s
            lin.plan.delay_from = 4
            it_cap = 120
            while grp.busy() and grp.iteration < it_cap:
                grp.step_iteration()
                if grp.remeshes >= 1 and grp.iteration >= 20:
                    break

    out.mkdir(parents=True, exist_ok=True)
    rec.export_jsonl(out / "telemetry_events.jsonl")
    summary = rec.summary()
    summary["artifacts"] = [str(out / "telemetry_events.jsonl"),
                            str(profile_dir)]
    return summary


def run(full: bool = False) -> list[tuple[str, float, str]]:
    configs = [(2000, 256, 8), (2000, 256, 16)] if full \
        else [(512, 64, 8)]
    rows = []
    from repro.launch.serve import SolverServer
    for m, n, k in configs:
        A, bs = _trace(m, n, k)
        batched, serial = SolverServer(slots=k), SolverServer(slots=1)
        # Warm both servers on the same matrix at a tiny iteration budget:
        # the first trace compiles each server's group step closure (one
        # per slot width), which must not be billed to the steady state.
        _serve(batched, A, bs, max_iters=2)
        _serve(serial, A, bs[:1], max_iters=2)

        wall_b, lats, passes_b, res_b = _serve(batched, A, bs)
        wall_s, _, passes_s, res_s = _serve(serial, A, bs)

        rps_b, rps_s = k / wall_b, k / wall_s
        rec = {"suite": "serve", "m": m, "n": n, "requests": k,
               "batched": {"wall_s": round(wall_b, 4),
                           "requests_per_s": round(rps_b, 2),
                           "p50_latency_ms": round(
                               lats[len(lats) // 2] * 1e3, 3),
                           "p99_latency_ms": round(
                               lats[min(int(len(lats) * 0.99),
                                        len(lats) - 1)] * 1e3, 3),
                           "group_a_passes": passes_b},
               "serial": {"wall_s": round(wall_s, 4),
                          "requests_per_s": round(rps_s, 2),
                          "total_a_passes": passes_s},
               "throughput_ratio": round(rps_b / max(rps_s, 1e-12), 3),
               "a_pass_ratio": round(passes_s / max(passes_b, 1), 3),
               "structural": group_pass_counts()}
        print("BENCH " + json.dumps(rec))
        rows.append((
            f"serve_{m}x{n}_k{k}",
            wall_b / k * 1e6,
            f"rps_batched={rps_b:.1f};rps_serial={rps_s:.1f};"
            f"throughput_ratio={rps_b / max(rps_s, 1e-12):.2f};"
            f"p99_ms={rec['batched']['p99_latency_ms']:.1f};"
            f"a_pass_ratio={rec['a_pass_ratio']:.2f}"))

    rec = recovery_overhead()
    print("BENCH " + json.dumps(rec))
    s = rec["stragglers"]
    recov = [x for r in s.values() for x in r["recovery_latency_s"]]
    rows.append((
        f"serve_recovery_{rec['m']}x{rec['n']}_k{rec['requests']}",
        (max(recov) if recov else 0.0) * 1e6,
        ";".join(f"rps_s{c}={r['requests_per_s']:.1f}"
                 for c, r in s.items())
        + f";remeshes={sum(r['remeshes'] for r in s.values())}"
        + (f";recovery_p100_ms={max(recov) * 1e3:.1f}" if recov else "")))
    return rows


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--traced-demo", action="store_true",
                    help="record a traced served solve + fault episode and "
                         "export JSONL + profiler trace artifacts")
    ap.add_argument("--out-dir", default="bench-artifacts")
    args = ap.parse_args()
    if args.traced_demo:
        summary = traced_demo(out_dir=args.out_dir)
        print("TRACE " + json.dumps(summary, sort_keys=True))
    else:
        for name, us, derived in run():
            print(f"{name},{us:.1f},{derived}")
