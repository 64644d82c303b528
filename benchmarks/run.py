"""Benchmark harness — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME]

Prints ``name,us_per_call,derived`` CSV rows (paper artifacts:
Table 1 = bench_svd, Figure 1 = bench_optim, Figure 2 = bench_gemm,
§4.2 = bench_sparse; autotune = the kernel block-size sweep, which also
emits ``BENCH {json}`` lines and refreshes the persistent config cache;
planner = execution-planner golden decisions + machine-model calibration
from measured timings, persisted next to the autotune cache;
collectives = modeled-vs-measured psum time by payload size and device
count plus the link_eff fit demo, BENCH json only — never persisted;
precision = bytes/wall-clock/solution-error by storage and wire format —
f32 vs bf16 storage, int8 BlockELL, compressed int8 psums — across the
Figure-1 family, BENCH json only).
bench_optim additionally emits ``BENCH {json}`` lines for the fused-vs-
unfused gradient hot path (wall time, iterations/sec, counted A-passes
per attempt: 2 unfused → 1 fused); serve = the solver serving frontend
(bench_serve: requests/sec + p50/p99 latency under a shared-matrix trace,
batched-vs-serial throughput ratio, grouped-vs-serial A-pass counts).
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-size problems (slow on one core)")
    ap.add_argument("--only", default=None,
                    help="run a single suite: "
                         "svd|optim|gemm|sparse|autotune|planner|serve|"
                         "collectives|precision")
    args = ap.parse_args()

    from repro import compile_cache
    compile_cache.enable()
    from benchmarks import (bench_svd, bench_optim, bench_gemm, bench_sparse,
                            bench_autotune, bench_planner, bench_serve,
                            bench_collectives, bench_precision)
    suites = {
        "svd": lambda: bench_svd.run(),
        "optim": lambda: bench_optim.run(full=args.full),
        "gemm": lambda: bench_gemm.run(),
        "sparse": lambda: bench_sparse.run(),
        "autotune": lambda: bench_autotune.run(),
        "planner": lambda: bench_planner.run(),
        "serve": lambda: bench_serve.run(full=args.full),
        "collectives": lambda: bench_collectives.run(),
        "precision": lambda: bench_precision.run(),
    }
    if args.only:
        suites = {args.only: suites[args.only]}

    print("name,us_per_call,derived")
    failures = 0
    for sname, fn in suites.items():
        try:
            for name, us, derived in fn():
                print(f"{name},{us:.1f},{derived}")
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{sname}_SUITE_ERROR,0,{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
