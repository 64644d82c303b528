#!/usr/bin/env python3
"""Readings that the correctness limits are set from (not part of a
benchmark run).

    python3 bench/calibrate.py --workload dense-solve --seeds 1-12 \\
        --control-seeds 1-3 --seconds 5

For each seed, in one process: the cell's inputs from that seed, its
set-up, a short window at the cell's own load, and the numbers that
bench/run.py compares, read from what the program produced (the lower
reading of each limit is the largest of these).  For each control seed,
also the same numbers with the program replaced by the plain reference
computed at matmul precision "high" (three bf16 passes: the control for
float32 at "highest"), and by the program's own bfloat16 storage path.
A solve reading also carries per-family diagnostics (the distance to the
optimum and to FISTA stopped at the same tol), and each reading and
control its verdict under the cell's limits (`judge`, as bench/run.py
decides `correct`).
One JSON line per seed."""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import run as R


def seeds(text):
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def one_seed(cell, seed, seconds, controls, *, sizes=None, diagnose=True):
    mat, jobs = R.make_jobs(cell, seed, sizes)
    t0 = time.perf_counter()
    jobs.setup()
    t1 = time.perf_counter()
    measured = jobs.run(seconds)
    t2 = time.perf_counter()
    prog = jobs.check(diagnose=diagnose)
    line = {"seed": seed, "setup_s": t1 - t0, "measured": measured,
            "counters": jobs.counters, "failed": jobs.failed(),
            "program": prog, "check_s": time.perf_counter() - t2,
            "correct": R.judge(prog, cell.limits)[1]}
    for kind in controls:
        try:
            got = jobs.control(kind, diagnose=diagnose)
            line[f"control_{kind}"] = got
            line[f"control_{kind}_correct"] = R.judge(got, cell.limits)[1]
        except Exception as e:            # noqa: BLE001 — a crashed control
            line[f"control_{kind}"] = {"error": repr(e)[:500]}
    del jobs, mat
    gc.collect()
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--controls", default="high,program_bf16")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    try:
        cell = R.Cell(R.read_json(R.ROOT / "BENCHMARK.json"), args.workload)
        R.device_check(cell, R.read_json(R.HERE / "peaks.json"))
    except (R.SetupError, OSError, KeyError) as e:
        print(f"bench/calibrate.py: {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(R.HERE))
    R.enable_cache()
    for s in args.seeds:
        controls = args.controls.split(",") if s in args.control_seeds \
            else []
        print(json.dumps(one_seed(cell, s, args.seconds, controls)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
