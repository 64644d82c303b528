#!/usr/bin/env python3
"""Chip benchmark of the matrix suite: one cell per run.

    python3 bench/run.py --workload dense-solve --seed 7 --seconds 30 --trace 0

Everything is found by name from BENCHMARK.json at the checkout's root:
the cell's configuration in bench/configs/<config>.json with its build
function and plain operators in bench/configs/<config>.py, its traffic in
bench/traffic/<traffic>.json, the limits of its correctness numbers in
bench/limits/<workload>.json, each per-layer metric's reader in
bench/metrics/<metric>.py, and the device's peaks in bench/peaks.json.

A run makes its inputs from --seed, warms every job kind the traffic
sends (set-up, timed as setup_s), drives the program for --seconds,
reads the device's peak memory, then compares a seeded sample of what
the window produced with the plain references (bench/refs).  With
--trace 1 the window runs under the JAX profiler and the run reports the
per-layer metrics instead of the end-to-end ones.  The last lines on
standard error name each compared number with its limit; the last line
on standard output is one JSON object.  Off TPU, with fewer chips than
the cell asks for, or on a device kind missing from peaks.json, the run
exits 2 and prints no result."""
from __future__ import annotations

import argparse
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
OUT = ROOT / ".bench_out"


class SetupError(Exception):
    """The run cannot start: its exit code is 2 and it prints no result."""


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path):
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of BENCHMARK.json's workloads, with everything it names."""

    def __init__(self, bench: dict, name: str, root: Path = ROOT):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SetupError(f"no workload {name!r} in BENCHMARK.json")
        self.spec = cells[name]
        self.name = name
        configs = {c["name"]: c for c in bench["configs"]}
        self.config = read_json(root / configs[self.spec["config"]]["file"])
        base = root / "bench"
        self.config_py = base / "configs" / f"{self.spec['config']}.py"
        self.traffic = read_json(base / "traffic"
                                 / f"{self.spec['traffic']}.json")
        self.limits = read_json(base / "limits" / f"{name}.json")
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if (name in m["workloads"] if "workloads" in m
                              else m["moves"] in reported)]
        self.metric_dir = base / "metrics"


def device_check(cell: Cell, peaks: dict):
    """The device this run measures, or SetupError."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SetupError(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < cell.spec["chips"]:
        raise SetupError(f"the cell needs {cell.spec['chips']} chips, "
                         f"JAX sees {len(devs)}")
    kind = devs[0].device_kind
    if kind not in peaks:
        raise SetupError(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (past 32 bits too)."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


class Run:
    """What the per-layer metric readers see."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def make_jobs(cell: Cell, seed: int, sizes: dict | None = None):
    """The cell's matrix and its load generator, inputs made from `seed`.
    `sizes` overrides configuration numbers; only the tests pass it."""
    import jax
    import loadgen
    key = seed_key(seed)
    config_py = load_module(cell.config_py, f"config_{cell.spec['config']}")
    mat = config_py.build(dict(cell.config, **(sizes or {})),
                        jax.random.fold_in(key, 0))
    jobs = loadgen.KINDS[cell.traffic["job"]](
        mat, cell.traffic, seed, jax.random.fold_in(key, 1))
    return mat, jobs


def enable_cache():
    """The program's persistent compilation cache, holding every program
    it compiles.  JAX by default keeps only those that took a second or
    more to compile, so which programs a later run finds depended on how
    busy the host was when they were first compiled: runs of one cell fell
    into two groups, 1.8 times apart (PERF.md)."""
    import jax
    sys.path.insert(0, str(ROOT / "src"))
    from repro import compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compile_cache.enable()


def judge(numbers: dict, limits: dict) -> tuple[dict, bool]:
    """Each limited number beside its limit, and whether every one is
    finite and within it (a number the run did not produce is not)."""
    checks = {k: {"value": float(numbers.get(k, math.nan)), "limit": v}
              for k, v in limits.items()}
    return checks, all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                       for c in checks.values())


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             peaks: dict, sizes: dict | None = None) -> dict:
    """Set up, measure, check."""
    import jax

    t_setup = time.perf_counter()
    enable_cache()
    compiles: list[tuple[float, float]] = []

    def on_event(event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            compiles.append((time.perf_counter(), duration))
    jax.monitoring.register_event_duration_secs_listener(on_event)

    mat, jobs = make_jobs(cell, seed, sizes)
    jobs.setup()
    setup_s = time.perf_counter() - t_setup

    trace_dir = OUT / "trace" / cell.name
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("window"):
        measured = jobs.run(seconds)
    t1 = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    window_compiles = [(t, d) for t, d in compiles if t0 <= t <= t1]

    checks, correct = judge(jobs.check(), cell.limits)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if trace:
        import trace_reduce
        tr = trace_reduce.load(str(trace_dir))
        run = Run(work=mat.work(), traffic=cell.traffic,
                  counters=jobs.counters, records=jobs.records,
                  compile_events=window_compiles, peaks=peaks, trace=tr)
        metrics = {}
        for m in cell.per_layer:
            mod = load_module(cell.metric_dir / f"{m['name']}.py",
                              f"metric_{m['name']}")
            value = mod.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=trace_reduce.busy_s(tr),
                      window_s=trace_reduce.window_s(tr))
        extra = {"breakdown": {"device_ops": trace_reduce.top_ops(tr),
                               "idle_gaps": trace_reduce.idle_gaps(tr)}}
        trace_reduce.save(tr, str(OUT / f"trace-{cell.name}.json"))
    else:
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in measured.items()}
        metrics["setup_s"] = {"value": setup_s, "unit": units["setup_s"]}
        extra = {}
    return {"correct": correct, "attempted": len(jobs.records),
            "failed": jobs.failed(), "metrics": metrics, "device": device,
            **extra, "counters": jobs.counters,
            "window_compile_s": sum(d for _, d in window_compiles),
            "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        if not (ROOT / "src" / "repro").is_dir():
            raise SetupError("the program (src/repro) is not in this checkout")
        bench = read_json(ROOT / "BENCHMARK.json")
        cell = Cell(bench, args.workload)
        peaks = device_check(cell, read_json(HERE / "peaks.json"))
    except (SetupError, OSError, KeyError, ValueError) as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(HERE))
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      peaks=peaks)
    c = result["counters"]
    print("counters " + json.dumps(c), file=sys.stderr)
    for name, chk in result["checks"].items():
        verdict = "ok" if chk["value"] <= chk["limit"] else "OVER"
        print(f"check {name} {chk['value']!r} limit {chk['limit']!r} "
              f"{verdict}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
