"""Device-idle time named by the program's own spans.

The program marks its layers on the profiler's clock: every span it
opens is a host annotation named `repro.<span>` (`api.solve`,
`solve.loop`, `svd.eigh`, ...; src/repro/launch/telemetry.py).  This
reader finds the run's own profiler trace, keeps those host events, and
hands each stretch of the device's idle time to the innermost program
span open over it.  The host work that kept the device waiting is then
named where it happens, by the layer that did it.

`attribute` is the pure part: a reduced trace (trace_reduce) and spans
in, idle seconds by span out.  The metric files call `idle_per_job`."""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from pathlib import Path

import trace_reduce

PREFIX = "repro."
OUTSIDE = "outside"
# Where bench/run.py writes its profiler traces, one directory per cell.
TRACES = Path(__file__).resolve().parents[2] / ".bench_out" / "trace"


def read_trace(path: str) -> tuple[list, list]:
    """The `window` events and the program's spans of one `.xplane.pb`:
    windows as [start, end], spans as (name without the prefix, start,
    end, thread), in nanoseconds on the trace's clock."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    windows, spans = [], []
    for plane in data.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for tid, line in enumerate(plane.lines):
            for ev in line.events:
                end = ev.start_ns + ev.duration_ns
                if ev.name == "window":
                    windows.append([ev.start_ns, end])
                elif ev.name.startswith(PREFIX):
                    spans.append((ev.name[len(PREFIX):], ev.start_ns, end,
                                  tid))
    return windows, spans


def find_trace(window, root=TRACES) -> tuple[str | None, list]:
    """The path and program spans of the trace whose `window` event
    starts and ends exactly at `window`, newest file first: runs that
    trace at the same time (test workers) write files of their own."""
    paths = sorted(glob.glob(os.path.join(str(root), "**", "*.xplane.pb"),
                             recursive=True),
                   key=os.path.getmtime, reverse=True)
    for path in paths:
        windows, spans = read_trace(path)
        if list(window) in windows:
            return path, spans
    return None, []


def _cut(gaps, marks):
    """Each (start, end) gap cut at every mark strictly inside it."""
    for s, e in gaps:
        k = bisect.bisect_right(marks, s)
        while k < len(marks) and marks[k] < e:
            yield s, marks[k]
            s = marks[k]
            k += 1
        yield s, e


def _path(spans, open_ids) -> str:
    """The innermost open span (the shortest) and the spans open around
    it on its thread, outermost first, as "outer/.../inner"."""
    if not open_ids:
        return OUTSIDE
    inner = min(open_ids, key=lambda i: (spans[i][2] - spans[i][1], i))
    tid = spans[inner][3]
    chain = sorted((spans[i] for i in open_ids if spans[i][3] == tid),
                   key=lambda sp: (sp[1] - sp[2], sp[1]))
    return "/".join(sp[0] for sp in chain)


def attribute(trace: dict, spans: list) -> dict[str, float]:
    """Device-idle seconds by the span that holds them, averaged over the
    devices.  Every idle gap of every device (trace_reduce.gaps) is cut at
    the spans' starts and ends; each piece goes to the innermost span open
    over all of it, keyed by its chain of names ("api.solve/solve.loop"),
    or to OUTSIDE."""
    devs = trace["devices"]
    if not devs:
        return {}
    marks = sorted({t for sp in spans for t in sp[1:3]})
    starts = sorted(range(len(spans)), key=lambda i: spans[i][1])
    ends = sorted(range(len(spans)), key=lambda i: spans[i][2])
    out = defaultdict(float)
    for ops in devs.values():
        open_ids, i, j = set(), 0, 0
        for a, b in _cut(trace_reduce.gaps(trace, ops), marks):
            while i < len(starts) and spans[starts[i]][1] <= a:
                open_ids.add(starts[i])
                i += 1
            while j < len(ends) and spans[ends[j]][2] <= a:
                open_ids.discard(ends[j])
                j += 1
            out[_path(spans, open_ids)] += (b - a) * 1e-9 / len(devs)
    return dict(out)


def program_idle(run) -> dict[str, float] | None:
    """`attribute` over the run's own trace, read once and kept on `run`;
    None without a trace or when it holds no program span."""
    if "program_idle" not in vars(run):
        idle = None
        if run.trace is not None and run.trace["window"] is not None:
            _, spans = find_trace(run.trace["window"])
            if spans:
                idle = attribute(run.trace, spans)
        run.program_idle = idle
    return run.program_idle


def idle_per_job(run, keep) -> float | None:
    """Idle seconds per job in the spans whose chain of names (a list,
    outermost first) `keep` accepts; 0.0 when none held idle time."""
    idle = program_idle(run)
    jobs = run.counters.get("jobs")
    if idle is None or not jobs:
        return None
    return sum(v for path, v in idle.items()
               if path != OUTSIDE and keep(path.split("/"))) / jobs
