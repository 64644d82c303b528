"""Helpers shared by the per-layer metric readers.

Each metric is a file of its own in this directory, named as the metric,
with one function `read(run)` that returns the value or None when the run
has nothing to read it from.  `run` carries the cell's work sizes
(`run.work`), the traffic, the window's counters and records, the compile
events inside the window, the peaks of the device, and the reduced trace
(`run.trace`, None without --trace 1)."""
from __future__ import annotations

import trace_reduce


def roofline(run, pattern, least_s_per_event):
    """Share of the roofline: the least time the chip could take for the
    matching operations' work, over the device time they took.  None when
    the trace holds no such operation."""
    if run.trace is None:
        return None
    events = trace_reduce.kernel_events(run.trace, pattern)
    spent = sum(e - s for s, e in events) * 1e-9
    if not events or spent <= 0:
        return None
    return 100.0 * least_s_per_event * len(events) / spent


def least_s(run, flops, nbytes):
    """The larger of flops over peak FLOP/s and bytes over peak bytes/s."""
    p = run.peaks
    return max(flops / p["flops_per_s"], nbytes / p["hbm_bytes_per_s"])


def idle(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    return 100.0 * trace_reduce.idle_share(run.trace)


def compile_per_job(run):
    jobs = run.counters.get("jobs")
    if not jobs:
        return None
    return sum(d for _, d in run.compile_events) / jobs


def passes_per_job(run):
    jobs = run.counters.get("jobs")
    if not jobs:
        return None
    return run.counters["a_passes"] / jobs
