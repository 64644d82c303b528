"""From a profiler trace to the numbers the per-layer metrics read.

`load` turns the JAX profiler's `.xplane.pb` into a small plain form:
the traced window, each device's operations as (name, start, end) and
the benchmark's own host spans as (name, start, end), all in nanoseconds
on the trace's clock.  On the TPU an operation's event carries its HLO
text (`%repro_bsr_matvec.1 = f32[...] custom-call(...)`); the name kept
is the instruction's own (`repro_bsr_matvec.1`), so a kernel is matched
by its name and never by an operand that another op reads.  Control-flow
ops (while, cond, call) hold the ops of their bodies on the same line;
they count toward busy time but not in the list of costly ops.  The
functions below work on that form alone, so a small recorded trace is
enough to test them."""
from __future__ import annotations

import glob
import json
import os
import re
from collections import defaultdict

# Host spans the benchmark writes (loadgen.span and run.py), by prefix.
HOST_PREFIXES = ("window", "job.", "gen.", "server.", "reference.")
# Device lines that hold one event per operation.
OP_LINES = ("XLA Ops",)
# Ops that contain other ops of the same line.
CONTAINERS = re.compile(r"^(while|cond|conditional|call)(\.\d+)?$")


def op_name(text: str) -> str:
    """The instruction's own name from an event's HLO text."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def load(log_dir: str) -> dict:
    import jax
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    devices, host = {}, []
    cpu_ops = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            lines = [ln for ln in plane.lines if ln.name in OP_LINES]
            ops = []
            for ln in lines:
                for ev in ln.events:
                    ops.append([op_name(ev.name), ev.start_ns,
                                ev.start_ns + ev.duration_ns])
            devices[plane.name.split("/device:")[1]] = ops
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(HOST_PREFIXES):
                        host.append([ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns])
                    elif ev.duration_ns > 0 and _has_hlo(ev):
                        cpu_ops.append([op_name(ev.name), ev.start_ns,
                                        ev.start_ns + ev.duration_ns])
    if not devices and cpu_ops:      # the CPU backend runs ops on host threads
        devices["CPU:0"] = cpu_ops
    win = [h for h in host if h[0] == "window"]
    window = [win[-1][1], win[-1][2]] if win else None
    return {"window": window, "devices": devices, "host": host}


def _has_hlo(ev) -> bool:
    return any(k == "hlo_op" for k, _ in ev.stats)


def save(trace: dict, path: str) -> None:
    with open(path, "w") as f:
        json.dump(trace, f)


def clip(trace: dict, intervals):
    """Intervals cut to the traced window."""
    lo, hi = trace["window"]
    for iv in intervals:
        s, e = max(iv[-2], lo), min(iv[-1], hi)
        if e > s:
            yield s, e


def union_ns(intervals) -> float:
    """Length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def window_s(trace: dict) -> float:
    lo, hi = trace["window"]
    return (hi - lo) * 1e-9


def busy_s(trace: dict) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    devs = trace["devices"]
    if not devs:
        return 0.0
    return sum(union_ns(clip(trace, ops)) for ops in devs.values()) \
        * 1e-9 / len(devs)


def idle_share(trace: dict) -> float:
    return 1.0 - busy_s(trace) / window_s(trace)


def kernel_events(trace: dict, pattern: str) -> list:
    """Operations in the window whose name matches `pattern`, on every
    device, as (start, end)."""
    rx = re.compile(pattern)
    out = []
    for ops in trace["devices"].values():
        out += list(clip(trace, [op for op in ops if rx.search(op[0])]))
    return out


def kernel_s(trace: dict, pattern: str) -> float:
    """Device seconds of the matching operations, averaged over devices."""
    devs = max(len(trace["devices"]), 1)
    return sum(e - s for s, e in kernel_events(trace, pattern)) * 1e-9 / devs


def top_ops(trace: dict, n: int = 10) -> list:
    """The operations that took most device time, summed by name without
    its instance number (`repro_bsr_matvec.1` → `repro_bsr_matvec`);
    control-flow ops, which contain others, are left out."""
    devs = max(len(trace["devices"]), 1)
    tot = defaultdict(float)
    for ops in trace["devices"].values():
        for op in ops:
            if CONTAINERS.match(op[0]):
                continue
            for s, e in clip(trace, [op]):
                tot[re.sub(r"\.\d+$", "", op[0])] += (e - s) * 1e-9 / devs
    return sorted(([k, v] for k, v in tot.items()),
                  key=lambda kv: -kv[1])[:n]


def gaps(trace: dict, ops) -> list:
    """(start, end) of the stretches of the window with no operation."""
    lo, hi = trace["window"]
    out, cur = [], lo
    for s, e in sorted(clip(trace, ops)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def idle_gaps(trace: dict, n: int = 10) -> list:
    """Idle device time, named by the innermost benchmark span on the host
    at the middle of each gap, summed by name, largest first."""
    devs = max(len(trace["devices"]), 1)
    spans = sorted(trace["host"], key=lambda h: h[2] - h[1])
    tot = defaultdict(float)
    for ops in trace["devices"].values():
        for s, e in gaps(trace, ops):
            mid = (s + e) / 2
            name = next((h[0] for h in spans if h[1] <= mid <= h[2]),
                        "outside any span")
            tot[name] += (e - s) * 1e-9 / devs
    return sorted(([k, v] for k, v in tot.items()),
                  key=lambda kv: -kv[1])[:n]
