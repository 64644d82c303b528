"""The trace reduction and the work counts, against hand counts."""
import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

import trace_reduce as T

HERE = Path(__file__).resolve().parent
METRICS = HERE.parent / "metrics"

# A hand-made trace, in nanoseconds: window 0..1000 on one device.
HAND = {
    "window": [0, 1000],
    "devices": {"TPU:0": [
        ["fusion.1", -50, 100],                       # clipped to 0..100
        ["repro_fused_grad.3", 150, 350],
        ["copy", 300, 400],                           # overlaps the kernel
        ["while.2", 140, 420],                        # holds the two above
        ["repro_fused_grad_multi", 600, 700],
        ["late", 950, 1100],                          # clipped to 950..1000
    ]},
    "host": [["window", 0, 1000], ["job.solve.quad_l1", 100, 500],
             ["server.step", 500, 1000]],
}


def test_union_and_idle():
    # busy: 0-100, 140-420, 600-700, 950-1000 = 100 + 280 + 100 + 50
    assert T.busy_s(HAND) == pytest.approx(530e-9)
    assert T.window_s(HAND) == pytest.approx(1000e-9)
    assert T.idle_share(HAND) == pytest.approx(0.47)
    assert T.union_ns([(0, 10), (5, 20), (30, 40), (40, 45)]) == 35


def test_kernel_time_matches_whole_names():
    assert T.kernel_s(HAND, r"\brepro_fused_grad\b") == pytest.approx(200e-9)
    assert len(T.kernel_events(HAND, r"\brepro_fused_grad\w*")) == 2


def test_gaps_named_by_innermost_span():
    # gaps: 100-140 (job), 420-600 (mid 510: server.step), 700-950
    # (server.step)
    gaps = dict(T.idle_gaps(HAND))
    assert gaps["job.solve.quad_l1"] == pytest.approx(40e-9)
    assert gaps["server.step"] == pytest.approx(430e-9)
    assert T.top_ops(HAND)[0] == ["repro_fused_grad", pytest.approx(200e-9)]
    assert "while" not in dict(T.top_ops(HAND))


def metric(name):
    spec = importlib.util.spec_from_file_location(name, METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_work_counts():
    w = {"m": 2097152, "n": 1024}
    fg = metric("fused_grad.roofline").work(w)
    assert fg["flops"] == 4 * 2097152 * 1024
    assert fg["bytes"] == 2097152 * 1024 * 4 + 4 * (2 * 1024 + 3 * 2097152)
    g = metric("gram.roofline").work(w)
    assert g["flops"] == 2 * 2097152 * 1024 ** 2
    assert g["bytes"] == 4 * 2097152 * 1024 + 4 * 1024 ** 2


class FakeRun:
    def __init__(self, trace, work, jobs=1):
        self.trace, self.work = trace, work
        self.counters = {"jobs": jobs, "a_passes": 3}
        self.peaks = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}


def test_roofline_by_hand():
    # one event of 200 ns; the work's least time is bytes / 1e9 B/s
    w = {"m": 10, "n": 5}
    least = metric("fused_grad.roofline").work(w)["bytes"] * 1e-9
    got = metric("fused_grad.roofline").read(FakeRun(HAND, w))
    assert got == pytest.approx(100 * least / 200e-9)
    no_kernel = dict(HAND, devices={"TPU:0": [["copy", 0, 10]]})
    assert metric("fused_grad.roofline").read(FakeRun(no_kernel, w)) is None


def test_recorded_trace():
    """A stretch of a sparse-svd window as the profiler recorded it on one
    v5e, cut to 2,000 operations: the reduction agrees with a count on a grid of
    microseconds, and with the busy time and event count worked out when
    it was cut (`expect`)."""
    tr = json.loads((HERE / "recorded_trace.json").read_text())
    lo, hi = tr["window"]
    grid = np.zeros(int((hi - lo) // 1000) + 1, bool)
    (ops,) = tr["devices"].values()
    for _, s, e in ops:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            grid[int((a - lo) // 1000):int(-(-(b - lo) // 1000))] = True
    # the grid rounds each interval out to whole microseconds
    assert T.busy_s(tr) <= grid.sum() * 1e-6
    assert T.busy_s(tr) == pytest.approx(grid.sum() * 1e-6, rel=0.05)
    want = tr["expect"]
    assert T.busy_s(tr) == pytest.approx(want["busy_s"], rel=1e-9)
    assert len(T.kernel_events(tr, want["pattern"])) == want["events"]
    assert sum(v for _, v in T.idle_gaps(tr, n=100)) == pytest.approx(
        T.window_s(tr) - T.busy_s(tr), rel=1e-9)
