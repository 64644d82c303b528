"""Device-idle time named by the program's spans (metrics/_spans.py), on
hand-made intervals in nanoseconds, and the finder on real profiler
traces written here."""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from metrics import _spans as S

METRICS = Path(__file__).resolve().parent.parent / "metrics"


def trace(*devices, window=(0, 1000)):
    return {"window": list(window),
            "devices": {f"TPU:{i}": ops for i, ops in enumerate(devices)},
            "host": []}


def test_nested_spans_go_to_the_innermost():
    # idle 100-400 and 600-1000; api 0-900 holds loop 150-300 holds plan
    # 200-250
    tr = trace([["a", 0, 100], ["b", 400, 600]])
    spans = [("api.solve", 0, 900, 0), ("solve.loop", 150, 300, 0),
             ("planner.plan", 200, 250, 0)]
    got = S.attribute(tr, spans)
    assert got == pytest.approx({
        "api.solve": (50 + 100 + 300) * 1e-9,       # 100-150, 300-400, 600-900
        "api.solve/solve.loop": 100e-9,             # 150-200, 250-300
        "api.solve/solve.loop/planner.plan": 50e-9,
        S.OUTSIDE: 100e-9})                         # 900-1000


def test_idle_outside_spans_is_outside():
    tr = trace([["a", 200, 800]])
    got = S.attribute(tr, [("svd.eigh", 300, 700, 0)])
    assert got == pytest.approx({S.OUTSIDE: 400e-9})
    assert S.attribute(tr, []) == pytest.approx({S.OUTSIDE: 400e-9})


def test_two_devices_are_averaged():
    tr = trace([["a", 0, 500]], [["a", 0, 900]])
    got = S.attribute(tr, [("api.svd", 0, 1000, 0)])
    assert got == pytest.approx({"api.svd": (500 + 100) / 2 * 1e-9})


def test_gap_cut_at_a_span_boundary_splits_exactly():
    # one gap 100-900; svd.fetch ends and svd.eigh starts at 437
    tr = trace([["a", 0, 100], ["b", 900, 1000]])
    spans = [("api.svd", 50, 950, 0), ("svd.fetch", 60, 437, 0),
             ("svd.eigh", 437, 811, 0)]
    got = S.attribute(tr, spans)
    assert got["api.svd/svd.fetch"] == pytest.approx(337e-9)
    assert got["api.svd/svd.eigh"] == pytest.approx(374e-9)
    assert got["api.svd"] == pytest.approx(89e-9)
    assert sum(got.values()) == pytest.approx(800e-9)


def test_innermost_is_the_shortest_and_its_thread_holds_the_chain():
    # a server thread's span (1) inside the main thread's api span (0)
    tr = trace([])
    spans = [("api.solve", 0, 1000, 0), ("solve.loop", 0, 900, 0),
             ("serve.admit", 100, 200, 1)]
    got = S.attribute(tr, spans)
    assert got["serve.admit"] == pytest.approx(100e-9)
    assert got["api.solve/solve.loop"] == pytest.approx(800e-9)


class FakeRun:
    def __init__(self, tr, jobs=2):
        self.trace = tr
        self.counters = {"jobs": jobs}


def metric(name):
    spec = importlib.util.spec_from_file_location(name, METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_metrics_split_the_job_spans():
    tr = trace([["a", 0, 100], ["b", 900, 1000]])
    run = FakeRun(tr)
    run.program_idle = S.attribute(tr, [
        ("api.solve", 100, 900, 0), ("solve.setup", 100, 150, 0),
        ("solve.loop", 200, 800, 0), ("collective.fused_grad", 210, 260, 0),
        ("planner.plan", 300, 310, 0)])
    loop = metric("idle.solve.loop_s").read(run)
    rest = metric("idle.solve.rest_s").read(run)
    assert loop == pytest.approx(600e-9 / 2)
    assert rest == pytest.approx(200e-9 / 2)
    assert metric("idle.svd.eigh_s").read(run) == 0.0
    assert metric("idle.svd.rest_s").read(run) == 0.0


def test_no_program_span_reads_nothing():
    run = FakeRun(None)
    assert metric("idle.svd.eigh_s").read(run) is None
    run = FakeRun(trace([["a", 0, 10]]))
    run.program_idle = None
    assert metric("idle.solve.loop_s").read(run) is None


def test_finder_picks_the_trace_whose_window_matches(tmp_path):
    """Two profiler traces under one directory, as two runs at once leave
    them: the finder returns the one whose `window` event is the run's,
    with its program spans and no other's."""
    import trace_reduce
    windows = {}
    for name in ("a", "b"):
        with jax.profiler.trace(str(tmp_path / name)):
            with jax.profiler.TraceAnnotation("window"):
                with jax.profiler.TraceAnnotation(f"repro.span_{name}"):
                    jnp.ones(8).block_until_ready()
        windows[name] = trace_reduce.load(str(tmp_path / name))["window"]
    for name in ("a", "b"):
        path, spans = S.find_trace(windows[name], tmp_path)
        assert Path(path).is_relative_to(tmp_path / name)
        assert [sp[0] for sp in spans] == [f"span_{name}"]
    assert S.find_trace([0.0, 1.0], tmp_path) == (None, [])
