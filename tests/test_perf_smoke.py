"""Perf-smoke: structural guards on the optimizer hot path and the planner.

Two deterministic, non-flaky properties that fail the moment a refactor
regresses a dispatch decision:

  * the fused gradient path's *counted* A-passes never exceed the unfused
    path's (counts are trace-level — CountingLinop: while-loop bodies trace
    once);
  * planner.plan() on the golden shape table (benchmarks/bench_planner)
    reproduces the recorded decisions against the reference machine model.
"""
import pytest

bench_optim = pytest.importorskip(
    "benchmarks.bench_optim",
    reason="benchmarks package needs the repo root on sys.path "
           "(run as `python -m pytest` from the checkout)")
bench_planner = pytest.importorskip("benchmarks.bench_planner")
bench_serve = pytest.importorskip("benchmarks.bench_serve")


@pytest.mark.perf_smoke
@pytest.mark.parametrize("pname", ["linear", "logistic"])
@pytest.mark.parametrize("method", ["gra", "lbfgs"])
def test_fused_a_passes_not_worse(pname, method):
    fused = bench_optim.fused_pass_counts(pname, method, True, m=120, n=24)
    unfused = bench_optim.fused_pass_counts(pname, method, False,
                                            m=120, n=24)
    assert fused["per_attempt"] <= unfused["per_attempt"], (fused, unfused)
    assert fused["total"] <= unfused["total"], (fused, unfused)
    # the whole point: one pass per attempt, down from two
    assert fused["per_attempt"] == 1, fused
    assert unfused["per_attempt"] == 2, unfused
    assert fused["counts"]["apply"] == fused["counts"]["adjoint"] == 0, fused


@pytest.mark.perf_smoke
def test_serving_grouped_passes_below_serial():
    """Serving canary: a shared-A group answered by the batched engine
    consumes strictly fewer A-passes than the serial schedule for the same
    requests (and identical trace-level call sites — one fused pass per
    attempt regardless of group width).  Deterministic counts, no timing."""
    rec = bench_serve.group_pass_counts(m=120, n=24, k=4, iters=6)
    assert rec["grouped_a_passes"] < rec["serial_a_passes"], rec
    assert rec["grouped_trace_counts"] == rec["serial_trace_counts"], rec
    assert rec["a_pass_ratio"] >= 2, rec


@pytest.mark.perf_smoke
def test_planner_decisions_stable_on_cpu():
    """Dispatch regressions fail fast: every golden-shape plan() decision
    matches the recorded expectation on the reference machine (priced
    explicitly against machine.V5E, so a stray user calibration file on
    the runner cannot flip it)."""
    for rec in bench_planner.golden_plans():
        assert rec["stable"], (
            f"planner decision drifted for {rec['op']} {rec['dims']}: "
            f"got {rec['choice']}, expected {rec['expected']}")


@pytest.mark.perf_smoke
def test_eager_dispatch_at_tiny_shapes():
    """Overlap canary: at shapes where one kernel call is cheaper than any
    pipeline (tiny shard, small psum), the planner must keep the eager
    single-dispatch path — chunks=1 — even with topology context, and a
    force-chunked call must still be bit-identical to eager (so a wrong
    auto decision could never corrupt results, only waste dispatches)."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core.distmat import RowMatrix
    from repro.launch import machine, planner

    p = planner.plan("gram", {"m": 4096, "n": 128}, machine=machine.V5E,
                     context={"axes": (8,)})
    assert p.choice == "eager" and p.blocks["chunks"] == 1, p.explain()
    g = planner.plan("grad", {"m": 4096, "n": 128}, machine=machine.V5E,
                     context={"axes": (8,)})
    assert g.blocks["chunks"] == 1, g.explain()

    rng = np.random.default_rng(0)
    A = rng.normal(size=(48, 16)).astype(np.float32)
    rm = RowMatrix.create(jnp.asarray(A))
    assert np.array_equal(np.asarray(rm.gram(chunks=4)),
                          np.asarray(rm.gram(chunks=1)))


@pytest.mark.perf_smoke
def test_tiny_shapes_stay_f32():
    """Precision canary: at tiny shapes the modeled-savings floor must
    keep the precision sweep at exact f32 eager even when the solver
    tolerance would admit bf16/psum8 — flipping precision to save
    nanoseconds is all risk and no win, and a regression here silently
    degrades every small solve."""
    from repro.launch import machine, planner

    for op in ("gram", "grad"):
        p = planner.plan(op, {"m": 4096, "n": 128}, machine=machine.V5E,
                         context={"axes": (8,), "tol": 1e-3})
        assert p.precision == "f32", p.explain()
        assert p.blocks["chunks"] == 1, p.explain()


@pytest.mark.perf_smoke
def test_telemetry_off_is_free_and_result_identical():
    """Telemetry canary: with no recorder installed every metric call
    resolves to the shared null singleton and a span is a profiler
    annotation that records nothing, and a traced solve returns
    bit-identical iterates to an untraced one — the instrumentation must
    observe, never perturb."""
    import numpy as np
    from repro import api
    from repro.launch import telemetry

    null = telemetry.current()
    assert null is telemetry.NULL and not null.enabled
    # metric no-ops hand back the SAME object every call; spans record
    # nothing
    with null.span("solver.iteration", k=1), null.span("serve.admit"):
        pass
    assert null.spans == [] and null.summary()["spans"] == 0
    assert null.counter("a") is null.counter("b", reason="x")

    rng = np.random.default_rng(3)
    A = rng.normal(size=(120, 12)).astype(np.float32)
    b = (A @ rng.normal(size=12)).astype(np.float32)
    base = api.solve(api.SolveRequest(A=A, b=b, loss="quad",
                                      tol=1e-7, max_iters=200))
    traced = api.solve(api.SolveRequest(A=A, b=b, loss="quad",
                                        tol=1e-7, max_iters=200,
                                        telemetry=True))
    np.testing.assert_array_equal(np.asarray(base.x),
                                  np.asarray(traced.x))
    assert int(base.info["iterations"]) == int(traced.info["iterations"])
    assert "trace" in traced.info and "trace" not in base.info


@pytest.mark.perf_smoke
def test_null_span_overhead_bounded():
    """A disabled span costs nanoseconds, not microseconds: 10k no-op
    spans must finish in well under the time one solver iteration takes.
    The bound is generous (0.25s) — it catches an accidental allocation
    or lock on the disabled path, not scheduler noise."""
    import time
    from repro.launch import telemetry

    null = telemetry.NULL
    t0 = time.perf_counter()
    for i in range(10_000):
        with null.span("solver.iteration", iteration=i) as sp:
            sp.annotate(ok=True)
    assert time.perf_counter() - t0 < 0.25
