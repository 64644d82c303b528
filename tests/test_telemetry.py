"""Runtime telemetry subsystem (launch/telemetry): spans, metrics,
plan-vs-actual, exporters — and the standardized ``Result.info`` contract
every public entry point reports.

Three layers:

  * Recorder unit behavior — span nesting/attrs/errors/cap, counter and
    gauge and histogram math (fixed log-spaced buckets, interpolated
    percentiles), the null recorder's annotation-only spans and shared
    no-op metrics, the JSONL round-trip;
  * the profiler's clock — every span is a ``repro.<name>`` annotation in
    a ``jax.profiler`` trace, nested as the layers nest, with or without a
    recorder, and nothing is recorded from code under a JAX trace;
  * integration — a traced api.solve carries ``info["trace"]`` with the
    solver span phases and fusedgrad plan-vs-actual records that
    ``planner.calibrate`` accepts; the served path renders per-reason
    degraded counters and non-trivial latency histograms; the elastic
    executor's fault episode (straggler → checkpoint → re-mesh) yields a
    span tree covering every recovery phase (``fault`` marker);
  * the Result.info key contract — iterations / a_passes / converged /
    plan / degraded on every entry point (solve direct, elastic,
    served, svd all modes, similarities) plus the deprecated native
    aliases ("fused", "n_evals", "mode" / "restarts" / "passes_over_A")
    kept for one release.
"""
import collections
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core.distmat import RowMatrix
from repro.core.distmat import types as distmat_types
from repro.launch import machine, telemetry


# =========================================================================
# Recorder unit behavior
# =========================================================================

class TestSpans:
    def test_nesting_records_parent_ids(self):
        rec = telemetry.Recorder()
        with rec.span("outer") as so:
            with rec.span("inner", depth=1):
                pass
        outer = next(s for s in rec.spans if s.name == "outer")
        inner = next(s for s in rec.spans if s.name == "inner")
        assert inner.parent == outer.id
        assert outer.parent is None
        assert inner.attrs["depth"] == 1
        assert inner.dur_s >= 0 and outer.dur_s >= inner.dur_s

    def test_annotate_and_duration(self):
        rec = telemetry.Recorder()
        with rec.span("work") as sp:
            sp.annotate(tries=3)
        (span,) = rec.spans
        assert span.attrs["tries"] == 3
        assert span.dur_s >= 0

    def test_exception_recorded_and_propagated(self):
        rec = telemetry.Recorder()
        with pytest.raises(ValueError, match="boom"):
            with rec.span("explodes"):
                raise ValueError("boom")
        (span,) = rec.spans
        assert "boom" in span.attrs["error"]

    def test_span_cap_drops_and_counts(self):
        rec = telemetry.Recorder(max_spans=3)
        for i in range(5):
            with rec.span(f"s{i}"):
                pass
        assert len(rec.spans) == 3
        assert rec.spans_dropped == 2

    def test_thread_safety_and_per_thread_stacks(self):
        """Concurrent spans from worker threads never cross-parent: each
        thread's stack is its own, and all spans commit."""
        rec = telemetry.Recorder()
        errs = []

        def worker(tid):
            try:
                for _ in range(50):
                    with rec.span("outer", tid=tid):
                        with rec.span("inner", tid=tid):
                            pass
            except Exception as e:  # pragma: no cover - failure detail
                errs.append(e)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        assert len(rec.spans) == 4 * 50 * 2
        by_id = {s.id: s for s in rec.spans}
        for s in rec.spans:
            if s.name == "inner":
                parent = by_id[s.parent]
                assert parent.name == "outer"
                assert parent.attrs["tid"] == s.attrs["tid"]


class TestMetrics:
    def test_counter_inc_and_labels(self):
        rec = telemetry.Recorder()
        rec.counter("reqs").inc()
        rec.counter("reqs").inc(2)
        rec.counter("deg", reason="fault").inc()
        rec.counter("deg", reason="deadline").inc(3)
        assert rec.counter("reqs").value == 3
        breakdown = rec.counters("deg")
        assert breakdown == {"reason=fault": 1, "reason=deadline": 3}

    def test_gauge_set(self):
        rec = telemetry.Recorder()
        g = rec.gauge("backlog")
        g.set(1)
        assert rec.gauge("backlog").value == 1
        g.set(0)
        assert rec.gauge("backlog").value == 0

    def test_histogram_percentiles_bracket_observations(self):
        rec = telemetry.Recorder()
        h = rec.histogram("lat")
        for v in [0.001] * 98 + [0.5, 1.0]:
            h.observe(v)
        assert h.count == 100
        # p50 lands in 0.001's bucket; interpolation stays within a
        # bucket factor (2x) of the true value, clamped to observed range.
        assert 0.0005 <= h.percentile(0.5) <= 0.002
        assert h.percentile(0.99) >= 0.25
        assert h.percentile(1.0) <= 1.0 + 1e-9
        assert h.min <= 0.001 and h.max >= 1.0

    def test_histogram_empty(self):
        h = telemetry.Recorder().histogram("lat")
        assert h.count == 0 and np.isnan(h.percentile(0.5))


class TestNullRecorder:
    def test_noops_share_singletons(self):
        """The disabled path records nothing: a span is its profiler
        annotation alone (annotate/sync_on are no-ops), every metric the
        same null sink."""
        null = telemetry.NULL
        assert not null.enabled
        s1 = null.span("a", x=1)
        s2 = null.span("b")
        assert type(s1) is type(s2) and s1.dur_s == s2.dur_s == 0.0
        assert null.counter("c") is null.histogram("h")
        with null.span("a") as sp:
            sp.annotate(ok=True)
            sp.sync_on(jnp.zeros(()))
        null.record_plan_actual(None, 0.0)
        assert null.summary()["spans"] == 0

    def test_current_defaults_to_null(self):
        assert telemetry.current() is telemetry.NULL

    def test_recording_scopes_current(self):
        rec = telemetry.Recorder()
        with telemetry.recording(rec):
            assert telemetry.current() is rec
            with rec.span("inside"):
                pass
        assert telemetry.current() is telemetry.NULL
        assert [s.name for s in rec.spans] == ["inside"]


class TestExporters:
    def test_jsonl_round_trip(self, tmp_path):
        rec = telemetry.Recorder()
        with rec.span("phase", k=1):
            pass
        rec.counter("n").inc(2)
        rec.histogram("h").observe(0.01)
        path = tmp_path / "events.jsonl"
        rec.export_jsonl(path)
        events = [json.loads(line) for line in
                  path.read_text().splitlines()]
        kinds = {e["type"] for e in events}
        assert {"span", "counter", "histogram"} <= kinds
        span = next(e for e in events if e["type"] == "span")
        assert span["name"] == "phase" and span["attrs"]["k"] == 1

    def test_timeit_blocks_and_feeds_histogram(self):
        rec = telemetry.Recorder()
        h = rec.histogram("bench")
        t = telemetry.timeit(lambda: jnp.ones(8) * 2, reps=3, warmup=1,
                             hist=h)
        assert len(t.times) == 3
        assert t.min_s <= t.median_s <= max(t.times)
        assert t.mean_us == pytest.approx(t.mean_s * 1e6)
        assert h.count == 3


# =========================================================================
# Integration: traced solves, serving metrics, plan-vs-actual
# =========================================================================

def _lstsq(m=120, n=12, k=1, seed=5):
    rng = np.random.default_rng(seed)
    A = rng.normal(size=(m, n)).astype(np.float32)
    bs = [(A @ rng.normal(size=n) + 0.01 * rng.normal(size=m))
          .astype(np.float32) for _ in range(k)]
    return A, bs


class TestTracedEntryPoints:
    def test_traced_solve_has_trace_and_matches_untraced(self):
        A, (b,) = _lstsq()
        ref = api.solve(api.SolveRequest(A=A, b=b, loss="quad",
                                         tol=1e-7, max_iters=300))
        res = api.solve(api.SolveRequest(A=A, b=b, loss="quad",
                                         tol=1e-7, max_iters=300,
                                         telemetry=True))
        np.testing.assert_allclose(np.asarray(res.x), np.asarray(ref.x),
                                   rtol=1e-6, atol=1e-6)
        trace = res.info["trace"]
        assert trace["spans"] >= 1
        assert "api.solve" in trace["phases"]
        assert "trace" not in ref.info       # off by default

    def test_traced_elastic_solve_covers_solver_phases(self, tmp_path):
        """The elastic (checkpointing) path is the fully-instrumented one:
        per-iteration spans, checkpoint spans, and fusedgrad plan-vs-actual
        records that feed calibration."""
        A, (b,) = _lstsq(m=150, n=10)
        rec = telemetry.Recorder()
        res = api.solve(api.SolveRequest(
            A=A, b=b, loss="quad", tol=1e-7, max_iters=300,
            checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=10,
            telemetry=rec))
        assert res.info["converged"]
        phases = set(res.info["trace"]["phases"])
        for name in ("api.solve", "solver.iteration", "solver.fused_pass",
                     "solver.seed_pass", "solver.checkpoint"):
            assert name in phases, (name, phases)
        pva = res.info["trace"]["plan_vs_actual"]
        assert pva["fusedgrad"]["records"] >= 1
        assert pva["fusedgrad"]["ratio"] > 0

    def test_plan_vs_actual_records_calibrate(self):
        """The acceptance property: traced records round-trip into
        MachineModel.calibrate and measurably tighten the model."""
        A, (b,) = _lstsq(m=200, n=16)
        rec = telemetry.Recorder()
        api.solve(api.SolveRequest(A=A, b=b, loss="quad", tol=0.0,
                                   max_iters=40, deadline_s=1e9,
                                   telemetry=rec))
        recs = rec.calibration_records()
        assert len(recs) >= 5
        for r in recs:
            assert r["op"] == "fusedgrad"
            assert {"flops", "hbm_bytes", "measured_s", "modeled_s",
                    "blocks"} <= set(r)
        mach = machine.builtin(jax.default_backend())
        before = mach.error(recs)
        fitted = mach.calibrate(recs)
        assert fitted.error(recs) < before

    def test_recorder_accumulates_across_requests(self):
        A, bs = _lstsq(k=2)
        rec = telemetry.Recorder()
        for b in bs:
            api.solve(api.SolveRequest(A=A, b=b, loss="quad", tol=1e-6,
                                       max_iters=200, telemetry=rec))
        assert sum(1 for s in rec.spans if s.name == "api.solve") == 2

    def test_traced_svd_and_similarities(self):
        A, _ = _lstsq(m=96, n=12)
        R = RowMatrix.create(jnp.asarray(A))
        r1 = api.svd(api.SvdRequest(A=R, k=3, telemetry=True))
        assert "api.svd" in r1.info["trace"]["phases"]
        r2 = api.similarities(api.SimilarityRequest(A=R, telemetry=True))
        assert "api.similarities" in r2.info["trace"]["phases"]


def _profiled(tmp_path, fn):
    """Run `fn` under jax.profiler.trace and return the host events named
    ``repro.*`` as (name, start_ns, end_ns, stats)."""
    import glob
    with jax.profiler.trace(str(tmp_path)):
        fn()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
             dict(ev.stats))
            for plane in data.planes if plane.name.startswith("/host:CPU")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("repro.")]


def _inside(events, outer, inner):
    """Every `inner` event lies in time inside some `outer` event."""
    outs = [e for e in events if e[0] == outer]
    ins = [e for e in events if e[0] == inner]
    return bool(ins) and all(any(o[1] <= i[1] and i[2] <= o[2] for o in outs)
                             for i in ins)


class TestProfilerClock:
    def test_profiler_trace_nests_layer_spans(self, tmp_path):
        """One api.solve and one Gram-mode api.svd under the JAX profiler,
        with no recorder: their layer spans are annotations on the
        profiler's clock, nested by time, the api spans carrying the
        request id."""
        A, (b,) = _lstsq(m=256, n=16)
        R = RowMatrix.create(jnp.asarray(A))
        sreq = api.SolveRequest(A=R, b=b, loss="quad", tol=1e-6,
                                max_iters=100)
        vreq = api.SvdRequest(A=R, k=3, mode="gram")

        def jobs():
            jax.block_until_ready(api.solve(sreq).x)
            jax.block_until_ready(api.svd(vreq).factors[1])
        ev = _profiled(tmp_path, jobs)
        names = {e[0] for e in ev}
        assert {"repro.api.solve", "repro.solve.setup", "repro.solve.loop",
                "repro.planner.plan", "repro.api.svd", "repro.svd.fetch",
                "repro.svd.eigh", "repro.svd.recover_u"} <= names, names
        for inner in ("repro.solve.setup", "repro.solve.loop"):
            assert _inside(ev, "repro.api.solve", inner), inner
        for inner in ("repro.svd.fetch", "repro.svd.eigh",
                      "repro.svd.recover_u", "repro.collective.gram"):
            assert _inside(ev, "repro.api.svd", inner), inner
        fetch, = [e for e in ev if e[0] == "repro.svd.fetch"]
        eigh, = [e for e in ev if e[0] == "repro.svd.eigh"]
        assert fetch[2] <= eigh[1]
        apis = {e[0]: e[3] for e in ev if e[0].startswith("repro.api.")}
        assert apis["repro.api.solve"] == {"request_id": sreq.request_id}
        assert apis["repro.api.svd"] == {"request_id": vreq.request_id}
        assert "request_id" not in next(
            e[3] for e in ev if e[0] == "repro.planner.plan")

    def test_programs_build_in_the_first_solve_only(self, tmp_path,
                                                    monkeypatch):
        """Two api.solve calls under the profiler, from an empty program
        cache: one repro.distmat.build span per RowMatrix program built,
        all inside the first call; the second call builds none."""
        monkeypatch.setattr(distmat_types, "_programs",
                            collections.OrderedDict())
        A, (b,) = _lstsq(m=4096, n=64)
        req = api.SolveRequest(A=RowMatrix.create(jnp.asarray(A)), b=b,
                               loss="quad", tol=1e-6, max_iters=100,
                               telemetry=telemetry.Recorder())
        first = {}

        def jobs():
            jax.block_until_ready(api.solve(req).x)
            first.update(req.telemetry.counters("distmat.program"))
            jax.block_until_ready(api.solve(req).x)
        ev = _profiled(tmp_path, jobs)
        solves = sorted((e for e in ev if e[0] == "repro.api.solve"),
                        key=lambda e: e[1])
        builds = [e for e in ev if e[0] == "repro.distmat.build"]
        assert len(solves) == 2
        assert len(builds) == first["result=miss"] >= 2
        assert all(solves[0][1] <= e[1] and e[2] <= solves[0][2]
                   for e in builds)
        both = req.telemetry.counters("distmat.program")
        assert both["result=miss"] == first["result=miss"]
        assert both["result=hit"] > first.get("result=hit", 0)

    def test_null_path_annotates_but_records_nothing(self, tmp_path):
        A, (b,) = _lstsq()
        null = telemetry.current()
        assert null is telemetry.NULL
        ev = _profiled(tmp_path, lambda: jax.block_until_ready(api.solve(
            api.SolveRequest(A=A, b=b, loss="quad", tol=1e-6,
                             max_iters=100)).x))
        assert {"repro.api.solve", "repro.solve.loop"} <= {e[0] for e in ev}
        assert null.spans == [] and null.plan_actual() == []
        assert null.snapshot()["counters"] == {}

    def test_spanless_recorder_annotates(self, tmp_path):
        """Recorder(spans=False), the server's always-on mode, keeps its
        metrics and puts its spans on the profiler's clock only."""
        rec = telemetry.Recorder(spans=False)

        def work():
            with rec.span("serve.admit", request_id="r-1"):
                rec.counter("n").inc()
        ev = _profiled(tmp_path, work)
        assert [(e[0], e[3]) for e in ev] == [
            ("repro.serve.admit", {"request_id": "r-1"})]
        assert rec.spans == [] and rec.counter("n").value == 1

    def test_fused_solve_records_only_the_eager_call(self):
        """The fused TFOCS engine calls RowMatrix.fused_grad once eagerly
        (the seed pass) and once under the while_loop's trace: only the
        eager call writes a span and a plan-vs-actual record."""
        A, (b,) = _lstsq(m=4096, n=64)
        rec = telemetry.Recorder()
        res = api.solve(api.SolveRequest(
            A=RowMatrix.create(jnp.asarray(A)), b=b, loss="quad",
            tol=1e-6, max_iters=100, telemetry=rec))
        assert res.info["plan"] == "fused"
        grads = [s for s in rec.spans if s.name == "collective.fused_grad"]
        assert len(grads) == 1 and grads[0].dur_s > 0
        assert [r["op"] for r in rec.plan_actual()] == ["grad"]
        by_id = {s.id: s for s in rec.spans}
        assert by_id[grads[0].parent].name == "solve.loop"

    @pytest.mark.parametrize("recorder", [True, False],
                             ids=["recorder", "null"])
    def test_span_under_trace_is_a_named_scope(self, recorder):
        rec = telemetry.Recorder() if recorder else telemetry.NULL

        def f(x):
            with rec.span("collective.rmatvec", n=3) as sp:
                sp.sync_on(x)
                return jnp.sin(x) * 2
        text = jax.jit(f).lower(1.0).as_text(debug_info=True)
        assert "collective.rmatvec" in text
        assert rec.spans == [] and rec.plan_actual() == []


class TestServerMetrics:
    def test_stats_view_and_degraded_breakdown(self):
        """`stats` renders from typed counters, and the degraded count is
        distinguishable by reason — shed (overloaded) here."""
        from repro.launch.serve import SolverServer
        A, bs = _lstsq(m=96, n=12, k=5)
        srv = SolverServer(slots=2, max_pending=2)
        ids = [srv.submit(api.SolveRequest(A=A, b=b, loss="quad",
                                           tol=1e-6, max_iters=200))
               for b in bs]
        srv.run()
        s = srv.stats
        assert s["admitted"] + s["shed"] == len(bs)
        assert s["shed"] >= 1
        assert s["degraded"].get("overloaded") == s["shed"]
        shed = [i for i in ids if srv.result(i).info["degraded"]
                == "overloaded"]
        assert len(shed) == s["shed"]

    def test_latency_histograms_nontrivial(self):
        from repro.launch.serve import SolverServer
        A, bs = _lstsq(m=96, n=12, k=4)
        srv = SolverServer(slots=4)
        for b in bs:
            srv.submit(api.SolveRequest(A=A, b=b, loss="quad",
                                        tol=1e-6, max_iters=200))
        srv.run()
        lat = srv.tel.histogram("serve.latency_s")
        wait = srv.tel.histogram("serve.queue_wait_s")
        assert lat.count == len(bs) and wait.count == len(bs)
        assert 0 < lat.percentile(0.5) <= lat.percentile(0.99)

    def test_server_spans_ride_ambient_recorder(self):
        """A server constructed under telemetry.recording() traces its
        scheduler actions; one constructed outside records metrics only."""
        from repro.launch.serve import SolverServer
        A, bs = _lstsq(m=96, n=12, k=2)
        rec = telemetry.Recorder()
        with telemetry.recording(rec):
            srv = SolverServer(slots=2)
            for b in bs:
                srv.submit(api.SolveRequest(A=A, b=b, loss="quad",
                                            tol=1e-6, max_iters=200))
            srv.run()
        names = {s.name for s in rec.spans}
        assert {"serve.admit", "serve.retire"} <= names

        plain = SolverServer(slots=2)
        assert plain.tel.spans == []        # private spanless recorder


@pytest.mark.fault
class TestFaultEpisodeTrace:
    def test_span_tree_covers_recovery_phases(self, tmp_path):
        """THE observability acceptance property: a solve that hits an
        injected straggler produces a span tree covering iterate /
        collective / checkpoint / re-mesh, with the trip and re-mesh
        visible as counters."""
        from repro.core.distmat.types import make_mesh
        from repro.core.optim.elastic import (ElasticConfig, ElasticGroup,
                                              SolveCheckpoint)
        from repro.core.tfocs.linop import LinopMatrix
        from repro.train.faults import FaultPlan, FaultyLinop, FaultyMesh
        from repro.train.straggler import ShardMonitor, StragglerConfig

        A, bs = _lstsq(m=256, n=16, k=2, seed=9)
        mesh = make_mesh((jax.device_count(), 1), ("data", "model"))
        fm = FaultyMesh(mesh)
        lin = FaultyLinop(
            LinopMatrix(RowMatrix.create(jnp.asarray(A), mesh)),
            FaultPlan(shard_delays={0: 0.2}, delay_from=4),
            sleep=lambda _dt: None)
        cfg = ElasticConfig(
            monitor=ShardMonitor(lin.row_shards(),
                                 StragglerConfig(warmup_steps=2,
                                                 threshold=2.0,
                                                 trip_limit=2)),
            remesh_to=fm.drop,
            checkpoint=SolveCheckpoint(tmp_path / "ck", every=5,
                                       async_save=False))
        rec = telemetry.Recorder()
        with telemetry.recording(rec):
            grp = ElasticGroup(lin, "quad", slots=2, elastic=cfg)
            for b in bs:
                grp.admit_slot(b, tol=1e-7)
            while grp.busy() and grp.iteration < 200:
                grp.step_iteration()
        assert grp.remeshes >= 1 and fm.casualties == [0]

        names = {s.name for s in rec.spans}
        for phase in ("solver.iteration", "solver.fused_pass",
                      "solver.checkpoint", "solver.remesh",
                      "solver.rejit"):
            assert phase in names, (phase, names)
        assert rec.counter("solver.remeshes").value >= 1
        assert rec.counter("straggler.trips").value >= 1

        # phase nesting: remesh and fused_pass spans parent to iterations
        by_id = {s.id: s for s in rec.spans}
        for s in rec.spans:
            if s.name in ("solver.fused_pass", "solver.remesh"):
                assert by_id[s.parent].name == "solver.iteration"

        remesh = [s for s in rec.spans if s.name == "solver.remesh"]
        assert remesh and all(s.dur_s > 0 and s.t_start_s >= 0
                              for s in remesh)


# =========================================================================
# Result.info standardized-key contract (every public entry point)
# =========================================================================

_STD_KEYS = ("iterations", "a_passes", "converged", "plan", "degraded")


def _assert_std(info, where):
    for key in _STD_KEYS:
        assert key in info, (where, key, sorted(info))


class TestResultInfoContract:
    def test_solve_direct_gra(self):
        A, (b,) = _lstsq()
        res = api.solve(api.SolveRequest(A=A, b=b, loss="quad",
                                         tol=1e-7, max_iters=300))
        _assert_std(res.info, "solve/gra")
        assert res.info["degraded"] is None
        # deprecated alias of plan == "fused", one release of grace
        assert res.info["fused"] == (res.info["plan"] == "fused")

    def test_solve_direct_lbfgs_alias(self):
        A, (b,) = _lstsq()
        res = api.solve(api.SolveRequest(A=A, b=b, loss="quad",
                                         method="lbfgs", tol=1e-7,
                                         max_iters=300))
        _assert_std(res.info, "solve/lbfgs")
        # n_evals stays as the native count; a_passes is the currency
        assert int(res.info["a_passes"]) >= int(res.info["n_evals"])

    def test_solve_elastic_path(self, tmp_path):
        A, (b,) = _lstsq()
        res = api.solve(api.SolveRequest(
            A=A, b=b, loss="quad", tol=1e-7, max_iters=300,
            checkpoint_dir=str(tmp_path / "ck")))
        _assert_std(res.info, "solve/elastic")
        assert res.info["converged"]

    def test_solve_served_path(self):
        from repro.launch.serve import SolverServer
        A, (b,) = _lstsq()
        srv = SolverServer(slots=2)
        rid = srv.submit(api.SolveRequest(A=A, b=b, loss="quad",
                                          tol=1e-7, max_iters=300))
        srv.run()
        _assert_std(srv.result(rid).info, "solve/served")

    @pytest.mark.parametrize("mode", ["gram", "lanczos", "randomized"])
    def test_svd_modes_and_aliases(self, mode):
        A, _ = _lstsq(m=128, n=16)
        R = RowMatrix.create(jnp.asarray(A))
        res = api.svd(api.SvdRequest(A=R, k=3, mode=mode))
        _assert_std(res.info, f"svd/{mode}")
        assert res.info["plan"] == mode
        if mode == "randomized":      # deprecated native alias
            assert res.info["a_passes"] == res.info["passes_over_A"]
        if mode == "lanczos":
            assert res.info["iterations"] == res.info["restarts"]
            assert res.info["mode"] == "lanczos"
        if mode == "gram":
            assert res.info["mode"] == "gram"

    def test_similarities(self):
        A, _ = _lstsq(m=96, n=12)
        res = api.similarities(api.SimilarityRequest(
            A=RowMatrix.create(jnp.asarray(A))))
        _assert_std(res.info, "similarities")
