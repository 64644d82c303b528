"""Runtime telemetry — spans, metrics, and plan-vs-actual tracing.

The planner (launch/planner.py) predicts where time goes; this module
records where it actually went.  Gittens et al. and Dünner et al.
(PAPERS.md) both built their Spark analyses on exactly this kind of
instrumentation — per-phase compute-vs-communication breakdowns feeding a
calibrated performance model — and ``MachineModel.calibrate()`` closes the
same loop here: every traced solve emits plan-vs-actual records that
``planner.calibrate()`` accepts directly.

Stdlib only at import; jax is looked up on the first span (and for the
optional device sync).  Three layers:

  * **Spans** — nestable, thread-safe wall-clock intervals at the layer
    boundaries of a job (api request, planner decision, solver set-up and
    loop, distributed ops, the SVD driver's Gram fetch, ``eigh`` and U
    recovery), around every elastic-solver iteration phase and every
    server scheduler action.  Every span is also a
    ``jax.profiler.TraceAnnotation`` named ``"repro." + name`` (with the
    span's ``request_id`` as metadata, where it has one), so a profiler
    trace shows it on the device's clock beside the device ops — with or
    without a recorder.  Under a JAX trace (a jitted function, a
    ``while_loop`` body, a ``shard_map`` body) a span is a
    ``jax.named_scope`` instead and records nothing: its clock would time
    the tracer, not the work.  ``sync_on()`` blocks on a device payload
    before a recorded span closes so the duration covers the device work,
    not just the dispatch.

  * **Metrics** — a registry of counters, gauges and histograms with FIXED
    log-spaced buckets (two histograms are always mergeable/comparable),
    giving the server real p50/p99 queue-wait and solve latency, per-reason
    ``degraded`` counters, fault/retry/remesh counters, and checkpoint
    write-duration/backlog gauges.

  * **Plan-vs-actual** — ``record_plan_actual(plan, measured_s)`` attaches
    the modeled cost of an ``ExecutionPlan`` to its measured wall time; for
    kernel ops the record carries the raw roofline terms, so
    ``calibration_records()`` feeds straight into ``planner.calibrate()``
    and modeled-vs-measured drift is visible in ``Result.info["trace"]``.

Exporters: ``snapshot()`` (in-memory, JSON-safe) and ``export_jsonl(path)``
(one event per line).  For a timeline, run the work under
``jax.profiler.trace(dir)``: the spans are in it beside the device ops.

Recording is OFF by default: the module-level recorder is a
``NullRecorder`` whose ``span()`` opens only the profiler annotation (no
bookkeeping, no device sync; about a microsecond) and whose metric
handles are one shared no-op.  Components resolve ``current()`` at call
time, so

    rec = telemetry.enable()           # or: with telemetry.recording() as rec
    ... run solves / serve requests ...
    rec.snapshot(); rec.export_jsonl("events.jsonl")

instruments the whole stack without threading a recorder through every
constructor (explicit ``telemetry=`` parameters on the api request objects
and SolverServer override the module default).  See the "observability"
section of examples/quickstart.py.
"""
from __future__ import annotations

import contextlib
import json
import math
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Mapping

__all__ = [
    "Counter", "Gauge", "Histogram", "Recorder", "NullRecorder", "Span",
    "Timing", "current", "enable", "disable", "recording", "timeit",
    "HIST_BOUNDS",
]


# -- fixed log-spaced histogram buckets ---------------------------------------
# 1 µs … ~1100 s in ×2 steps.  Fixed bounds (not per-instance) so any two
# histograms — a live server's and a benchmark's — merge and compare
# bucket-for-bucket.  Out-of-range observations clamp into the edge buckets.
HIST_MIN = 1e-6
HIST_FACTOR = 2.0
HIST_BUCKETS = 31
HIST_BOUNDS = tuple(HIST_MIN * HIST_FACTOR ** i for i in range(HIST_BUCKETS))
_LOG_MIN = math.log(HIST_MIN)
_LOG_FACTOR = math.log(HIST_FACTOR)


def _bucket_index(v: float) -> int:
    if v <= HIST_MIN:
        return 0
    i = int((math.log(v) - _LOG_MIN) / _LOG_FACTOR)
    return min(max(i, 0), HIST_BUCKETS - 1)


def _label_key(name: str, labels: Mapping[str, Any]) -> str:
    if not labels:
        return name
    inner = ",".join(f"{k}={labels[k]}" for k in sorted(labels))
    return f"{name}{{{inner}}}"


class Counter:
    """Monotone event count (thread-safe)."""
    __slots__ = ("name", "labels", "value", "_lock")

    def __init__(self, name: str, labels: Mapping[str, Any]):
        self.name, self.labels = name, dict(labels)
        self.value = 0
        self._lock = threading.Lock()

    def inc(self, n: int = 1) -> int:
        with self._lock:
            self.value += n
            return self.value


class Gauge:
    """Last-write-wins level (thread-safe enough: float stores are atomic)."""
    __slots__ = ("name", "labels", "value")

    def __init__(self, name: str, labels: Mapping[str, Any]):
        self.name, self.labels = name, dict(labels)
        self.value = float("nan")

    def set(self, v: float) -> None:
        self.value = float(v)


class Histogram:
    """Log-spaced-bucket histogram of seconds (thread-safe).

    Percentiles interpolate inside the chosen bucket geometrically and are
    clamped to the observed [min, max], so a histogram fed one constant
    value reports that value at every quantile.
    """
    __slots__ = ("name", "labels", "counts", "count", "sum", "min", "max",
                 "_lock")

    def __init__(self, name: str, labels: Mapping[str, Any]):
        self.name, self.labels = name, dict(labels)
        self.counts = [0] * HIST_BUCKETS
        self.count = 0
        self.sum = 0.0
        self.min = math.inf
        self.max = -math.inf
        self._lock = threading.Lock()

    def observe(self, v: float) -> None:
        v = float(v)
        with self._lock:
            self.counts[_bucket_index(v)] += 1
            self.count += 1
            self.sum += v
            self.min = min(self.min, v)
            self.max = max(self.max, v)

    def percentile(self, q: float) -> float:
        """Approximate q-quantile (q in [0, 1]) from the bucket counts."""
        if self.count == 0:
            return float("nan")
        target = q * self.count
        seen = 0.0
        for i, c in enumerate(self.counts):
            if c == 0:
                continue
            if seen + c >= target:
                lo = HIST_BOUNDS[i]
                hi = lo * HIST_FACTOR
                frac = min(max((target - seen) / c, 0.0), 1.0)
                v = lo * (hi / lo) ** frac          # geometric interpolation
                return min(max(v, self.min), self.max)
            seen += c
        return self.max

    def snapshot(self) -> dict:
        return {"count": self.count, "sum_s": self.sum,
                "min_s": self.min if self.count else None,
                "max_s": self.max if self.count else None,
                "mean_s": (self.sum / self.count) if self.count else None,
                "p50_s": self.percentile(0.50) if self.count else None,
                "p90_s": self.percentile(0.90) if self.count else None,
                "p99_s": self.percentile(0.99) if self.count else None}


# -- spans --------------------------------------------------------------------

# Prefix of every span's name on the profiler's timeline.
PROFILER_PREFIX = "repro."
# jax.profiler.TraceAnnotation, jax.named_scope and jax.core.trace_ctx,
# looked up on the first span so that importing this module needs no jax.
_annotation_cls = _named_scope = _trace_ctx = None


def _load_jax() -> None:
    global _annotation_cls, _named_scope, _trace_ctx
    import jax
    _annotation_cls = jax.profiler.TraceAnnotation
    _named_scope = jax.named_scope
    _trace_ctx = jax.core.trace_ctx


@dataclass
class Span:
    """One closed interval on one thread's span stack."""
    id: int
    parent: int | None
    name: str
    tid: int
    t_start_s: float            # seconds since the recorder's epoch
    dur_s: float = 0.0
    attrs: dict = field(default_factory=dict)


class _SpanCtx:
    """Context manager for one recorded span; created by Recorder.span()
    outside any JAX trace.  It holds the span's profiler annotation open
    for as long as it times the span."""
    __slots__ = ("_rec", "_span", "_t0", "_payload", "_mark")

    def __init__(self, rec: "Recorder", name: str, attrs: dict, mark):
        self._rec = rec
        self._payload = None
        self._mark = mark
        tid = threading.get_ident()
        stack = rec._stack()
        parent = stack[-1] if stack else None
        self._span = Span(id=rec._next_id(), parent=parent, name=name,
                          tid=tid, t_start_s=0.0, attrs=attrs)

    def annotate(self, **attrs) -> "_SpanCtx":
        self._span.attrs.update(attrs)
        return self

    def sync_on(self, payload) -> "_SpanCtx":
        """Block on `payload` (any jax pytree) before the span closes, so
        the duration covers the device work the span launched."""
        self._payload = payload
        return self

    @property
    def dur_s(self) -> float:
        return self._span.dur_s

    def __enter__(self) -> "_SpanCtx":
        self._mark.__enter__()
        self._rec._stack().append(self._span.id)
        self._t0 = time.perf_counter()
        self._span.t_start_s = self._t0 - self._rec.epoch
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._payload is not None:
            _block_until_ready(self._payload)
        self._span.dur_s = time.perf_counter() - self._t0
        stack = self._rec._stack()
        if stack and stack[-1] == self._span.id:
            stack.pop()
        if exc_type is not None:
            self._span.attrs["error"] = f"{exc_type.__name__}: {exc}" \
                if exc is not None else exc_type.__name__
        self._rec._commit(self._span)
        self._mark.__exit__(exc_type, exc, tb)


def _block_until_ready(payload) -> None:
    try:
        import jax
        jax.block_until_ready(payload)
    except ImportError:  # pragma: no cover - jax is always present here
        pass


class _MarkCtx:
    """A span that records nothing: it only enters its mark (a profiler
    annotation, or a named scope under a JAX trace).  The span API's
    annotate / sync_on are no-ops, so the caller's code runs exactly as
    it would with no span at all."""
    __slots__ = ("_mark",)
    dur_s = 0.0

    def __init__(self, mark):
        self._mark = mark

    def annotate(self, **attrs):
        return self

    def sync_on(self, payload):
        return self

    def __enter__(self):
        self._mark.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        self._mark.__exit__(exc_type, exc, tb)


class _NullMetric:
    """Shared no-op counter/gauge/histogram."""
    __slots__ = ()
    value = 0
    count = 0

    def inc(self, n: int = 1) -> int:
        return 0

    def set(self, v: float) -> None:
        return None

    def observe(self, v: float) -> None:
        return None

    def percentile(self, q: float) -> float:
        return float("nan")

    def snapshot(self) -> dict:
        return {}


_NULL_METRIC = _NullMetric()


# -- the recorder -------------------------------------------------------------

class Recorder:
    """One telemetry sink: spans + metrics registry + plan-vs-actual log.

    ``spans=False`` keeps the metrics registry live but makes ``span()``
    open only the profiler annotation, as the null recorder does — the
    mode SolverServer uses for its always-on counters.  ``max_spans``
    bounds memory on long-lived recorders: past it, new spans are dropped
    and counted in ``spans_dropped``.
    """
    enabled = True

    def __init__(self, *, spans: bool = True, max_spans: int = 100_000):
        self.record_spans = spans
        self.max_spans = int(max_spans)
        self.epoch = time.perf_counter()
        self.spans: list[Span] = []
        self.spans_dropped = 0
        self._metrics: dict[str, Any] = {}
        self._plan_actual: list[dict] = []
        self._lock = threading.Lock()
        self._ids = iter(range(1, 1 << 62)).__next__
        self._local = threading.local()

    # -- span plumbing --------------------------------------------------------

    def _stack(self) -> list[int]:
        s = getattr(self._local, "stack", None)
        if s is None:
            s = self._local.stack = []
        return s

    def _next_id(self) -> int:
        with self._lock:
            return self._ids()

    def _commit(self, span: Span) -> None:
        with self._lock:
            if len(self.spans) >= self.max_spans:
                self.spans_dropped += 1
                return
            self.spans.append(span)

    def span(self, name: str, **attrs):
        """Open a nested span; use as ``with rec.span("phase") as sp:``.
        It is a profiler annotation ``repro.<name>`` too, with the span's
        ``request_id`` as metadata where it has one.  Under a JAX trace it
        is a named scope and records nothing: its clock would time the
        tracer, not the work."""
        if _trace_ctx is None:
            _load_jax()
        if not _trace_ctx.is_top_level():
            return _MarkCtx(_named_scope(name))
        rid = attrs.get("request_id")
        mark = _annotation_cls(PROFILER_PREFIX + name) if rid is None \
            else _annotation_cls(PROFILER_PREFIX + name, request_id=rid)
        if not self.record_spans:
            return _MarkCtx(mark)
        return _SpanCtx(self, name, attrs, mark)

    # -- metrics registry -----------------------------------------------------

    def _metric(self, cls, name: str, labels: Mapping[str, Any]):
        key = _label_key(name, labels)
        m = self._metrics.get(key)
        if m is None:
            with self._lock:
                m = self._metrics.setdefault(key, cls(name, labels))
        return m

    def counter(self, name: str, **labels) -> Counter:
        return self._metric(Counter, name, labels)

    def gauge(self, name: str, **labels) -> Gauge:
        return self._metric(Gauge, name, labels)

    def histogram(self, name: str, **labels) -> Histogram:
        return self._metric(Histogram, name, labels)

    def counters(self, name: str) -> dict[str, int]:
        """{label-suffix: value} for every counter named `name` (the
        per-reason breakdown view, e.g. ``counters("serve.degraded")``)."""
        out = {}
        for m in list(self._metrics.values()):
            if isinstance(m, Counter) and m.name == name:
                lbl = ",".join(f"{k}={v}" for k, v in sorted(m.labels.items()))
                out[lbl or "total"] = m.value
        return out

    # -- plan-vs-actual -------------------------------------------------------

    def record_plan_actual(self, plan, measured_s: float, **attrs) -> dict:
        """Attach a measured wall time to an ExecutionPlan.  The stored
        record carries op/choice/modeled/measured/ratio (drift is
        ``ratio``), plus — for kernel ops — the raw roofline terms, so it
        feeds ``planner.calibrate()`` unchanged."""
        from repro.launch import planner as _planner
        rec = _planner.actual_record(plan, measured_s)
        rec.update(attrs)
        with self._lock:
            self._plan_actual.append(rec)
        return rec

    def plan_actual(self) -> list[dict]:
        with self._lock:
            return list(self._plan_actual)

    def calibration_records(self) -> list[dict]:
        """The plan-vs-actual records that carry raw roofline terms — the
        exact shape ``planner.calibrate()`` / ``MachineModel.calibrate()``
        consume."""
        return [r for r in self.plan_actual() if "flops" in r]

    # -- exporters ------------------------------------------------------------

    def snapshot(self) -> dict:
        """JSON-safe point-in-time view of every metric + span/record
        counts."""
        counters, gauges, hists = {}, {}, {}
        for key, m in sorted(self._metrics.items()):
            if isinstance(m, Counter):
                counters[key] = m.value
            elif isinstance(m, Gauge):
                gauges[key] = None if math.isnan(m.value) else m.value
            else:
                hists[key] = m.snapshot()
        return {"counters": counters, "gauges": gauges,
                "histograms": hists, "spans": len(self.spans),
                "spans_dropped": self.spans_dropped,
                "plan_actual_records": len(self._plan_actual)}

    def summary(self) -> dict:
        """Compact per-solve digest for ``Result.info["trace"]``: total
        time per span phase plus the plan-vs-actual drift per op."""
        phases: dict[str, dict] = {}
        with self._lock:
            spans = list(self.spans)
            pa = list(self._plan_actual)
        for s in spans:
            p = phases.setdefault(s.name, {"count": 0, "total_s": 0.0,
                                           "max_s": 0.0})
            p["count"] += 1
            p["total_s"] += s.dur_s
            p["max_s"] = max(p["max_s"], s.dur_s)
        drift: dict[str, dict] = {}
        for r in pa:
            d = drift.setdefault(r["op"], {"records": 0, "modeled_s": 0.0,
                                           "measured_s": 0.0})
            d["records"] += 1
            d["modeled_s"] += r["modeled_s"]
            d["measured_s"] += r["measured_s"]
        for d in drift.values():
            d["ratio"] = (d["measured_s"] / d["modeled_s"]
                          if d["modeled_s"] > 0 else None)
        return {"spans": len(spans), "phases": phases,
                "plan_vs_actual": drift,
                "counters": {k: v for k, v in
                             self.snapshot()["counters"].items()}}

    def events(self) -> list[dict]:
        """Every recorded event as a JSON-safe dict (the JSONL payload)."""
        out = []
        with self._lock:
            spans = list(self.spans)
            pa = list(self._plan_actual)
        for s in spans:
            out.append({"type": "span", "id": s.id, "parent": s.parent,
                        "name": s.name, "tid": s.tid,
                        "t_start_s": s.t_start_s, "dur_s": s.dur_s,
                        "attrs": s.attrs})
        for r in pa:
            out.append(dict(r, type="plan_actual"))
        snap = self.snapshot()
        for kind in ("counters", "gauges"):
            for key, v in snap[kind].items():
                out.append({"type": kind[:-1], "key": key, "value": v})
        for key, h in snap["histograms"].items():
            out.append(dict(h, type="histogram", key=key))
        return out

    def export_jsonl(self, path) -> int:
        """Write one JSON event per line; returns the event count."""
        evs = self.events()
        with open(path, "w") as f:
            for e in evs:
                f.write(json.dumps(e, default=_json_default) + "\n")
        return len(evs)

    def clear(self) -> None:
        with self._lock:
            self.spans.clear()
            self.spans_dropped = 0
            self._metrics.clear()
            self._plan_actual.clear()


def _json_default(v):
    try:
        return float(v)
    except (TypeError, ValueError):
        return str(v)


class NullRecorder(Recorder):
    """The disabled default: a span is its profiler annotation alone, and
    every other operation is a no-op returning a shared singleton — the
    near-zero-overhead path the escape hatches buy out of."""
    enabled = False

    def __init__(self):
        super().__init__(spans=False, max_spans=0)

    def counter(self, name: str, **labels):
        return _NULL_METRIC

    def gauge(self, name: str, **labels):
        return _NULL_METRIC

    def histogram(self, name: str, **labels):
        return _NULL_METRIC

    def record_plan_actual(self, plan, measured_s: float, **attrs) -> dict:
        return {}


NULL = NullRecorder()
_current: Recorder = NULL


def current() -> Recorder:
    """The active module-level recorder (a NullRecorder unless enabled)."""
    return _current


def enable(recorder: Recorder | None = None) -> Recorder:
    """Install `recorder` (or a fresh one) as the module default; every
    component that resolves ``current()`` starts recording into it."""
    global _current
    _current = recorder if recorder is not None else Recorder()
    return _current


def disable() -> None:
    global _current
    _current = NULL


@contextlib.contextmanager
def recording(recorder: Recorder | None = None):
    """Scoped enable(): installs a recorder for the body, restores the
    previous one after — the api-level ``telemetry=`` escape hatch uses
    this so one traced request never leaks instrumentation into the
    next."""
    global _current
    prev = _current
    rec = recorder if recorder is not None else Recorder()
    _current = rec
    try:
        yield rec
    finally:
        _current = prev


# -- the shared timing helper -------------------------------------------------

@dataclass
class Timing:
    """Warm repeated-call timing: the one measurement path shared by the
    benchmarks' BENCH json and the live metrics (same block-until-ready
    discipline, same statistics)."""
    times: list[float]

    @property
    def median_s(self) -> float:
        s = sorted(self.times)
        return s[len(s) // 2]

    @property
    def mean_s(self) -> float:
        return sum(self.times) / len(self.times)

    @property
    def min_s(self) -> float:
        return min(self.times)

    @property
    def mean_us(self) -> float:
        return self.mean_s * 1e6

    @property
    def median_us(self) -> float:
        return self.median_s * 1e6


def timeit(fn: Callable[[], Any], *, reps: int = 3, warmup: int = 1,
           hist: Histogram | None = None) -> Timing:
    """Time ``fn()`` over `reps` warm calls (after `warmup` compile-eating
    calls), blocking on each call's result so async dispatch doesn't leak
    between reps.  Every benchmark timing loop routes through here; pass
    ``hist=`` to additionally feed a live histogram so offline BENCH
    numbers and online metrics share one measurement path."""
    for _ in range(warmup):
        _block_until_ready(fn())
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        _block_until_ready(fn())
        dt = time.perf_counter() - t0
        times.append(dt)
        if hist is not None:
            hist.observe(dt)
    return Timing(times=times)
