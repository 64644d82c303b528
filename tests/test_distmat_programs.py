"""RowMatrix runs each shard_map body as a jitted program built once per
static signature (types.program): a repeated call compiles nothing,
computes exactly what a freshly built program of the same body computes,
keeps programs of different statics and meshes apart, counts its hits and
misses, and keeps no matrix alive.

Against the same body run eagerly (op by op, as an uncached shard_map
call runs it) the compiled program may round differently in the last
bits on the CPU, where XLA fuses the body's ops; that comparison is held
to a few f32 ulps of the result's scale."""
import collections
import gc
import os
import subprocess
import sys
import textwrap
import threading
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.distmat import RowMatrix
from repro.core.distmat import rowmatrix as R
from repro.core.distmat import types as T
from repro.core.tfocs import SmoothLogLoss, SmoothQuad, row_separable
from repro.launch import telemetry

M, N, K = 100, 24, 5
AX = ("data",)
SPEC, ROWS = P(AX, None), P(AX)
F32 = jnp.float32


class Problem:
    """One matrix and the operands every op below takes."""

    def __init__(self, seed=0):
        rng = np.random.default_rng(seed)
        self.A = rng.normal(size=(M, N)).astype(np.float32)
        self.rm = RowMatrix.create(jnp.asarray(self.A))
        self.x = jnp.asarray(rng.normal(size=N), F32)
        self.X = jnp.asarray(rng.normal(size=(3, N)), F32)
        self.u = jnp.asarray(rng.normal(size=M), F32)
        self.B = jnp.asarray(rng.normal(size=(N, K)), F32)
        self.d = jnp.asarray(rng.random(N), F32)
        b = jnp.asarray(rng.normal(size=M), F32)
        w = self.rm._row_mask()
        self.quad = row_separable(SmoothQuad(b=b, weights=w))
        self.logistic = row_separable(SmoothLogLoss(
            y=jnp.sign(b), weights=w))
        self.res = self.rm.init_psum_residual()
        self.Q = self.rm.multiply_local(self.B)


# How the reference runs the body: "jit" builds a fresh program outside
# the cache, "eager" calls the shard_map op by op.
MODE = {"how": "jit"}


def uncached(p, build, in_specs, out_specs, *static):
    """`build`'s body through a T.shard_map of its own, outside the cache."""
    f = T.shard_map(build(AX, *static), p.rm.mesh, in_specs, out_specs)
    return jax.jit(f) if MODE["how"] == "jit" else f


def grad_args(p, sep):
    return p.rm.rows, p.x, sep.target, sep.weights


# name: (the cached call, the same body through a shard_map of its own)
OPS = {
    "row_mask": (
        lambda p: p.rm._row_mask(),
        lambda p: uncached(p, R._mask_body, (), ROWS, M, M, F32)()),
    "gram": (
        lambda p: p.rm.gram(chunks=1),
        lambda p: uncached(p, R._gram_body, (SPEC,), P(), N, 1)(p.rm.rows)),
    "gram_chunked": (
        lambda p: p.rm.gram(chunks=3),
        lambda p: uncached(p, R._gram_body, (SPEC,), P(), N, 3)(p.rm.rows)),
    "matvec": (
        lambda p: p.rm.matvec(p.x),
        lambda p: uncached(p, R._matvec_body, (SPEC, P()), ROWS)(
            p.rm.rows, p.x)),
    "rmatvec": (
        lambda p: p.rm.rmatvec(p.u),
        lambda p: uncached(p, R._rmatvec_body, (SPEC, ROWS), P())(
            p.rm.rows, p.u)),
    "fused_grad": (
        lambda p: p.rm.fused_grad(p.x, p.logistic, chunks=1),
        lambda p: uncached(p, R._fused_grad_body, (SPEC, P(), ROWS, ROWS),
                           (P(), P(), ROWS), 1, "logistic", 1.0, N, 1)(
            *grad_args(p, p.logistic))),
    "fused_grad_chunked": (
        lambda p: p.rm.fused_grad(p.x, p.quad, chunks=4),
        lambda p: uncached(p, R._fused_grad_body, (SPEC, P(), ROWS, ROWS),
                           (P(), P(), ROWS), 1, "quad", 1.0, N, 4)(
            *grad_args(p, p.quad))),
    "fused_grad_int8": (
        lambda p: p.rm.fused_grad(p.x, p.quad, chunks=1, residual=p.res),
        lambda p: uncached(p, R._fused_grad_body,
                           (SPEC, P(), ROWS, ROWS, SPEC),
                           (P(), P(), ROWS, SPEC), 1, "quad", 1.0, N, 1)(
            *grad_args(p, p.quad), p.res)),
    "fused_grad_int8_chunked": (
        lambda p: p.rm.fused_grad(p.x, p.quad, chunks=2, residual=p.res),
        lambda p: uncached(p, R._fused_grad_body,
                           (SPEC, P(), ROWS, ROWS, SPEC),
                           (P(), P(), ROWS, SPEC), 1, "quad", 1.0, N, 2)(
            *grad_args(p, p.quad), p.res)),
    "fused_grad_multi": (
        lambda p: p.rm.fused_grad_multi(p.X, [p.quad] * 3),
        lambda p: uncached(p, R._fused_grad_multi_body,
                           (SPEC, P(), P(None, AX), P(None, AX)),
                           (P(), P(), P(None, AX)), "quad", 1.0)(
            p.rm.rows, p.X, jnp.stack([p.quad.target] * 3),
            jnp.stack([p.quad.weights] * 3))),
    "multiply_local": (
        lambda p: p.rm.multiply_local(p.B).rows,
        lambda p: uncached(p, R._gemm_body, (SPEC, P()), SPEC)(
            p.rm.rows, p.B)),
    "sketch": (
        lambda p: p.rm.sketch(K, seed=7).rows,
        lambda p: uncached(p, R._sketch_body, (SPEC,), SPEC, N, K, 7)(
            p.rm.rows)),
    "project": (
        lambda p: p.rm.project(p.Q),
        lambda p: uncached(p, R._project_body, (SPEC, SPEC), P())(
            p.rm.rows, p.Q.rows)),
    "scale_columns": (
        lambda p: p.rm.scale_columns(p.d).rows,
        lambda p: uncached(p, R._scale_body, (SPEC, P()), SPEC)(
            p.rm.rows, p.d)),
    "column_stats": (
        lambda p: {k: v for k, v in p.rm.column_stats().items()
                   if k in ("num_nonzeros", "min", "max")},
        lambda p: dict(zip(("num_nonzeros", "min", "max"), uncached(
            p, R._stats_body, (SPEC, ROWS), (P(),) * 5)(
            p.rm.rows, p.rm._row_mask())[2:]))),
    "frobenius_norm": (
        lambda p: p.rm.frobenius_norm(),
        lambda p: jnp.sqrt(uncached(p, R._frobenius_body, (SPEC,), P())(
            p.rm.rows))),
}
# Ops whose whole call runs in one cached program plus eager pre- and
# post-processing; each is checked for compiles only.
CALLS = dict({name: call for name, (call, _) in OPS.items()},
             column_similarities=lambda p: p.rm.column_similarities(0.5))


@pytest.fixture(scope="module")
def prob():
    return Problem()


@pytest.fixture
def compiles():
    """The `/jax/core/compile/*` events recorded while the test runs."""
    events = []

    def listen(name, secs, **kw):
        if name.startswith("/jax/core/compile"):
            events.append(name)
    jax.monitoring.register_event_duration_secs_listener(listen)
    yield events
    jax.monitoring.unregister_event_duration_listener(listen)


@pytest.fixture
def fresh(monkeypatch):
    """An empty program cache for the test, the shared one after it."""
    monkeypatch.setattr(T, "_programs", collections.OrderedDict())


@pytest.mark.parametrize("name", sorted(CALLS))
def test_second_call_compiles_nothing(name, prob, compiles):
    call = CALLS[name]
    jax.block_until_ready(call(prob))
    compiles.clear()
    jax.block_until_ready(call(prob))
    assert compiles == []


def run_both(name, prob, how, monkeypatch):
    monkeypatch.setitem(MODE, "how", how)
    call, ref = OPS[name]
    got, want = jax.tree.flatten(call(prob)), jax.tree.flatten(ref(prob))
    assert got[1] == want[1]
    for g, w in zip(got[0], want[0]):
        assert g.dtype == w.dtype and g.shape == w.shape
    return zip(map(np.asarray, got[0]), map(np.asarray, want[0]))


@pytest.mark.parametrize("name", sorted(OPS))
def test_matches_uncached_body(name, prob, monkeypatch):
    for g, w in run_both(name, prob, "jit", monkeypatch):
        assert np.array_equal(g, w), name


# The int8 wire's new residual (leaf 3) is the gradient's partial less
# its quantized value: its rounding is on the gradient's (leaf 1) scale.
SCALE_OF = {"fused_grad_int8": {3: 1}, "fused_grad_int8_chunked": {3: 1}}


@pytest.mark.parametrize("name", sorted(OPS))
def test_close_to_eager_body(name, prob, monkeypatch):
    pairs = list(run_both(name, prob, "eager", monkeypatch))
    for i, (g, w) in enumerate(pairs):
        ref = pairs[SCALE_OF.get(name, {}).get(i, i)][1]
        scale = float(np.abs(ref).max()) if ref.size else 0.0
        np.testing.assert_allclose(g, w, rtol=0,
                                   atol=8 * np.finfo(np.float32).eps * scale)


def test_counts_hits_and_misses(prob, fresh):
    with telemetry.recording(telemetry.Recorder()) as rec:
        prob.rm._row_mask()
        prob.rm._row_mask()
        prob.rm.gram(chunks=1)
        prob.rm.gram(chunks=1)
        prob.rm.gram(chunks=2)
    assert rec.counters("distmat.program") == {"result=miss": 3,
                                               "result=hit": 2}
    builds = [s for s in rec.spans if s.name == "distmat.build"]
    assert len(builds) == 3 and all(s.dur_s > 0 for s in builds)
    gram_spans = {s.id for s in rec.spans if s.name == "collective.gram"}
    assert sum(s.parent in gram_spans for s in builds) == 2


def test_null_recorder_counts_nothing(prob, fresh):
    null = telemetry.current()
    assert null is telemetry.NULL
    prob.rm.gram(chunks=1)
    prob.rm.gram(chunks=1)
    assert null.spans == [] and null.snapshot()["counters"] == {}


def test_cache_holds_the_newest_programs(prob, fresh, monkeypatch):
    monkeypatch.setattr(T, "PROGRAM_CACHE_SIZE", 2)
    with telemetry.recording(telemetry.Recorder()) as rec:
        for c in (1, 2, 3, 3, 1):        # 1 is evicted by 3, built again
            prob.rm.gram(chunks=c)
    assert len(T._programs) == 2
    assert rec.counters("distmat.program") == {"result=miss": 4,
                                               "result=hit": 1}


def test_threads_share_the_cache(fresh, monkeypatch):
    """Threads that look programs up at once lose no count and never grow
    the cache past its size."""
    monkeypatch.setattr(T, "PROGRAM_CACHE_SIZE", 4)
    threads, calls, keys = 16, 200, 8
    errors = []

    def work(i):
        try:
            for j in range(calls):
                T.program(("stress", (i + j) % keys), lambda: abs)
        except Exception as e:          # noqa: BLE001 — reported below
            errors.append(e)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with telemetry.recording(telemetry.Recorder()) as rec:
            pool = [threading.Thread(target=work, args=(i,))
                    for i in range(threads)]
            for t in pool:
                t.start()
            for t in pool:
                t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in pool) and errors == []
    assert sum(rec.counters("distmat.program").values()) == threads * calls
    assert len(T._programs) <= 4


def test_static_values_key_the_program(prob, fresh):
    """Programs differ by every static their body closes over: the seed
    of a sketch, the loss of a fused gradient."""
    a = prob.rm.sketch(K, seed=1).rows
    b = prob.rm.sketch(K, seed=2).rows
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    fq = prob.rm.fused_grad(prob.x, prob.quad, chunks=1)[0]
    fl = prob.rm.fused_grad(prob.x, prob.logistic, chunks=1)[0]
    assert float(fq) != float(fl)
    assert len(T._programs) == 4


def test_deleted_matrix_leaves_no_buffers(prob):
    shape = (997, 29)
    rm = RowMatrix.create(jnp.ones(shape, F32))
    rows = weakref.ref(rm.rows)
    sep = row_separable(SmoothQuad(b=jnp.zeros(shape[0]),
                                   weights=rm._row_mask()))
    jax.block_until_ready((
        rm.gram(), rm.fused_grad(jnp.ones(shape[1]), sep, chunks=1),
        rm.multiply_local(jnp.ones((shape[1], 2))).rows,
        rm.column_stats(), rm.frobenius_norm()))
    del rm, sep
    gc.collect()
    assert rows() is None
    assert not any(a.shape == shape for a in jax.live_arrays())


FOUR_DEV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, numpy as np, jax.numpy as jnp
    assert len(jax.devices()) == 4
    from repro.core.distmat import RowMatrix
    from repro.core.distmat.types import make_mesh
    from repro.launch import telemetry

    # 10 and 11 rows both pad to 12 over 4 shards: the same shapes, two
    # masks.
    rows = make_mesh((4, 1), ("data", "model"))
    masks = {m: np.asarray(RowMatrix.create(jnp.ones((m, 3)), rows)
                           ._row_mask()) for m in (10, 11)}
    for m, mask in masks.items():
        assert mask.shape == (12,), mask.shape
        assert np.array_equal(mask, np.arange(12) < m), (m, mask)

    # The same matrix on two meshes: two programs, both right.
    A = np.random.default_rng(0).normal(size=(12, 6)).astype(np.float32)
    square = make_mesh((2, 2), ("data", "model"))
    with telemetry.recording(telemetry.Recorder()) as rec:
        grams = [np.asarray(RowMatrix.create(jnp.asarray(A), mesh)
                            .gram(chunks=1)) for mesh in (rows, square)]
        again = RowMatrix.create(jnp.asarray(A), square).gram(chunks=1)
    assert rec.counters("distmat.program") == {"result=miss": 2,
                                               "result=hit": 1}, \\
        rec.counters("distmat.program")
    for g in grams:
        np.testing.assert_allclose(g, A.T @ A, rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.asarray(again), grams[1])
    print("PROGRAMS_4DEV_OK")
""")


def test_programs_on_4_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", FOUR_DEV_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PROGRAMS_4DEV_OK" in out.stdout
