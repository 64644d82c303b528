"""Gradient compression: exactness properties + error feedback."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.train.compression import (init_error_feedback, lowrank_compressor,
                                     int8_compressor, compression_ratio,
                                     psum_int8)


def _grads(seed=0):
    rng = np.random.default_rng(seed)
    return {"w": jnp.asarray(rng.normal(size=(64, 32)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(32,)), jnp.float32)}


def test_lowrank_exact_when_rank_sufficient():
    rng = np.random.default_rng(1)
    U = rng.normal(size=(64, 4)).astype(np.float32)
    V = rng.normal(size=(32, 4)).astype(np.float32)
    g = {"w": jnp.asarray(U @ V.T)}
    comp = lowrank_compressor(rank=8)
    ef = init_error_feedback(g)
    out, ef2 = comp(g, ef)
    np.testing.assert_allclose(np.asarray(out["w"]), U @ V.T, atol=1e-3)
    assert float(jnp.abs(ef2.residual["w"]).max()) < 1e-3


def test_error_feedback_preserves_signal():
    """Σ_t compressed_t + final residual == Σ_t grads (EF identity)."""
    comp = int8_compressor(seed=0)
    g = _grads()
    ef = init_error_feedback(g)
    total_sent = jax.tree.map(jnp.zeros_like, g)
    total_true = jax.tree.map(jnp.zeros_like, g)
    for t in range(10):
        gt = jax.tree.map(lambda x: x * (0.9 ** t), g)
        sent, ef = comp(gt, ef)
        total_sent = jax.tree.map(lambda a, s: a + s.astype(jnp.float32),
                                  total_sent, sent)
        total_true = jax.tree.map(lambda a, s: a + s, total_true, gt)
    # EF: sent-so-far + residual == true-so-far exactly
    recon = jax.tree.map(lambda s, r: s + r, total_sent, ef.residual)
    for a, b in zip(jax.tree.leaves(recon), jax.tree.leaves(total_true)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-4)


def test_int8_bounded_error():
    comp = int8_compressor()
    g = _grads(2)
    out, ef = comp(g, init_error_feedback(g))
    for k in g:
        scale = float(jnp.abs(g[k]).max()) / 127.0
        err = float(jnp.abs(out[k] - g[k]).max())
        assert err <= scale * 1.0 + 1e-6


def test_small_tensors_passthrough():
    comp = lowrank_compressor(rank=8)
    g = {"b": jnp.ones((5,), jnp.float32)}
    out, _ = comp(g, init_error_feedback(g))
    np.testing.assert_array_equal(np.asarray(out["b"]), np.ones(5))


def test_compression_ratio():
    g = _grads()
    r = compression_ratio(g, rank=8)
    want = (8 * (64 + 32) + 32) / (64 * 32 + 32)
    assert abs(r - want) < 1e-6


# -- non-f32 leaves (the compressors ship in the leaf dtype, EF stays f32) ----

def _bf16_grads(seed=3):
    rng = np.random.default_rng(seed)
    return {"w": jnp.asarray(rng.normal(size=(64, 32)), jnp.bfloat16),
            "b": jnp.asarray(rng.normal(size=(32,)), jnp.bfloat16)}


def test_lowrank_bf16_leaves_residual_vs_sent():
    """The residual must be measured against what was actually SENT (the
    leaf-dtype cast of the approximation), so the EF identity holds in f32
    exactly even when the wire truncates to bf16."""
    comp = lowrank_compressor(rank=4)
    g = _bf16_grads()
    ef = init_error_feedback(g)
    out, ef2 = comp(g, ef)
    assert out["w"].dtype == jnp.bfloat16
    assert ef2.residual["w"].dtype == jnp.float32
    # EF identity: sent + residual == corrected gradient, exactly in f32.
    recon = (out["w"].astype(jnp.float32)
             + ef2.residual["w"].astype(jnp.float32))
    want = g["w"].astype(jnp.float32) + ef.residual["w"]
    np.testing.assert_array_equal(np.asarray(recon), np.asarray(want))


def test_int8_bf16_leaves_ef_identity():
    comp = int8_compressor(seed=1)
    g = _bf16_grads(4)
    ef = init_error_feedback(g)
    out, ef2 = comp(g, ef)
    for k in g:
        assert ef2.residual[k].dtype == jnp.float32
        recon = (np.asarray(out[k], np.float32)
                 + np.asarray(ef2.residual[k]))
        want = (np.asarray(g[k], np.float32) + np.asarray(ef.residual[k]))
        np.testing.assert_allclose(recon, want, rtol=1e-6, atol=1e-6)


# -- compressed psum wire (psum_int8) -----------------------------------------

def test_psum_int8_local_round_trip():
    """Degenerate (no named axes) wire: out + new_res == x + res exactly —
    the EF identity that makes the quantization bias vanish over
    iterations."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(256,)) * 30.0, jnp.float32)
    res = jnp.asarray(rng.normal(size=(256,)) * 0.1, jnp.float32)
    out, new_res = psum_int8(x, res, (), 1)
    np.testing.assert_array_equal(np.asarray(out + new_res),
                                  np.asarray(x + res))
    # Deterministic rounding error bounded by half a quantization step.
    scale = float(jnp.abs(x + res).max()) / 127.0
    assert float(jnp.abs(out - (x + res)).max()) <= 0.5 * scale + 1e-7


def test_psum_int8_ef_telescopes():
    """Σ_t out_t + res_final == Σ_t x_t (+ res_0): nothing is lost to the
    wire across iterations."""
    rng = np.random.default_rng(6)
    res = jnp.zeros((128,), jnp.float32)
    total_out = jnp.zeros((128,), jnp.float32)
    total_x = jnp.zeros((128,), jnp.float32)
    for t in range(12):
        x = jnp.asarray(rng.normal(size=(128,)) * (0.8 ** t), jnp.float32)
        out, res = psum_int8(x, res, (), 1)
        total_out = total_out + out
        total_x = total_x + x
    np.testing.assert_allclose(np.asarray(total_out + res),
                               np.asarray(total_x), rtol=1e-5, atol=1e-5)


def test_psum_int8_distributed_round_trip():
    """The real wire: shard_map over a named axis — int8 payloads summed
    across shards with a shared pmax scale must reproduce the f32 psum
    within the per-shard quantization bound, and the EF identity must hold
    per shard."""
    from jax.sharding import Mesh, PartitionSpec as P

    devs = np.array(jax.devices()[:1])
    mesh = Mesh(devs.reshape(1), ("data",))
    nshards = 1
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(nshards, 64)) * 10.0, jnp.float32)
    res = jnp.zeros((nshards, 64), jnp.float32)

    @jax.jit
    def run(x, res):
        def body(xs, rs):
            out, nr = psum_int8(xs[0], rs[0], ("data",), nshards)
            return out[None], nr[None]
        return jax.shard_map(body, mesh=mesh,
                             in_specs=(P("data", None), P("data", None)),
                             out_specs=(P("data", None), P("data", None)))(x, res)

    out, new_res = run(x, res)
    true_sum = np.asarray(x).sum(0)
    qmax = max(127 // nshards, 1)
    scale = np.abs(np.asarray(x)).max() / qmax
    err = np.abs(np.asarray(out)[0] - true_sum).max()
    assert err <= 0.5 * scale * nshards + 1e-6
    # per-shard EF identity: q·scale + new_res == x + res
    sent = np.asarray(out)[0]          # single shard: psum == own payload
    np.testing.assert_allclose(sent + np.asarray(new_res)[0],
                               np.asarray(x)[0] + np.asarray(res)[0],
                               rtol=1e-6, atol=1e-6)
