"""Shape-aware autotuner: candidate legality, roofline ranking, cache."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune as at
from repro.kernels import ops, ref

# knob -> required multiple, per kernel (lane for last-dim knobs, dtype
# sublane for second-to-last-dim knobs).
KNOB_KIND = {
    "gemm": {"bm": "sublane", "bn": "lane", "bk": "lane"},
    "tsgram": {"bm": "sublane", "bn": "lane"},
    "randsketch": {"bm": "sublane", "bn": "lane"},
    # fusedgrad's bm doubles as the lane width of its t/w/z vector strips,
    # so its candidates are lane-aligned.
    "fusedgrad": {"bm": "lane"},
    "flash_attention": {"bq": "sublane", "bk": "lane"},
    "selective_scan": {"q": "sublane"},
}

DIMS = {
    "gemm": {"m": 1000, "k": 700, "n": 900},
    "tsgram": {"m": 20000, "n": 300},
    "randsketch": {"m": 20000, "n": 2000, "r": 72},
    "fusedgrad": {"m": 10000, "n": 1024},
    "flash_attention": {"sq": 2048, "sk": 2048, "d": 128, "causal": 1},
    "selective_scan": {"s": 4096, "d": 768, "n": 16},
}


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    """Every test gets an empty persistent cache and fresh counters."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    at.reset()
    yield
    at.reset()


@pytest.mark.parametrize("kernel", sorted(KNOB_KIND))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_candidates_respect_layout_multiples(kernel, dtype):
    sub = at.sublane(dtype)
    cands = at.candidates(kernel, DIMS[kernel], dtype)
    assert cands, (kernel, dtype)
    for blocks in cands:
        assert set(blocks) == set(KNOB_KIND[kernel])
        for knob, kind in KNOB_KIND[kernel].items():
            mult = sub if kind == "sublane" else at.LANE
            assert blocks[knob] % mult == 0, (kernel, blocks, knob)


@pytest.mark.parametrize("kernel", sorted(KNOB_KIND))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_candidates_respect_vmem_budget(kernel, dtype):
    for blocks in at.candidates(kernel, DIMS[kernel], dtype):
        used = at.estimate_vmem(kernel, blocks, DIMS[kernel], dtype)
        assert 0 < used <= at.VMEM_BUDGET, (kernel, blocks, used)


@pytest.mark.parametrize("kernel", sorted(KNOB_KIND))
def test_ranking_never_worse_than_legacy(kernel):
    """The acceptance bar: the model-selected config scores at least as
    well as the old hand-picked constants (which stay in the pool)."""
    ranked = at.rank(kernel, DIMS[kernel], jnp.float32)
    legacy = dict(at.KERNELS[kernel].legacy)
    legacy_score = at.model_time(kernel, legacy, DIMS[kernel], jnp.float32)
    assert ranked[0][0] <= legacy_score
    assert legacy in [b for _, b in ranked]
    assert ranked == at.rank(kernel, DIMS[kernel], jnp.float32)  # determinism


def test_small_shapes_prefer_less_padding():
    """For a tiny GEMM the tuner must not pick giant tiles that would be
    pure padding waste."""
    blocks = at.rank("gemm", {"m": 16, "k": 128, "n": 128}, jnp.float32)[0][1]
    assert blocks["bm"] <= 16 and blocks["bn"] == 128


def test_shape_bucketing():
    assert at.bucket(1000) == 1024 and at.bucket(1024) == 1024
    assert at.bucket(1025) == 2048 and at.bucket(1) == 1
    k1 = at.cache_key("gemm", "cpu", jnp.float32,
                      {"m": 1000, "k": 1000, "n": 1000})
    k2 = at.cache_key("gemm", "cpu", jnp.float32,
                      {"m": 1024, "k": 1024, "n": 1024})
    k3 = at.cache_key("gemm", "cpu", jnp.bfloat16,
                      {"m": 1024, "k": 1024, "n": 1024})
    assert k1 == k2 and k2 != k3


def test_cache_roundtrip(tmp_path):
    """record() → fresh process state → lookup hits the JSON file, and a
    second lookup hits the in-memory memo — no re-ranking either time."""
    dims = {"m": 3000, "k": 500, "n": 400}
    blocks = {"bm": 128, "bn": 256, "bk": 512}
    key = at.record("gemm", dims, jnp.float32, blocks, backend="cpu")
    saved = json.loads((at.user_cache_path()).read_text())
    assert saved["entries"][key]["blocks"] == blocks

    at.reset()                      # drop memo + cache handles, keep file
    got = at.get_config("gemm", dims, jnp.float32, backend="cpu")
    assert got == blocks
    assert at.stats == {"memo_hits": 0, "cache_hits": 1, "ranked": 0,
                        "swept": 0}
    # same bucket, different exact shape: memo hit, still no ranking
    got2 = at.get_config("gemm", {"m": 2900, "k": 400, "n": 300},
                         jnp.float32, backend="cpu")
    assert got2 == blocks
    assert at.stats["memo_hits"] == 1 and at.stats["ranked"] == 0


def test_shipped_v5e_defaults_resolve_on_tpu_key():
    """The pre-swept defaults shipped with the package satisfy a TPU-keyed
    lookup without any ranking."""
    got = at.get_config("gemm", {"m": 1024, "k": 1024, "n": 1024},
                        jnp.float32, backend="tpu")
    assert at.stats["cache_hits"] == 1 and at.stats["ranked"] == 0
    assert set(got) == {"bm", "bn", "bk"}


def test_resolve_explicit_overrides_win():
    full = at.resolve("gemm", DIMS["gemm"], jnp.float32,
                      {"bm": 8, "bn": 128, "bk": 128})
    assert full == {"bm": 8, "bn": 128, "bk": 128}
    assert at.stats["ranked"] == 0          # no tuner involvement
    partial = at.resolve("gemm", DIMS["gemm"], jnp.float32,
                         {"bm": 8, "bn": None, "bk": None})
    assert partial["bm"] == 8 and partial["bn"] % at.LANE == 0
    assert at.stats["ranked"] == 1


def test_resolve_tune_off_is_legacy():
    cfg = at.resolve("tsgram", DIMS["tsgram"], jnp.float32, {"bm": None},
                     tune="off")
    assert cfg == dict(at.KERNELS["tsgram"].legacy)
    assert at.stats["ranked"] == 0
    with pytest.raises(ValueError):
        at.resolve("tsgram", DIMS["tsgram"], jnp.float32, {"bm": None},
                   tune="bogus")


def test_ops_gemm_second_call_skips_ranking():
    """Dispatch-level acceptance: two ops.gemm calls in the same shape
    bucket rank once and memo-hit the second time — and the autotuned
    result matches the reference."""
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(72, 96)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(96, 80)), jnp.float32)
    got = ops.gemm(a, b, force_pallas=True, out_dtype=jnp.float32)
    np.testing.assert_allclose(got, ref.gemm_ref(a, b, jnp.float32),
                               rtol=1e-4, atol=1e-2)
    assert at.stats["ranked"] == 1
    ops.gemm(a, b, force_pallas=True, out_dtype=jnp.float32)
    assert at.stats["ranked"] == 1 and at.stats["memo_hits"] >= 1


def test_sweep_selects_fastest_candidate():
    """sweep() orders candidates by measured median; a runner with a known
    per-config cost must produce that order (no device needed)."""
    import time
    calls = []

    def run_fn(blocks):
        # emulate work with a known per-config cost: small bm tiles "fast",
        # large ones "slow" — the sweep orders by wall clock alone
        calls.append(dict(blocks))
        time.sleep(0.001 if blocks["bm"] <= 128 else 0.004)

    dims = {"m": 512, "n": 128}
    timed = at.sweep("tsgram", dims, jnp.float32, run_fn, top_n=2, reps=2)
    assert len(timed) >= 2
    assert timed == sorted(timed, key=lambda t: t[0])
    # every timed config was warmed up once + timed `reps` times
    assert len(calls) == len(timed) * 3
    assert at.stats["swept"] == 1
