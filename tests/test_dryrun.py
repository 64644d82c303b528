"""Dry-run plumbing: plan construction, roofline math, HLO collective
parsing; plus a reduced-size end-to-end lower+compile in a subprocess."""
import os
import subprocess
import sys
import textwrap

import pytest

from repro.launch import roofline as RL


def test_parse_collectives():
    hlo = """
  %all-reduce.1 = f32[256,512]{1,0} all-reduce(%dot), channel_id=1
  %ag = bf16[8,128]{1,0} all-gather(%x), dimensions={0}
  %ag-done = bf16[8,128]{1,0} all-gather-done(%ag)
  %rs = (f32[16]{0}, f32[16]{0}) reduce-scatter(%a, %b)
  %cp = u32[4]{0} collective-permute(%c)
  %dot.5 = f32[64,64]{1,0} dot(%p, %q)
"""
    out = RL.parse_collectives(hlo)
    assert out["all-reduce"]["bytes"] == 256 * 512 * 4
    assert out["all-reduce"]["count"] == 1
    assert out["all-gather"]["bytes"] == 8 * 128 * 2
    assert out["all-gather"]["count"] == 1          # -done not re-counted
    assert out["reduce-scatter"]["bytes"] == 2 * 16 * 4
    assert out["collective-permute"]["bytes"] == 4 * 4
    assert out["total_bytes"] == sum(
        out[k]["bytes"] for k in ("all-reduce", "all-gather",
                                  "reduce-scatter", "all-to-all",
                                  "collective-permute"))


def test_roofline_terms():
    meta = {"mesh": {"data": 16, "model": 16}, "kind": "train",
            "params_active": 1e9, "tokens_per_step": 1e6,
            "argument_bytes": 1e9}
    r = RL.analyze(flops=1e13, hbm=1e11, collective_bytes=1e9, meta=meta)
    assert r.compute_s == pytest.approx(1e13 / RL.PEAK_FLOPS)
    assert r.memory_s == pytest.approx(1e11 / RL.HBM_BW)
    assert r.collective_s == pytest.approx(1e9 / RL.LINK_BW)
    assert r.bound == "memory"
    assert r.model_flops == pytest.approx(6e15)
    assert 0 < r.roofline_fraction <= 1.0


def test_costmodel_linear_solve():
    from repro.launch.costmodel import _solve
    # fixed=5, slope_a=2, slope_b=3
    counts = [{"a": 1, "b": 1}, {"a": 2, "b": 1}, {"a": 1, "b": 2}]
    values = [10.0, 12.0, 13.0]
    est = _solve(counts, values, {"a": 10, "b": 20})
    assert est == pytest.approx(5 + 2 * 10 + 3 * 20)


MINI_DRYRUN = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import jax
    from repro.core.distmat.types import make_mesh
    from repro.launch.specs import make_plan
    from repro.launch import roofline as RL
    mesh = make_mesh((4, 4), ("data", "model"))
    # reduced depth/width but the REAL plan machinery end to end
    ov = {"num_layers": 2, "d_model": 256, "num_heads": 8,
          "num_kv_heads": 4, "head_dim": 32, "d_ff": 512,
          "vocab_size": 1024, "scan_unroll": True}
    for shape in ("train_4k", "decode_32k"):
        plan = make_plan("qwen3-4b", shape, mesh, overrides=ov,
                         microbatches=1)
        compiled = plan.lower().compile()
        cost = compiled.cost_analysis()
        assert cost.get("flops", 0) > 0
        mem = compiled.memory_analysis()
        assert mem.argument_size_in_bytes > 0
        coll = RL.parse_collectives(compiled.as_text())
        assert coll["total_bytes"] > 0, shape
        print(shape, "MINI_DRYRUN_OK")
""")


def test_mini_dryrun_end_to_end():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", MINI_DRYRUN], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.count("MINI_DRYRUN_OK") == 2
