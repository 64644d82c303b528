"""A configuration of another matrix type, with its cell, traffic mix and
limits, added as files and BENCHMARK.json entries alone, in a copy of the
benchmark, runs without an edit to any file that was there.

The configuration (`new_config/throwaway-blocksparse.{json,py}`) is a
SparseRowMatrix of dense 128 × 128 tiles; its cell is a closed loop of
`api.svd(k=5, mode="auto")`, which takes the Lanczos path, and its
references never form an n × n array."""
import json
import shutil
from pathlib import Path

import pytest

import calibrate
import test_faults
import tiny
from repro import api

NAME = "throwaway-blocksparse"
CELL = "throwaway-sparse-svd"
FILES = Path(__file__).resolve().parent / "new_config"
TRAFFIC = {"job": "svd", "loop": "closed", "k": 5, "mode": "auto",
           "expect_plan": "lanczos", "check_sample": 3}
# From CPU readings at TINY, seeds 2**33 + 1..6, 11 and 12: the program
# read at most 7.1e-7, 1.7e-7 and 1.1e-6; V permuted read v_resid 1.6e-2;
# the program's bfloat16 storage 5.4e-5, 1.1e-3 and 7.2e-4.
LIMITS = {"sigma_gap": 1e-5, "u_resid": 1e-5, "v_resid": 1e-4}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A copy of the benchmark with the configuration, its cell and the
    cell's entries added."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(tiny.R.ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    base = root / "bench"
    for suffix in (".json", ".py"):
        shutil.copy(FILES / f"{NAME}{suffix}", base / "configs")
    (base / "traffic" / "svd-lanczos-k5.json").write_text(json.dumps(TRAFFIC))
    (base / "limits" / f"{CELL}.json").write_text(json.dumps(LIMITS))
    bench = tiny.bench()
    bench["configs"].append({
        "name": NAME, "source": "test", "file": f"bench/configs/{NAME}.json",
        "reduced": [], "why": "test"})
    bench["workloads"].append({
        "name": CELL, "config": NAME, "traffic": "svd-lanczos-k5",
        "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("svd_s", "svd.a_passes", "device_idle.svd",
                         "host.compile_s.svd"):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root, bench


@pytest.mark.parametrize("trace", [False, True], ids=["e2e", "traced"])
def test_config_from_files_alone(checkout, trace):
    root, bench = checkout
    res = tiny.run(CELL, trace=trace, root=root, bench_json=bench)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["checks"]) == set(LIMITS)
    if trace:
        assert res["metrics"]["svd.a_passes"]["value"] > 1
    else:
        assert set(res["metrics"]) == {"svd_s", "setup_s"}


def test_permuted_v_is_not_correct(checkout, monkeypatch):
    root, bench = checkout
    monkeypatch.setattr(api, "svd", test_faults.svd_permuted(api.svd))
    res = tiny.run(CELL, root=root, bench_json=bench)
    assert res["correct"] is False, res["checks"]


def test_controls(checkout):
    """The reference's own triplets at "high" (`svd_at`) give every
    number, and the program's bfloat16 storage is not correct."""
    root, bench = checkout
    cell = tiny.R.Cell(bench, CELL, root)
    line = calibrate.one_seed(cell, 11, 1.0, ["high", "program_bf16"],
                              sizes=tiny.sizes(cell), diagnose=False)
    assert line["correct"], line["program"]
    assert set(line["control_high"]) == set(LIMITS), line
    assert line["control_program_bf16_correct"] is False, line
