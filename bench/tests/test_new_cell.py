"""A cell, a traffic mix, a limits file and a per-layer metric added as
files and BENCHMARK.json entries alone, in a copy of the benchmark,
run without an edit to any file that was there."""
import json
import shutil

import tiny


def test_cell_from_files_alone(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(tiny.R.ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = tiny.bench()
    base = root / "bench"
    traffic = json.loads((base / "traffic" / "solve-cycle.json").read_text())
    traffic["families"] = [{"loss": "quad", "reg": "l2"}]
    (base / "traffic" / "quad-l2-only.json").write_text(json.dumps(traffic))
    (base / "limits" / "throwaway.json").write_text(
        (base / "limits" / "dense-solve.json").read_text())
    (base / "metrics" / "throwaway.jobs.py").write_text(
        "def read(run):\n    return float(run.counters['jobs'])\n")
    bench["workloads"].append({
        "name": "throwaway", "config": "fig1-dense",
        "traffic": "quad-l2-only", "chips": 1, "why": "test"})
    bench["end_to_end"][0]["workloads"].append("throwaway")
    bench["per_layer"].append({
        "name": "throwaway.jobs", "unit": "jobs", "better": "higher",
        "source": "program_counter", "layer": "solver", "moves": "solve_s",
        "workloads": ["throwaway"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    res = tiny.run("throwaway", trace=True, root=root, bench_json=bench)
    assert res["correct"], res["checks"]
    assert res["metrics"]["throwaway.jobs"]["value"] >= 1
    res = tiny.run("throwaway", root=root, bench_json=bench)
    assert set(res["metrics"]) == {"solve_s", "setup_s"}
