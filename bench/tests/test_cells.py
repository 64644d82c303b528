"""Every cell of BENCHMARK.json runs end to end on the CPU at a tiny size:
set-up, window, the comparison with the references, and (traced) the
per-layer readers and the breakdown."""
import json

import pytest

import tiny

CELLS = [w["name"] for w in tiny.bench()["workloads"]]


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("trace", [False, True], ids=["e2e", "traced"])
def test_cell_runs(name, trace):
    res = tiny.run(name, trace=trace)
    json.dumps(res)                                   # one JSON line
    cell = tiny.R.Cell(tiny.bench(), name)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == set(cell.limits)
    if trace:
        want = {m["name"] for m in cell.per_layer}
        assert set(res["metrics"]) <= want
        assert {m for m in want if "roofline" not in m} <= set(res["metrics"])
        assert res["device"]["busy_s"] > 0
        assert len(res["breakdown"]["device_ops"]) <= 10
    else:
        assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
        assert all(v["value"] > 0 for v in res["metrics"].values())


@pytest.mark.parametrize("name", ["dense-solve", "dense-svd"])
def test_same_seed_same_inputs(name):
    """The seed fixes the inputs, and with them the numbers compared
    with the references: two runs draw the same jobs.  The check samples
    from the jobs the window completed, so the window is one cycle of
    jobs (`seconds=0`) on both runs, however busy the host."""
    a = tiny.run(name, seed=99, seconds=0)
    b = tiny.run(name, seed=99, seconds=0)
    assert a["attempted"] == b["attempted"]
    assert a["checks"] == b["checks"]
