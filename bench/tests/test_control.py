"""The control that the correctness limits are set against, at a size a
test run holds, goes through the harness's own verdict and comes out not
correct.  The control is the program's own bfloat16 storage path.  On
the CPU a matmul precision of "high" computes as "highest" does, so the
reference at "high" is read beside it only for its set of numbers."""
import pytest

import calibrate
import tiny


@pytest.mark.parametrize("name", ["dense-solve", "dense-svd"])
def test_bf16_control_is_not_correct(name):
    cell = tiny.R.Cell(tiny.bench(), name)
    line = calibrate.one_seed(cell, 11, 1.0, ["high", "program_bf16"],
                              sizes=tiny.sizes(cell), diagnose=False)
    assert line["correct"], line["program"]
    assert line["control_program_bf16_correct"] is False, line
    checks, correct = tiny.R.judge(line["control_program_bf16"], cell.limits)
    assert correct is False
    assert any(c["value"] > 3 * line["program"][k]
               for k, c in checks.items())
    assert set(line["control_high"]) == set(cell.limits)


def test_judge_needs_every_number():
    assert tiny.R.judge({"a": 1.0}, {"a": 1.0, "b": 1.0})[1] is False
    assert tiny.R.judge({"a": float("nan")}, {"a": 1.0})[1] is False
    assert tiny.R.judge({"a": 1.0, "c": 9.0}, {"a": 1.0})[1] is True
