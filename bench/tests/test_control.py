"""The control: the reference at bfloat16, put in the program's place on
the same captured inputs, fails the comparison in every cell."""

import pytest


@pytest.mark.parametrize("cell", ("dqn.lockstep", "dqn.async", "dpg.async"))
def test_control_fails(run_cell, cell):
    result, _ = run_cell(cell, calibrate=("control",))
    limits = {k: c["limit"] for k, c in result["checks"].items()}
    control = result["calibration"]["control"]
    assert any(control[k] > limits[k] for k in limits if k in control)
    assert result["correct"]
