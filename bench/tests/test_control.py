"""The control, the plain reference computed in bfloat16 (the precision
below the configurations' float32) put in the program's place, fails
each cell's check at a size the CPU holds, while the program passes."""

import pytest

import calibrate
from small import cell_data, cells


@pytest.mark.parametrize("name", cells())
def test_control_fails_and_program_passes(name):
    cell, cfg, traffic, limits = cell_data(name)
    n = limits["steps_compared"]
    out = calibrate.calibrate(cell, cfg, traffic, n, [7], [7],
                              require_accelerator=False,
                              log_fn=lambda msg: None)
    row = out["rows"][0]
    lim = {k: v["limit"] for k, v in limits["numbers"].items()}
    sound = [row[f"sound.step{i + 1}"][0] <= lim[f"rms_err.step{i + 1}"]
             for i in range(n)]
    control = [row[f"control.step{i + 1}"][0] > lim[f"rms_err.step{i + 1}"]
               for i in range(n)]
    assert all(sound), row
    assert any(control), row
