"""The precision control, at a size a test run holds: the reference one
precision step below the configuration's, in the program's place, must
come out as not correct under each cell's limits."""
from __future__ import annotations

import pytest

from bench_tiny import CELLS, harness, tiny_cell

from bench.run import Run


@pytest.mark.parametrize("name", CELLS)
def test_precision_control_is_not_correct(name):
    cell = tiny_cell(name)
    readings = cell.runner.control(Run(cell, 3, 0.0, False, None))
    checks = harness.Checks(cell.limits)
    for key, value in readings.items():
        name = key.removeprefix("control.")
        if name in cell.limits and (key == name or key.startswith("control.")):
            checks.add(name, value)
    assert not checks.correct, checks.summary()
