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


@pytest.mark.parametrize("dtype", ["bfloat16", "float8_e4m3fn"])
def test_control_rounds_as_the_cast_does(dtype):
    """The control rounds by arithmetic, since the TPU's compiler may drop
    a cast there and back; where the cast is kept, as on the CPU, both
    give the same values up to the format's largest."""
    import jax.numpy as jnp
    import numpy as np

    from bench.references.mnv2 import round_to

    rng = np.random.default_rng(0)
    x = np.concatenate([rng.normal(0, s, 20000) for s in (50, 1, 1e-2, 1e-4)])
    x = jnp.asarray(np.clip(x, -400, 400), jnp.float32)
    want = x.astype(dtype).astype(jnp.float32)
    assert bool((round_to(x, dtype) == want).all())
