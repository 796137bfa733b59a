"""The control: the plain reference computed in bfloat16, the next
precision below the configurations' float32, stands in for the program;
the check must reject it.  (On the chip, ``bench/calibrate.py`` reads the
same control at each cell's own size.)"""
import pytest

import benchtest

CONTROL = """
import jax.numpy as _jnp
import cells as _cells
_make = _cells.make_job
def _make_job(*a, **k):
    job = _make(*a, **k)
    _check = job.check
    job.check = lambda dtype=None: _check(dtype=_jnp.bfloat16)
    return job
_cells.make_job = _make_job
"""

CELLS = benchtest.cells_of()


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_bfloat16_control_fails(workload):
    rc, last, out, err = benchtest.run_cell(
        workload, patch=CONTROL)
    assert rc == 0, err[-3000:]
    assert last["correct"] is False, last
