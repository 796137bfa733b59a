"""The correctness check fails when the timed path is broken underneath:
each fault a cell can have is planted in the system under test in a child
process, the rest of a run is driven at the rehearsal extent on the CPU,
and ``correct`` must come out false."""
import pytest

import benchtest

#: every window program returns its input: a step that leaves the state
#: unchanged
UNCHANGED = """
from repro.core import timeloop as _tl
_orig = _tl.TimeloopEngine._window
def _window(self, kw, masked=False, donate=False):
    _orig(self, kw, masked=masked, donate=donate)
    return lambda arrays, *rest: arrays
_tl.TimeloopEngine._window = _window
"""

#: one point of every window's output is scaled by 1.001 where the
#: program produces it (the engine's run and the differentiable path's
#: window callables alike)
ALTERED = """
import jax.numpy as _jnp
from repro.core import timeloop as _tl
def _alter(arrays, swap):
    out = dict(arrays)
    g = swap[0]
    idx = tuple(s // 2 for s in out[g].shape)
    out[g] = out[g].at[idx].multiply(1.001).at[idx].add(1e-3)
    return out
_run = _tl.TimeloopEngine.run
def _altered_run(self, *a, **k):
    return _alter(_run(self, *a, **k), self.swap)
_tl.TimeloopEngine.run = _altered_run
_wa = _tl.TimeloopEngine.window_arrays
def _altered_window_arrays(self, kw, masked=False):
    fn = _wa(self, kw, masked=masked)
    return lambda arrays, *rest: _alter(fn(arrays, *rest), self.swap)
_tl.TimeloopEngine.window_arrays = _altered_window_arrays
"""

#: the first interior plane of every window's output on the face at the
#: start of axis 0 (where every cell carries signal), with its halo, comes
#: back zero: an edge lost in the layout's pad and crop
EDGE = ALTERED.replace(
    "out[g] = out[g].at[idx].multiply(1.001).at[idx].add(1e-3)",
    "out[g] = out[g].at[:5].set(0.0)")

CELLS = benchtest.cells_of()
CASES = [(w, name, patch) for w in sorted(CELLS)
         for name, patch in (("unchanged", UNCHANGED), ("altered", ALTERED),
                             ("edge", EDGE))]


def _fails(last):
    assert last["correct"] is False, last
    assert any(c["value"] > c["limit"] for c in last["checks"].values())


@pytest.mark.parametrize("workload,fault,patch", CASES,
                         ids=[f"{w}-{f}" for w, f, _ in CASES])
def test_fault_fails_the_check(workload, fault, patch):
    rc, last, out, err = benchtest.run_cell(
        workload, patch=patch)
    assert rc == 0, err[-3000:]
    _fails(last)
