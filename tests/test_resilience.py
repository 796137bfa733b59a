"""Leapfrog checkpoint/restore: ``timeloop.run_resilient`` drives the
engine one fusion window per restartable step through
``train.checkpoint`` + ``train.fault_tolerance``.  Window replay is
deterministic (the identical compiled program on the identical carry),
so a run that crashes and restores must be BIT-EXACT with an
uninterrupted one — asserted with ``np.array_equal``, not allclose —
including ``between``-hook timing and a fresh-process resume from an
existing checkpoint directory.  The multi-device distributed variant
lives in tests/test_distributed.py's subprocess harness."""
import numpy as np
import jax
from repro.launch.mesh import make_mesh
import pytest

from repro.core import dsl as st, suite
from repro.core.timeloop import TimeloopEngine, run_resilient
from repro.train.fault_tolerance import FailureInjector

SHAPE = (12, 10)
STEPS = 7
FUSE = 2


def _engine(backend=None, mesh=None):
    k = suite.get_kernel("star2d1r")
    halos = {g: (k.info.order,) * k.info.ndim for g in k.ir.grid_params}
    return TimeloopEngine(k.ir, halos, SHAPE, backend or st.xla(),
                          swap=suite.swap_pair(k.name), mesh=mesh)


def _inits(seed=0):
    # engine.run consumes the grid's full (halo-padded) arrays
    k = suite.get_kernel("star2d1r")
    gs = {g: st.grid(np.float32, SHAPE, k.info.order).randomize(seed + i)
          for i, g in enumerate(k.ir.grid_params)}
    return {g: np.asarray(v.data) for g, v in gs.items()}


def _between(t, arrays):
    # a mid-run source injection: resilience must replay it at the same
    # window boundary after a restart
    arrays = dict(arrays)
    arrays["u"] = arrays["u"].at[3, 4].add(np.float32(0.25 * t))
    return arrays


def _assert_bit_exact(a, b, label):
    for g in a:
        assert np.array_equal(np.asarray(a[g]), np.asarray(b[g])), \
            f"{label}: grid '{g}' diverged after restore"


def test_resilient_bit_exact_with_injected_failures(tmp_path):
    eng = _engine()
    inits = _inits()
    ref = eng.run(dict(inits), {}, STEPS, FUSE, _between)

    got = run_resilient(_engine(), dict(inits), {}, STEPS, FUSE, _between,
                        ckpt_dir=str(tmp_path / "ck"), ckpt_every=1,
                        injector=FailureInjector([1, 3]))
    _assert_bit_exact(got, ref, "injected failures")


def test_resilient_checkpoint_cadence(tmp_path):
    # sparse cadence: a failure between checkpoints rolls back and
    # replays deterministically
    eng = _engine()
    inits = _inits(1)
    ref = eng.run(dict(inits), {}, STEPS, FUSE)
    got = run_resilient(_engine(), dict(inits), {}, STEPS, FUSE,
                        ckpt_dir=str(tmp_path / "ck"), ckpt_every=2,
                        injector=FailureInjector([3]))
    _assert_bit_exact(got, ref, "sparse cadence")


def test_resume_from_existing_checkpoint_dir(tmp_path):
    """A fresh driver pointed at a populated directory resumes at the
    last window boundary instead of restarting from scratch."""
    ckpt = str(tmp_path / "ck")
    inits = _inits(2)
    # first process: covers windows 0-1 (4 of 8 steps), then "dies"
    run_resilient(_engine(), dict(inits), {}, 4, FUSE, ckpt_dir=ckpt)
    # second process: same directory, full horizon — windows 0-1 restore,
    # 2-3 execute
    got = run_resilient(_engine(), dict(inits), {}, 8, FUSE, ckpt_dir=ckpt)
    ref = _engine().run(dict(inits), {}, 8, FUSE)
    _assert_bit_exact(got, ref, "fresh-process resume")


def test_failures_beyond_budget_raise(tmp_path):
    inits = _inits()
    with pytest.raises(RuntimeError, match="injected node failure"):
        run_resilient(_engine(), dict(inits), {}, STEPS, FUSE,
                      ckpt_dir=str(tmp_path / "ck"), max_failures=1,
                      injector=FailureInjector([0, 1]))


def test_resilient_distributed_single_device(tmp_path):
    """The fused sharded window restores bit-exactly too (single-device
    mesh here; the 4-device run is exercised in test_distributed.py)."""
    mesh = make_mesh((1,), ("data",))
    be = st.distributed(grid_axes=("data", None), time_steps=2)
    inits = _inits(3)
    ref = _engine(be, mesh).run(dict(inits), {}, STEPS, 4)
    got = run_resilient(_engine(be, mesh), dict(inits), {}, STEPS, 4,
                        ckpt_dir=str(tmp_path / "ck"), ckpt_every=1,
                        injector=FailureInjector([1]))
    _assert_bit_exact(got, ref, "distributed fused window")
