"""The readers of the program's own spans and counters: the idle time of
the device under the engine's host spans on synthetic traces with known
overlaps, and ``replay_ratio.grad`` against the plan of a gradient."""
import importlib.util
import os
import sys

import pytest

import benchtest

sys.path.insert(0, benchtest.BENCH)
import cells  # noqa: E402
import hostspans  # noqa: E402
from devtrace import Op, Trace  # noqa: E402


def reader(name):
    path = os.path.join(benchtest.BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class _Run:
    def __init__(self, tr):
        self.trace = tr


def _window(host, ops):
    return _Run(Trace(window=(0, 100), devices={0: ops},
                      host=[("bench.window", 0, 100),
                            ("bench.job", 0, 100)] + host))


def test_program_span_names_match_the_program():
    from repro.core import trace
    names = {v for k, v in vars(trace).items()
             if k.isupper() and isinstance(v, str)
             and not k.startswith("ADJOINT")}
    assert names == set(hostspans.PROGRAM)
    assert set(hostspans.HOST_PATH) == {trace.TO_PADDED, trace.DISPATCH,
                                        trace.FROM_PADDED, trace.HOOK}
    assert hostspans.SYNC == (trace.SYNC,)


def test_gap_split_between_hook_and_sync():
    # device busy 0-40 and 60-100; the gap 40-60 lies half in the window's
    # sync (40-50) and half in the hook after it (50-60)
    run = _window([("engine.window", 0, 50), ("engine.sync", 30, 50),
                   ("engine.hook", 50, 60), ("engine.window", 60, 100)],
                  [Op("stencil_k", 0, 40, True),
                   Op("stencil_k", 60, 100, True)])
    assert reader("host_idle_share.hooked")(run) == pytest.approx(10.0)
    assert reader("sync_idle_share.hooked")(run) == pytest.approx(10.0)


def test_nested_spans_and_idle_outside_program_spans():
    # JAX's spans inside program spans count as the program span's time;
    # a program span nested in another is taken out of the outer one's
    host = [("engine.window", 10, 60),
            ("layout.to_padded", 10, 20), ("PjitFunction(pad)", 12, 18),
            ("engine.dispatch", 20, 30), ("DevicePut", 22, 28),
            ("engine.compile", 24, 26),
            ("layout.from_padded", 30, 35),
            ("engine.sync", 35, 60),
            ("engine.hook", 60, 70), ("PjitFunction(add)", 61, 69)]
    ops = [Op("pad", 19, 21, False), Op("stencil_k", 40, 55, True),
           Op("add", 69, 72, False)]
    run = _window(host, ops)
    # idle: 0-19 (10-19 in layout.to_padded), 21-40 (21-24, 26-30 in
    # dispatch, 24-26 in engine.compile, 30-35 in from_padded, 35-40 in
    # sync), 55-69 (55-60 sync, 60-69 hook), 72-100 outside any
    host_path = (19 - 10) + (24 - 21) + (30 - 26) + (35 - 30) + (69 - 60)
    assert reader("host_idle_share.hooked")(run) == pytest.approx(host_path)
    assert reader("sync_idle_share.hooked")(run) == pytest.approx(5 + 5)
    assert reader("device_idle_share.hooked")(run) == pytest.approx(
        19 + 19 + 14 + 28)


def test_no_program_spans_read_zero():
    run = _window([("PjitFunction(f)", 20, 30)],
                  [Op("fusion", 0, 50, False)])
    assert reader("host_idle_share.hooked")(run) == 0.0
    assert reader("sync_idle_share.hooked")(run) == 0.0


def test_silent_without_a_trace_or_without_spans(monkeypatch):
    run = _window([("engine.sync", 0, 100)], [Op("fusion", 0, 50, False)])
    monkeypatch.setattr(hostspans, "has_spans", lambda: False)
    assert reader("host_idle_share.hooked")(run) is None
    assert reader("sync_idle_share.hooked")(run) is None
    monkeypatch.undo()
    run.trace = None
    assert reader("host_idle_share.hooked")(run) is None


def test_intersect_and_self_time():
    assert hostspans.intersect([(0, 10), (20, 30)],
                               [(5, 25), (28, 40)]) == [(5, 10), (20, 25),
                                                        (28, 30)]
    host = [("engine.window", 0, 10), ("engine.sync", 4, 6),
            ("bench.job", 0, 10)]
    assert hostspans.self_time(host, ("engine.window",)) == [(0, 4), (6, 10)]


@pytest.mark.parametrize("steps,want", [(None, None), (320, 65.0)])
def test_replay_ratio_matches_the_plan(steps, want):
    """The gradient cell's kernel at its rehearsal extent, with the
    rehearsal's steps and with the cell's 320 steps in 8-step windows (40
    windows, a checkpoint every third: 26 replayed)."""
    import jax
    import jax.numpy as jnp
    from repro.core import adjoint, dsl as st
    spec = cells.Spec("acoustic-iso-256-fwi.grad", rehearse=True)
    k = cells.program_kernel(spec)
    grids = [st.grid(dtype=st.f32, shape=spec.shape,
                     order=spec.order).randomize(i)
             for i, _ in enumerate(k.ir.grid_params)]
    fn = st.differentiable_timeloop(
        k, *grids, *spec.scalars.values(), steps=steps or spec.steps,
        swap=spec.swap, fuse_steps=spec.fuse, backend=st.xla())
    jax.make_jaxpr(jax.grad(lambda a: jnp.sum(fn(a)["p0"] ** 2)))(fn.arrays)
    w, ckpts = len(fn.schedule["windows"]), fn.schedule["checkpoints"]
    got = reader("replay_ratio.grad")(None)
    assert got == pytest.approx(100.0 * (w - ckpts) / w)
    if want is not None:
        assert got == want
    adjoint.CHECKPOINT_STATS["forward_windows"] = 0    # no pass traced
    assert reader("replay_ratio.grad")(None) is None
