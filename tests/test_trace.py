"""Host spans and adjoint scopes (``core/trace.py``): the engine's spans in
a profiler trace recorded on the CPU, the launch profile they feed, the
adjoint's named scopes in compiled HLO, and the per-pass checkpoint
counters."""
import re

import jax
import jax.numpy as jnp

from repro.core import acoustic, adjoint, dsl as st, suite
from repro.core import trace

STEPS = 4
ENGINE_SPANS = {trace.WINDOW, trace.TO_PADDED, trace.DISPATCH,
                trace.FROM_PADDED, trace.SYNC, trace.HOOK, trace.COMPILE,
                trace.CODEGEN, trace.LAUNCH}


def _grids(name="star2d2r", shape=(8, 16)):
    k = st.kernel(suite.get_kernel(name).fn)    # its own build cache
    return k, [st.grid(dtype=st.f32, shape=shape,
                       order=k.info.order).randomize(i)
               for i, _ in enumerate(k.ir.grid_params)]


def _hooked_launch(k, grids):
    """A ``fuse_steps=1`` interpreted-Pallas timeloop with a hook."""
    def hook(t, gs):
        gs["u"].data = gs["u"].data * 1.0

    def tgt(*args):
        st.timeloop(STEPS, swap=suite.swap_pair("star2d2r"), fuse_steps=1,
                    between=hook)(k)(*args)
    return st.launch(backend=st.pallas(template="gmem"))(tgt)(*grids)


def _host_spans(trace_dir):
    """(name, start, end) of the program's spans, per host thread."""
    import glob
    from jax.profiler import ProfileData
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    threads = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                     for e in line.events if e.name in ENGINE_SPANS]
            if spans:
                threads.append(spans)
    return threads


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_engine_spans_in_a_profiler_trace(tmp_path):
    k, grids = _grids()
    _hooked_launch(k, grids)                 # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        res = _hooked_launch(k, grids)
    finally:
        jax.profiler.stop_trace()
    threads = _host_spans(str(tmp_path))
    assert len(threads) == 1, "the spans belong to the calling thread"
    spans = sorted(threads[0], key=lambda s: s[1])
    windows = [s for s in spans if s[0] == trace.WINDOW]
    assert len(windows) == STEPS            # fuse_steps=1: one per step
    for w in windows:
        inner = [s[0] for s in spans if s is not w and _inside(s, w)]
        assert inner == [trace.TO_PADDED, trace.DISPATCH,
                         trace.FROM_PADDED, trace.SYNC]
    hooks = [s for s in spans if s[0] == trace.HOOK]
    assert len(hooks) == STEPS - 1
    for h, (a, b) in zip(hooks, zip(windows, windows[1:])):
        assert a[2] <= h[1] and h[2] <= b[1]  # between two windows
    launch = [s for s in spans if s[0] == trace.LAUNCH]
    assert len(launch) == 1 and all(_inside(s, launch[0]) for s in spans)
    assert set(res.profile) == {"kernel", "layout", "total"}


def test_launch_profile_keys_and_nesting():
    k, grids = _grids()
    first = _hooked_launch(k, grids).profile
    assert set(first) == {"codegen", "comp", "kernel", "layout", "total"}
    again = _hooked_launch(k, grids).profile    # engine and windows cached
    assert set(again) == {"kernel", "layout", "total"}
    for prof in (first, again):
        assert 0 < prof["layout"] <= prof["kernel"] <= prof["total"]


def test_span_feeds_only_an_active_profile():
    with trace.span("outside", "kernel"):       # no launch: annotation only
        pass
    with trace.collect() as outer:
        with trace.span("a", "kernel"):
            with trace.collect() as inner:
                with trace.span("b", "layout"):
                    pass
        with trace.span("c"):                   # no phase: annotation only
            pass
    assert set(outer) == {"kernel"} and set(inner) == {"layout"}
    assert outer["kernel"] >= inner["layout"] >= 0


def test_map_profile_phases():
    k, (u, v) = _grids()

    def tgt(u, v):
        st.map(e=u.shape)(k)(u, v)
    prof = st.launch(backend=st.xla())(tgt)(u, v).profile
    assert set(prof) == {"codegen", "comp", "kernel", "total"}
    assert prof["comp"] <= prof["codegen"] <= prof["total"]


def _acoustic_grad():
    k = acoustic.acoustic_iso_kernel
    grids = [st.grid(dtype=st.f32, shape=(8, 8, 16),
                     order=k.info.order).randomize(i)
             for i, _ in enumerate(k.ir.grid_params)]
    fn = st.differentiable_timeloop(k, *grids, 0.3, steps=12,
                                    swap=("p0", "p1"), fuse_steps=1,
                                    backend=st.xla())
    return fn, jax.jit(jax.grad(lambda a: jnp.sum(fn(a)["p0"] ** 2)))


def test_adjoint_scopes_in_compiled_hlo():
    """The coefficient grid ``vp2`` multiplies the wavefield, so each
    window's pullback needs its replayed carry and no replay is dead."""
    fn, grad = _acoustic_grad()
    hlo = grad.lower(fn.arrays).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    for scope in (trace.ADJOINT_FORWARD, trace.ADJOINT_REPLAY,
                  trace.ADJOINT_VJP):
        assert any(scope in n for n in names), scope


def test_checkpoint_stats_describe_one_pass():
    fn, _ = _acoustic_grad()
    sched = fn.schedule
    passes = []
    for _ in range(2):
        jax.jit(jax.grad(lambda a: jnp.sum(fn(a)["p0"] ** 2))).lower(
            fn.arrays)
        passes.append(dict(adjoint.CHECKPOINT_STATS))
    assert passes[0] == passes[1]
    w = len(sched["windows"])
    assert passes[0] == {"forward_windows": w,
                         "checkpoints": sched["checkpoints"],
                         "replayed_windows": w - sched["checkpoints"],
                         "vjp_windows": w}
