"""The reduction from a profiler trace to the per-layer metrics: interval
arithmetic on synthetic events with known overlaps, the metric readers on
a synthetic trace, and ``devtrace.load`` on a trace recorded on the CPU
inside the test."""
import importlib.util
import os
import sys

import pytest

import benchtest

sys.path.insert(0, benchtest.BENCH)
import devtrace  # noqa: E402
from devtrace import Op, Trace, clip, gaps, length, subtract, union  # noqa: E402


def reader(name):
    path = os.path.join(benchtest.BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_union_merges_overlaps_and_touching():
    assert union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == [(0, 4), (5, 7)]
    assert length([(0, 10), (5, 15), (20, 25)]) == 20


def test_subtract_clip_and_gaps():
    assert subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert subtract([(0, 4), (6, 8)], []) == [(0, 4), (6, 8)]
    assert clip([(0, 5), (8, 12), (20, 30)], 2, 10) == [(2, 5), (8, 10)]
    assert gaps([(2, 4), (6, 7)], 0, 10) == [(0, 2), (4, 6), (7, 10)]


@pytest.mark.parametrize("text,name,opcode,kernel", [
    ("%stencil_star3d4r_gmem_fused_step_k1.3 = f32[528,528,768]{2,1,0:T(8,128)}"
     " custom-call(f32[528,528,768]{2,1,0:T(8,128)} %get-tuple-element.35),"
     " custom_call_target=\"tpu_custom_call\"",
     "stencil_star3d4r_gmem_fused_step_k1.3", "custom-call", True),
    ("%while = (s32[]{:T(128)}, f32[80,80,384]{2,1,0:T(8,128)}) while((s32[]"
     "{:T(128)}, f32[80,80,384]{2,1,0:T(8,128)}) %tuple.12), condition=%c",
     "while", "while", False),
    ("%copy.10 = f32[528,528,768]{2,1,0:T(8,128)} copy(f32[528,528,768]"
     "{2,1,0:T(8,128)} %get-tuple-element.35)", "copy.10", "copy", False),
    ("%collective-permute-done.1 = f32[4,8]{1,0} collective-permute-done("
     "(f32[4,8]{1,0}, f32[4,8]{1,0}) %collective-permute-start.1)",
     "collective-permute-done.1", "collective-permute-done", False),
    ("%all-reduce.7 = (f32[], f32[]) all-reduce(f32[] %a, f32[] %b)",
     "all-reduce.7", "all-reduce", False),
    ("dot_general.1", "dot_general.1", "", False),
    ("stencil_x_gmem", "stencil_x_gmem", "", True),
])
def test_classify(text, name, opcode, kernel):
    assert devtrace.classify(text) == (name, opcode, kernel)


def _synthetic():
    # window [0, 100); device 0: kernel 10-40, pad 40-50, kernel 60-90;
    # device 1: fusion 0-30, a copy 20-50 overlapping it, fusion 70-80
    d0 = [Op("stencil_k.1", 10, 40, True), Op("pad", 40, 50, False),
          Op("stencil_k.2", 60, 90, True)]
    d1 = [Op("fusion", 0, 30, False), Op("copy-start", 20, 50, False),
          Op("fusion", 70, 80, False)]
    host = [("bench.window", 0, 100), ("bench.job", 0, 100),
            ("PjitFunction(win)", 50, 60), ("bench.hook", 90, 100)]
    return Trace(window=(0, 100), devices={0: d0, 1: d1}, host=host)


def test_trace_reductions():
    tr = _synthetic()
    assert tr.busy_ns(0) == 70 and tr.busy_ns(1) == 60
    assert tr.idle_share(0) == pytest.approx(0.3)
    assert tr.summed_ns(1) == 70 and tr.summed_ns(0, "kernel") == 60
    # the most idle device is 1: gaps 50-70 (in bench.job) and 80-100
    assert tr.idle_by_host() == [["bench.job", 2e-08], ["bench.hook", 2e-08]]
    assert tr.top_ops(1) == [["stencil_k", 3e-08]]


class _Run:
    def __init__(self, tr):
        self.trace, self.jobs, self.work_points = tr, 2, 1000
        self.bytes_per_point = 8
        self.peaks = {"hbm_bytes_per_s": 1e9}


def test_metric_readers_on_synthetic_trace():
    run = _Run(_synthetic())
    # 16,000 compulsory bytes at 1 GB/s is 16 us against 60 ns of kernel
    assert reader("kernel_roofline")(run) == pytest.approx(
        100 * 16000 / (1e9 * 60e-9))
    assert reader("layout_share")(run) == pytest.approx(
        100 * (10 / 70 + 60 / 60) / 2)
    assert reader("device_idle_share.prop")(run) == pytest.approx(40.0)
    assert reader("pallas_share.grad")(run) == pytest.approx(
        100 * (60 / 70 + 0 / 60) / 2)


def test_readers_silent_without_their_events():
    tr = Trace(window=(0, 100), devices={0: [Op("fusion", 0, 50, False)]},
               host=[("bench.window", 0, 100)])
    run = _Run(tr)
    assert reader("kernel_roofline")(run) is None
    assert reader("layout_share")(run) is None
    assert reader("pallas_share.grad")(run) == 0.0
    assert reader("device_idle_share.grad")(run) == pytest.approx(50.0)
    run.trace = None
    assert reader("device_idle_share.prop")(run) is None


def test_load_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(devtrace.WINDOW):
        for _ in range(3):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = devtrace.load(str(tmp_path), devices=[0])
    assert tr.window_ns > 0
    assert tr.devices[0], "no operation found in the CPU trace"
    assert 0 < tr.busy_ns(0) <= tr.window_ns
    assert 0 <= tr.idle_share(0) < 1
    assert not tr.has("kernel")
    assert tr.top_ops() and tr.idle_by_host()


@pytest.mark.parametrize("name,base", [
    ("gpts_per_s.hooked", "gpts_per_s"),
    ("kernel_roofline.hooked", "kernel_roofline"),
    ("layout_share.hooked", "layout_share"),
    ("device_idle_share.hooked", "device_idle_share.prop")])
def test_hooked_metric_reads_as_its_base(name, base):
    run = _Run(_synthetic())
    run.window_s = 2.0
    assert reader(name)(run) == reader(base)(run) is not None
