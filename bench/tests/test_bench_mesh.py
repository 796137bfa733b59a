"""The four-chip cell on four host devices, and the mesh metrics' readers.

The cell's rehearsal runs through ``run.py --rehearse`` on a (4,) mesh of
CPU devices and passes its check; the check fails when the program
leaves the state unchanged or exchanges no halo.  The readers of the
collectives, the compulsory halo and the program's exchange counter are
checked on synthetic traces and counters with known answers."""
import importlib.util
import os
import sys

import pytest

import benchtest

sys.path.insert(0, benchtest.BENCH)
import cells  # noqa: E402
import meshtrace  # noqa: E402
from devtrace import Op, Trace  # noqa: E402
from reference.stencil import Kernel  # noqa: E402

CELL = "acoustic-iso-1k-4chip.fused100"

#: four CPU devices, set before the child process imports JAX
FOUR = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
"""

#: every window program returns its input
UNCHANGED = """
from repro.core import timeloop as _tl
_orig = _tl.TimeloopEngine._window
def _window(self, kw, masked=False, donate=False):
    _orig(self, kw, masked=masked, donate=donate)
    return lambda arrays, *rest: arrays
_tl.TimeloopEngine._window = _window
"""

#: every halo slab arrives as zeros, as if no shard had a neighbour
NO_EXCHANGE = """
import jax.numpy as _jnp
from repro.core import distributed as _dist
def _no_exchange(local, axis, mesh_axis, h, mesh):
    edge = tuple(slice(0, h) if a == axis else slice(None)
                 for a in range(local.ndim))
    zero = _jnp.zeros_like(local[edge])
    return zero, zero
_dist._halo_exchange = _no_exchange
"""

SHARDED = """
import cells, jax
_make = cells.make_job
def _make_job(spec, seed, devices):
    job = _make(spec, seed, devices)
    assert len(job.mesh.devices.flat) == 4, job.mesh
    return job
cells.make_job = _make_job
"""


def test_rehearsal_on_four_devices_is_correct():
    rc, last, out, err = benchtest.run_cell(CELL, patch=FOUR + SHARDED)
    assert rc == 0, err[-3000:]
    assert last["rehearsal"] is True and last["correct"] is True, last
    assert last["checks"]["rel_err"]["value"] <= 1e-6, last


@pytest.mark.parametrize("fault,patch", [("unchanged", UNCHANGED),
                                         ("no_exchange", NO_EXCHANGE)])
def test_fault_fails_the_check_on_four_devices(fault, patch):
    rc, last, out, err = benchtest.run_cell(CELL, patch=FOUR + patch)
    assert rc == 0, err[-3000:]
    assert last["correct"] is False, last
    assert last["checks"]["rel_err"]["value"] > 1e-2, last


def test_sharded_reference_equals_the_plain_one():
    """The reference in shards (its own ppermute exchange) gives the plain
    reference's leapfrog on four devices, bit for bit: the same jnp
    expression on the same values, only split into slabs."""
    import subprocess
    import textwrap
    code = FOUR + textwrap.dedent(f"""
        import sys, json
        sys.path[:0] = [{benchtest.BENCH!r}, {os.path.join(benchtest.ROOT, 'src')!r}]
        import jax, numpy as np, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from reference import sharded, stencil as ref
        cfg = json.load(open({os.path.join(benchtest.BENCH, 'configs',
                                           'acoustic-iso-1k-4chip.json')!r}))
        k = ref.Kernel(cfg["kernel"], 4)
        mesh = make_mesh((4,), ("data",))
        rng = np.random.default_rng(11)
        shape = (40, 12, 20)
        a = {{g: np.pad(rng.uniform(lo, hi, shape).astype(np.float32), 4)
             for g, lo, hi in (("p0", -1, 1), ("p1", -1, 1),
                               ("vp2", 1.6, 2.2), ("damp", 0, .2))}}
        on = {{g: jax.device_put(v, NamedSharding(mesh, P("data")))
              for g, v in a.items()}}
        got = sharded.leapfrog(k, ("p0", "p1"), 5, mesh, "data")(on, {{"dt": 0.3}})
        want = ref.leapfrog(k, ("p0", "p1"), 5)(a, {{"dt": 0.3}})
        for g in a:
            np.testing.assert_array_equal(np.asarray(got[g]), np.asarray(want[g]))
            assert len(got[g].sharding.device_set) == 4
        assert sharded.rel_errs(got, want, ("p0", "p1"), 4) == 0.0
        assert float(jnp.abs(want["p0"]).max()) > 0.1
        print("OK")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0 and "OK" in p.stdout, p.stderr[-3000:]


def reader(name):
    path = os.path.join(benchtest.BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class _Run:
    def __init__(self, tr, workload=CELL):
        self.spec = cells.Spec(workload)
        self.trace, self.jobs = tr, 2
        self.work_points = self.spec.points * self.spec.steps
        self.bytes_per_point = 20
        self.peaks = {"hbm_bytes_per_s": 8e11, "ici_bits_per_s": 1.6e12}


def _mesh_trace():
    # window [0, 1000) ns.  Device 0: compute 0-400 and 600-1000, a
    # permute in flight 300-700 (its start's span) and done 700-720: only
    # 400-600 runs with no compute beside it (200 ns exposed).  Device 1:
    # compute 0-900, a permute 850-950 (50 ns exposed).
    d0 = [Op("fusion.1", 0, 400, False), Op("fusion.2", 600, 1000, False),
          Op("collective-permute-start.1", 300, 700, False),
          Op("collective-permute-done.1", 700, 720, False)]
    d1 = [Op("fusion.1", 0, 900, False),
          Op("collective-permute-start.2", 850, 950, False)]
    return Trace(window=(0, 1000), devices={0: d0, 1: d1},
                 host=[("bench.window", 0, 1000)])


def test_collectives_classified_by_opcode():
    for name in ("collective-permute-start.1", "collective-permute-done",
                 "all-reduce.7", "all-gather-start", "all-to-all.2",
                 "reduce-scatter.1", "%all-reduce-done.3"):
        assert meshtrace.is_collective(name), name
    for name in ("fusion.3", "copy-start", "slice-start.2",
                 "stencil_acoustic_iso_kernel", "dynamic-update-slice"):
        assert not meshtrace.is_collective(name), name


def test_compulsory_halo_of_the_cell():
    """Only p1 is read off-centre along the split axis, 4 rows deep: a
    4 × 1024 × 512 f32 slab (8.39 MB) across each of 3 shard boundaries,
    both ways, is 12.58 MB per chip per step."""
    spec = cells.Spec(CELL)
    offs = meshtrace.read_offsets(Kernel(spec.config["kernel"], spec.order))
    assert {g for g, o in offs.items() if any(x[0] for x in o)} == {"p1"}
    assert meshtrace.halo_bytes_per_chip_step(spec) == 6 * 4 * 1024 * 512 \
        * 4 / 4 == pytest.approx(12.58e6, rel=1e-3)
    assert meshtrace.halo_bytes_per_chip_step(
        cells.Spec("acoustic-iso-512.src1")) is None


def _no_async_line_trace():
    # the shape of a chip whose trace has no ``Async XLA Ops`` line (on a
    # TPU v5e host of four, every chip but the first): each permute shows
    # its issue and its wait on the ``XLA Ops`` line between the compute,
    # 5 + 5 + 120 + 1 ns exposed
    ops = [Op("fusion.1", 0, 400, False),
           Op("collective-permute-start", 400, 405, False),
           Op("collective-permute-start.1", 405, 410, False),
           Op("fusion.2", 410, 800, False),
           Op("collective-permute-done", 800, 920, False),
           Op("collective-permute-done.1", 920, 921, False),
           Op("fusion.3", 921, 1000, False)]
    return Trace(window=(0, 1000), devices={1: ops}, host=[])


@pytest.mark.parametrize("trace,want", [(_mesh_trace, 20.0),
                                        (_no_async_line_trace, 13.1)],
                         ids=["async_line", "no_async_line"])
def test_exposed_collective_share_partly_hidden(trace, want):
    # with the async line, device 0: 300-720 in flight, compute leaves
    # 400-600 uncovered
    assert reader("exposed_collective_share.mesh")(
        _Run(trace())) == pytest.approx(want)


def test_compute_roofline_counts_non_collective_time():
    run = _Run(_mesh_trace())
    secs = (400 + 400 + 900) * 1e-9
    want = 100 * 2 * run.work_points * 20 / (8e11 * secs)
    assert reader("compute_roofline.mesh")(run) == pytest.approx(want)
    # a copy in flight 100-500 (its start's span) and a slice 350-450
    # overlap device 0's first fusion: the union of 0-400, 100-500,
    # 350-450 and 600-1000 is 0-500 and 600-1000, 100 ns more than the
    # fusions alone, where a sum of durations would add 500 ns
    tr = _mesh_trace()
    tr.devices[0] += [Op("copy-start.1", 100, 500, False),
                      Op("slice.4", 350, 450, False)]
    secs = (500 + 400 + 900) * 1e-9
    want = 100 * 2 * run.work_points * 20 / (8e11 * secs)
    assert reader("compute_roofline.mesh")(_Run(tr)) == pytest.approx(want)
    quiet = Trace(window=(0, 10), devices={0: [Op("fusion", 0, 5, False)]},
                  host=[])
    assert reader("compute_roofline.mesh")(_Run(quiet)) is None
    assert reader("exposed_collective_share.mesh")(_Run(quiet)) is None
    assert reader("compute_roofline.mesh")(_Run(None)) is None


def test_exchange_ratio_reads_the_program_counter(monkeypatch):
    from repro.core import distributed as dist
    run = _Run(None)
    per_step = meshtrace.halo_bytes_per_chip_step(run.spec)
    monkeypatch.setattr(dist, "EXCHANGE_STATS", {
        "windows": 3, "groups": 300,
        "collective_bytes": int(3 * 100 * 2.5 * per_step)})
    assert reader("exchange_ratio.mesh")(run) == pytest.approx(250.0)
    monkeypatch.setattr(dist, "EXCHANGE_STATS", {
        "windows": 0, "groups": 0, "collective_bytes": 0})
    assert reader("exchange_ratio.mesh")(run) is None
