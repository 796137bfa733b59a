"""The sharded fused window on four host devices: acoustic-ISO against the
plain reference, the sharding of what it returns, and its exchange
counter.

Each test runs in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the main test
process keeps the default single device, as the dry-run requires).
"""
import os
import subprocess
import sys
import textwrap

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_in_subprocess(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


SETUP = f"""
import json, sys
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import dsl as st
from repro.launch.mesh import make_mesh
sys.path.insert(0, {os.path.join(_REPO, 'bench')!r})
from reference import stencil as ref

assert len(jax.devices()) == 4, jax.devices()
CFG = json.load(open({os.path.join(_REPO, 'bench', 'configs',
                                   'acoustic-iso-1k-4chip.json')!r}))
SHAPE, H, SWAP = (48, 16, 32), 4, ("p0", "p1")
GRIDS = ("p0", "p1", "vp2", "damp")
mesh = make_mesh((4,), ("data",))
sharding = NamedSharding(mesh, P("data", None, None))
backend = st.distributed(grid_axes=("data", None, None), swap=SWAP)


def program_kernel():
    ns = {{"st": st}}
    exec(CFG["kernel"], ns)
    fn = [v for k, v in ns.items() if k != "st" and callable(v)][0]
    fn.__stencil_source__ = CFG["kernel"]
    return st.kernel(fn)


def fields(seed):
    rng = np.random.default_rng(seed)
    a = {{"p0": rng.standard_normal(SHAPE), "p1": rng.standard_normal(SHAPE),
         "vp2": rng.uniform(1.6, 2.2, SHAPE), "damp": rng.uniform(0, .2, SHAPE)}}
    return {{g: np.pad(v.astype(np.float32), H) for g, v in a.items()}}


def run(arrays, backend, steps, fuse, mesh=None, place=None):
    k = program_kernel()
    gs = [st.grid(dtype=st.f32, shape=SHAPE, order=H,
                  data=jax.device_put(arrays[g], place) if place
                  else jnp.asarray(arrays[g])) for g in GRIDS]
    st.launch(backend=backend, mesh=mesh, fuse_steps=fuse)(
        lambda: st.timeloop(steps, swap=SWAP)(k)(*gs, 0.3))()
    return dict(zip(GRIDS, gs))
"""


def test_sharded_acoustic_matches_reference_and_one_device():
    """Six steps in windows of four and two on a (4,) mesh, from seeded
    random wavefields, equal the plain reference (the configuration's
    DSL source evaluated with plain jnp) and the one-device ``st.xla()``
    run.  The tolerance is float32 rounding: XLA fuses and reassociates
    the 25-point sum differently on each path, and six steps of the
    leapfrog carry those last-bit differences; a missing or misplaced
    halo slab errs by order one."""
    _run_in_subprocess(SETUP + """
arrays = fields(1_900_000_007)
got = run(arrays, backend, 6, 4, mesh, sharding)
one = run(arrays, st.xla(), 6, 4)
want = ref.leapfrog(ref.Kernel(CFG["kernel"], H), SWAP, 6)(
    {g: jnp.asarray(a) for g, a in arrays.items()}, {"dt": 0.3})
for g in SWAP:
    scale = float(jnp.abs(want[g]).max())
    assert scale > 0.1, scale
    for other, name in ((want[g], "reference"), (one[g].data, "xla")):
        err = float(jnp.abs(got[g].data - other).max()) / scale
        assert err < 1e-5, (g, name, err)
print("OK")
""")


def test_timeloop_keeps_every_grid_sharded():
    """After a distributed ``st.timeloop`` every grid is the mesh's
    ``NamedSharding``; the window program returns no fully replicated
    output, and a device holds fewer bytes than the grids' global
    extent (the crop and write-back no longer gather the grids whole)."""
    _run_in_subprocess(SETUP + """
from repro.core.timeloop import TimeloopEngine
gs = run(fields(3), backend, 5, None, mesh, sharding)
for g, grid in gs.items():
    assert grid.data.sharding.is_equivalent_to(sharding, 3), (
        g, grid.data.sharding)

k = program_kernel()
halos = {g: (H,) * 3 for g in GRIDS}
eng = TimeloopEngine(k.ir, halos, SHAPE, backend, swap=SWAP, mesh=mesh)
full = tuple(n + 2 * H for n in SHAPE)
arrays = {g: jax.ShapeDtypeStruct(full, jnp.float32, sharding=sharding)
          for g in GRIDS}
scal = {"dt": jax.ShapeDtypeStruct((), jnp.float32)}
c = jax.jit(eng.window_arrays(5)).lower(arrays, scal).compile()
outs = jax.tree.leaves(c.output_shardings)
assert len(outs) == len(GRIDS)
assert not any(s.is_fully_replicated for s in outs), outs
m = c.memory_analysis()
per_device = (m.argument_size_in_bytes + m.output_size_in_bytes
              + m.temp_size_in_bytes - m.alias_size_in_bytes)
global_bytes = len(GRIDS) * int(np.prod(full)) * 4
assert per_device < global_bytes, (per_device, global_bytes)
assert "all-gather" not in c.as_text()
print("OK")
""")


def test_exchange_stats_count_sent_windows_only():
    """``EXCHANGE_STATS`` adds ``spec.window_collective_bytes`` and the
    exchange groups of every window the engine sends (two windows of 4
    and one of 2 for 10 steps at ``fuse_steps=4``), and nothing for the
    pure ``window_arrays`` callables the adjoint traces."""
    _run_in_subprocess(SETUP + """
from repro.core import distributed as dist
from repro.core.timeloop import TimeloopEngine
dist.reset_exchange_stats()
assert dist.EXCHANGE_STATS == {"windows": 0, "groups": 0,
                               "collective_bytes": 0}
run(fields(5), backend, 10, 4, mesh, sharding)
k = program_kernel()
halos = {g: (H,) * 3 for g in GRIDS}
eng = TimeloopEngine(k.ir, halos, SHAPE, backend, swap=SWAP, mesh=mesh)
want = {"windows": 3, "groups": 0, "collective_bytes": 0}
for kw in (4, 4, 2):
    fn = eng._window(kw)
    want["groups"] += sum(n for n, _ in fn.groups)
    want["collective_bytes"] += fn.spec.window_collective_bytes(kw, 4)
assert dist.EXCHANGE_STATS == want, (dist.EXCHANGE_STATS, want)
assert want["groups"] == 10 and want["collective_bytes"] > 0, want
arrays = {g: jnp.asarray(a) for g, a in fields(6).items()}
jax.block_until_ready(eng.window_arrays(4)(arrays, {"dt": 0.3}))
assert dist.EXCHANGE_STATS == want, dist.EXCHANGE_STATS
print("OK")
""")


def test_odd_halo_leaves_the_padded_axis_unsplit():
    """With a halo of 1 on a (4,) mesh the padded axis holds n + 2 rows,
    which no JAX array can split four ways: the window leaves that axis
    whole on every device (the documented limit of ``_on_padded``), and
    the run still equals the one-device ``st.xla()`` run.  The tolerance
    is float32 rounding of the 7-point sum over four steps."""
    _run_in_subprocess(SETUP + """
from repro.core import suite
k = suite.get_kernel("star3d1r")
shape = (16, 8, 8)

def go(backend, mesh=None):
    u = st.grid(dtype=st.f32, shape=shape, order=1).randomize(0)
    v = st.grid(dtype=st.f32, shape=shape, order=1)
    st.launch(backend=backend, mesh=mesh, fuse_steps=2)(
        lambda: st.timeloop(4, swap=("v", "u"))(k)(u, v))()
    return u, v

got = go(backend, mesh)
want = go(st.xla())
assert got[0].data.shape[0] % 4 != 0, got[0].data.shape
for g, w in zip(got, want):
    assert g.data.sharding.is_fully_replicated, g.data.sharding
    err = float(jnp.abs(g.data - w.data).max())
    assert err < 1e-5, err
print("OK")
""")
