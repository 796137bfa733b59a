"""Distributed stencil runtime tests.

These must see >1 device, so they run in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the main test
process keeps the default single device, as required by the dry-run
contract)."""
import os
import subprocess
import sys
import textwrap

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_in_subprocess(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


CHECK_BODY = """
import jax, numpy as np, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from jax.sharding import Mesh
from repro.core import dsl as st, suite
from repro.kernels.stencil import ref

assert len(jax.devices()) == 8, jax.devices()

def check(name, mesh_shape, axis_names, grid_axes, overlap, inner):
    k = suite.get_kernel(name)
    nd = k.info.ndim
    interior = (32, 32) if nd == 2 else (16, 16, 32)
    mesh = make_mesh(mesh_shape, axis_names)
    u = st.grid(dtype=st.f32, shape=interior, order=k.info.order).randomize(0)
    v = st.grid(dtype=st.f32, shape=interior, order=k.info.order)
    be = st.distributed(grid_axes=grid_axes, overlap=overlap, inner=inner)
    def tgt(u, v):
        for _ in range(3):
            st.map(e=u.shape)(k)(u, v)
            (v, u) = (u, v)
        return u
    got = st.launch(backend=be, mesh=mesh)(tgt)(u, v).value.interior

    u2 = st.grid(dtype=st.f32, shape=interior, order=k.info.order).randomize(0)
    v2 = st.grid(dtype=st.f32, shape=interior, order=k.info.order)
    want = st.launch(backend=st.xla())(tgt)(u2, v2).value.interior
    err = float(jnp.abs(got - want).max())
    assert err < 1e-5, (name, mesh_shape, grid_axes, overlap, err)
    print('OK', name, mesh_shape, grid_axes, 'overlap' if overlap else 'sync')
"""


def test_distributed_1d_decomposition():
    _run_in_subprocess(CHECK_BODY + """
check('star2d2r', (8,), ('data',), ('data', None), False, st.xla())
check('star2d2r', (8,), ('data',), ('data', None), True, st.xla())
""")


def test_distributed_2d_decomposition_box():
    _run_in_subprocess(CHECK_BODY + """
check('box2d1r', (4, 2), ('data', 'model'), ('data', 'model'), False, st.xla())
check('box2d1r', (4, 2), ('data', 'model'), ('data', 'model'), True, st.xla())
""")


def test_distributed_3d_multipod_axes():
    _run_in_subprocess(CHECK_BODY + """
check('star3d2r', (2, 2, 2), ('pod', 'data', 'model'),
      ('pod', 'data', 'model'), True, st.xla())
""")


def test_distributed_with_pallas_inner():
    _run_in_subprocess(CHECK_BODY + """
check('star3d1r', (2, 2), ('data', 'model'), ('data', 'model', None), False,
      st.pallas(template='gmem', block=(8, 8, 128)))
""")


def test_distributed_rejects_bad_divisibility():
    _run_in_subprocess(CHECK_BODY + """
from repro.core import distributed as dist
from jax.sharding import Mesh
k = suite.get_kernel('star2d1r')
mesh = make_mesh((8,), ('data',))
try:
    dist.lower_distributed(k.ir, {'u': (1, 1), 'v': (1, 1)}, (30, 30), None,
                           st.distributed(grid_axes=('data', None)), mesh)
except ValueError as e:
    assert 'not divisible' in str(e)
    print('OK divisibility')
else:
    raise AssertionError('expected ValueError')
""")


def test_timeloop_fused_distributed_matches_per_step():
    """st.timeloop on the distributed backend (one shard_mapped program
    per fusion window: fori_loop over depth-k exchange groups) must match
    the per-step distributed target.  The window is a fuse cadence, not
    an exchange depth — any size works; depth (time_steps × time_block)
    is clamped to the window and to k·h ≤ local extent by HaloSpec."""
    _run_in_subprocess("""
import jax, numpy as np
from repro.launch.mesh import make_mesh
from repro.core import acoustic, dsl as st

mesh = make_mesh((4, 2), ("data", "model"))
shape = (48, 32, 24)

def mk():
    p0, p1, vp2, damp, dt = acoustic.make_fields(shape, pml_width=4)
    acoustic.inject_source(p1, 0)
    return p0, p1, vp2, damp, dt

p0, p1, vp2, damp, dt = mk()
st.launch(backend=st.distributed(grid_axes=("data", "model", None),
                                 overlap=False), mesh=mesh)(
    acoustic.acoustic_target)(p0, p1, vp2, damp, dt, 6)
ref0, ref1 = np.asarray(p0.data), np.asarray(p1.data)

for fuse in (1, 2, 3, 6):   # 6-step window = 6 depth-1 groups, ONE program
    q = mk()
    st.launch(backend=st.distributed(grid_axes=("data", "model", None)),
              mesh=mesh, fuse_steps=fuse)(
        lambda *a: st.timeloop(6, swap=("p0", "p1"))(
            acoustic.acoustic_iso_kernel)(*a))(*q[:5])
    err = max(float(np.abs(np.asarray(q[0].data) - ref0).max()),
              float(np.abs(np.asarray(q[1].data) - ref1).max()))
    assert err < 1e-6, (fuse, err)
    print("OK fused-distributed", fuse)
""")


def test_time_skewed_matches_stepwise():
    """Overlapped tiling (time_steps=k, ONE k·h-wide exchange) must equal
    k separately-exchanged steps — including at global boundaries where
    the zero grid-halo is re-imposed between fused steps."""
    _run_in_subprocess("""
import jax, numpy as np, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core import acoustic, dsl as st
from repro.core import distributed as dist

mesh = make_mesh((4, 2), ("data", "model"))
shape = (48, 32, 24)  # local (12,16): fits k*h <= 12 for k=3, h=4
k_ir = acoustic.acoustic_iso_kernel.ir
halos = {g: acoustic.acoustic_iso_kernel.info.halo for g in k_ir.grid_params}

for k_steps in (2, 3):
    p0, p1, vp2, damp, dt = acoustic.make_fields(shape, pml_width=4)
    acoustic.inject_source(p1, 0)
    arrays = {"p0": p0.data, "p1": p1.data, "vp2": vp2.data,
              "damp": damp.data}
    scal = {"dt": dt}

    be = st.distributed(grid_axes=("data", "model", None),
                        time_steps=k_steps, swap=("p0", "p1"))
    fused = dist.lower_distributed(k_ir, halos, shape, None, be, mesh)
    got = fused(dict(arrays), scal)

    be1 = st.distributed(grid_axes=("data", "model", None), overlap=False)
    step = dist.lower_distributed(k_ir, halos, shape, None, be1, mesh)
    ref = dict(arrays)
    for _ in range(k_steps):
        out = step(ref, scal)
        ref = dict(out, p0=ref["p1"], p1=out["p0"])

    for g in ("p0", "p1"):
        err = float(jnp.abs(got[g] - ref[g]).max())
        assert err < 1e-6, (k_steps, g, err)
    print("OK time-skew", k_steps)
""")


def test_time_skew_composes_with_inner_time_block():
    """Device-level skewing × in-kernel temporal blocking: a pallas inner
    carrying time_block=k_inner widens the exchange to
    time_steps·k_inner·h, and the fused result still equals separately
    exchanged steps.  Also reachable through st.timeloop, whose window
    maps onto (kw / k_inner) skewing groups."""
    _run_in_subprocess("""
import jax, numpy as np, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core import acoustic, dsl as st
from repro.core import distributed as dist

mesh = make_mesh((4, 2), ("data", "model"))
shape = (48, 32, 24)  # local (12,16): k_total*h <= 12 for k_total=3, h=4
k_ir = acoustic.acoustic_iso_kernel.ir
halos = {g: acoustic.acoustic_iso_kernel.info.halo for g in k_ir.grid_params}

be1 = st.distributed(grid_axes=("data", "model", None), overlap=False)

for t_steps, k_inner in ((1, 2), (1, 3), (3, 1)):
    k_total = t_steps * k_inner
    p0, p1, vp2, damp, dt = acoustic.make_fields(shape, pml_width=4)
    acoustic.inject_source(p1, 0)
    arrays = {"p0": p0.data, "p1": p1.data, "vp2": vp2.data,
              "damp": damp.data}
    scal = {"dt": dt}

    be = st.distributed(grid_axes=("data", "model", None),
                        time_steps=t_steps, swap=("p0", "p1"),
                        inner=st.pallas(time_block=k_inner))
    fused = dist.lower_distributed(k_ir, halos, shape, None, be, mesh)
    got = fused(dict(arrays), scal)

    step = dist.lower_distributed(k_ir, halos, shape, None, be1, mesh)
    ref = dict(arrays)
    for _ in range(k_total):
        out = step(ref, scal)
        ref = dict(out, p0=ref["p1"], p1=out["p0"])

    for g in ("p0", "p1"):
        err = float(jnp.abs(got[g] - ref[g]).max())
        assert err < 1e-6, (t_steps, k_inner, g, err)
    print("OK compose", t_steps, "x", k_inner)

# through the engine: fuse window -> (kw / k_inner) skewing groups
p0, p1, vp2, damp, dt = acoustic.make_fields(shape, pml_width=4)
acoustic.inject_source(p1, 0)
st.launch(backend=st.distributed(grid_axes=("data", "model", None),
                                 overlap=False), mesh=mesh)(
    acoustic.acoustic_target)(p0, p1, vp2, damp, dt, 6)
ref0, ref1 = np.asarray(p0.data), np.asarray(p1.data)

q = acoustic.make_fields(shape, pml_width=4)
acoustic.inject_source(q[1], 0)
st.launch(backend=st.distributed(grid_axes=("data", "model", None),
                                 inner=st.pallas(time_block=2)),
          mesh=mesh, fuse_steps=2)(
    lambda *a: st.timeloop(6, swap=("p0", "p1"))(
        acoustic.acoustic_iso_kernel)(*a))(*q[:5])
err = max(float(np.abs(np.asarray(q[0].data) - ref0).max()),
          float(np.abs(np.asarray(q[1].data) - ref1).max()))
assert err < 1e-6, err
print("OK engine-compose")
""")


def test_fused_window_single_program_and_collective_model():
    """The fused window lowering advances W steps in ONE jitted program
    (fori_loop over full-depth groups + unrolled remainder), matches the
    proven per-exchange path, and its compiled HLO moves exactly the
    collective bytes ``HaloSpec.window_collective_bytes`` prices — the
    model the distributed cost model and the regression guard rely on."""
    _run_in_subprocess("""
import jax, numpy as np, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core import acoustic, dsl as st
from repro.core import distributed as dist
from repro.launch import hlo_analysis

mesh = make_mesh((4, 2), ("data", "model"))
shape = (48, 32, 24)
k_ir = acoustic.acoustic_iso_kernel.ir

for window, t_steps in ((5, 2), (6, 3), (4, 1)):
    p0, p1, vp2, damp, dt = acoustic.make_fields(shape, pml_width=4)
    acoustic.inject_source(p1, 0)
    arrays = {"p0": p0.data, "p1": p1.data, "vp2": vp2.data,
              "damp": damp.data}
    scal = {"dt": jnp.float32(dt)}
    interiors = {g: a[tuple(slice(4, 4 + s) for s in shape)]
                 for g, a in arrays.items()}

    be = st.distributed(grid_axes=("data", "model", None),
                        time_steps=t_steps, swap=("p0", "p1"))
    fn = dist.lower_distributed_window(k_ir, shape, be, mesh,
                                       ("p0", "p1"), window)
    assert fn.depth == t_steps and fn.window == window
    got = fn(dict(arrays), scal)

    # reference: the per-exchange time-skewed path, group by group
    halos = {g: acoustic.acoustic_iso_kernel.info.halo
             for g in k_ir.grid_params}
    ref = dict(arrays)
    for count, d in fn.groups:
        bd = st.distributed(grid_axes=("data", "model", None),
                            time_steps=d, swap=("p0", "p1")) if d > 1 \
            else st.distributed(grid_axes=("data", "model", None),
                                overlap=False)
        g_fn = dist.lower_distributed(k_ir, halos, shape, None, bd, mesh)
        for _ in range(count):
            out = g_fn(ref, scal)
            # the time-skewed path (d > 1) returns post-swap state; the
            # per-step path writes swap[0] and leaves the swap to us
            ref = dict(out, p0=ref["p1"], p1=out["p0"]) if d == 1 else out
    for g in ("p0", "p1"):
        err = float(jnp.abs(got[g] - ref[g]).max())
        assert err < 1e-6, (window, t_steps, g, err)

    # ONE program; its HLO collective traffic == the HaloSpec price
    hlo = fn.jitted.lower(interiors, scal).compile().as_text()
    stats = hlo_analysis.op_stats(hlo, n_devices=8)
    want = fn.spec.window_collective_bytes(window, 4)
    assert stats.collective_bytes == want, (
        window, t_steps, stats.collective_bytes, want)
    print("OK fused-window", window, "depth", t_steps,
          int(stats.collective_bytes), "coll bytes")
""")


def test_distributed_batched_multi_device():
    """Satellite: batched scenarios ride the fused sharded timeloop — a
    leading unsharded batch axis over a real multi-device mesh must equal
    per-scenario distributed runs."""
    _run_in_subprocess("""
import jax, numpy as np, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core import dsl as st, suite

B, STEPS, FUSE = 3, 6, 3
mesh = make_mesh((4,), ("data",))
k = suite.get_kernel("star2d2r")
shape = (32, 24)
rng = np.random.default_rng(0)
inits = {g: rng.standard_normal((B,) + shape).astype(np.float32)
         for g in k.ir.grid_params}
be = st.distributed(grid_axes=("data", None), time_steps=2)

ser = []
for b in range(B):
    gs = {g: st.grid(st.f32, shape, k.info.order) for g in k.ir.grid_params}
    for g in gs:
        gs[g].interior = inits[g][b]
    st.launch(backend=be, mesh=mesh, fuse_steps=FUSE)(
        lambda *a: st.timeloop(STEPS, swap=suite.swap_pair(k.name))(k)(*a))(
        *gs.values())
    ser.append({g: np.asarray(gs[g].interior) for g in gs})

gb = {g: st.grid(st.f32, shape, k.info.order, batch=B)
      for g in k.ir.grid_params}
for g in gb:
    gb[g].interior = inits[g]
st.launch(backend=be, mesh=mesh, fuse_steps=FUSE)(
    lambda *a: st.timeloop(STEPS, swap=suite.swap_pair(k.name), batch=B)(k)(
        *a))(*gb.values())

for g in gb:
    for b in range(B):
        err = float(np.abs(np.asarray(gb[g].interior)[b] - ser[b][g]).max())
        assert err < 1e-5, (g, b, err)
print("OK batched-distributed 4dev")
""")


def test_resilient_distributed_multi_device(tmp_path):
    """Satellite: checkpoint/restore of the leapfrog carry under the
    fused sharded timeloop is bit-exact across an injected failure on a
    real multi-device mesh."""
    _run_in_subprocess(f"""
import jax, numpy as np
from repro.launch.mesh import make_mesh
from repro.core import dsl as st, suite
from repro.core.timeloop import TimeloopEngine, run_resilient
from repro.train.fault_tolerance import FailureInjector

mesh = make_mesh((4,), ("data",))
k = suite.get_kernel("star2d1r")
shape = (24, 16)
halos = {{g: (k.info.order,) * k.info.ndim for g in k.ir.grid_params}}
be = st.distributed(grid_axes=("data", None), time_steps=2)

def engine():
    return TimeloopEngine(k.ir, halos, shape, be,
                          swap=suite.swap_pair(k.name), mesh=mesh)

gs = {{g: st.grid(np.float32, shape, k.info.order).randomize(i)
      for i, g in enumerate(k.ir.grid_params)}}
inits = {{g: np.asarray(v.data) for g, v in gs.items()}}

ref = engine().run(dict(inits), {{}}, 7, 4)
got = run_resilient(engine(), dict(inits), {{}}, 7, 4,
                    ckpt_dir={str(tmp_path / 'ck')!r}, ckpt_every=1,
                    injector=FailureInjector([1]))
for g in ref:
    assert np.array_equal(np.asarray(ref[g]), np.asarray(got[g])), g
print("OK resilient-distributed 4dev")
""")
