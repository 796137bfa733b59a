"""Distributed adjoint tests: the shard_mapped backward wave propagation.

The backward pass of a distributed engine replays each checkpointed
segment through the engine's own fused shard_map window programs and
pulls cotangents through a second shard_map program whose halo exchanges
are the reverse ``ppermute``s of the forward ones
(``HaloSpec.transpose`` geometry).  These tests pin, on a forced
4-host-device mesh:

  * gradient vs central finite differences (<1e-3 rel err, f64) across
    ``time_steps`` × inner ``time_block`` exchange-depth combinations,
  * primal bit-for-bit equality and gradient equality with the
    single-device (xla) adjoint on the same problem,
  * sharded coefficient-grid (velocity model) and per-scenario scalar
    gradients under batching,
  * masked-cell freezing in the sharded adjoint (vs the batched xla
    masked adjoint),
  * resume-mid-backward resilience (``run_resilient(loss=...)`` +
    ``FailureInjector``) bit-exact with an uninterrupted run.

They must see >1 device, so they run in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the main test
process keeps the default single device, per the dry-run contract)."""
import os
import subprocess
import sys
import tempfile
import textwrap

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_in_subprocess(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    # a real file (not -c) so the DSL frontend can inspect.getsource
    # kernels defined inside the test body
    with tempfile.NamedTemporaryFile("w", suffix=".py", delete=False) as f:
        f.write(textwrap.dedent(code))
        path = f.name
    try:
        r = subprocess.run([sys.executable, path], capture_output=True,
                           text=True, env=env, timeout=900)
    finally:
        os.unlink(path)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


# shared prelude: f64, a 4-device mesh, engines over star2d2r, and an
# interior-only loss (the distributed carry convention keeps grid-halo
# cells fixed at zero and never rotates them, so only interiors are
# comparable across backends — and only interiors are physics)
PRELUDE = """
import jax
from repro.launch.mesh import make_mesh
jax.config.update("jax_enable_x64", True)
import numpy as np, jax.numpy as jnp
from repro.core import adjoint, dsl as st, suite
from repro.core import timeloop as tl

assert len(jax.devices()) == 4, jax.devices()
MESH = make_mesh((4,), ("data",))
K = suite.get_kernel("star2d2r")
SHAPE = (16, 12)
O = K.info.order
SWAP = suite.swap_pair(K.name)

def make_arrays(dtype=jnp.float64, batch=0, seed=0):
    gs = {g: st.grid(dtype=dtype, shape=SHAPE, order=O,
                     batch=batch or None).randomize(i + seed)
          for i, g in enumerate(K.ir.grid_params)}
    halos = {n: g.halo for n, g in gs.items()}
    return {n: jnp.asarray(g.data, dtype) for n, g in gs.items()}, halos

def engine(be, halos, batch=0):
    return tl.TimeloopEngine(K.ir, halos, SHAPE, be, swap=SWAP, mesh=MESH,
                             batch=batch, differentiable=True)

def idx(batch=0):
    return (slice(None),) * (1 if batch else 0) \\
        + tuple(slice(O, O + s) for s in SHAPE)

def interior_loss(fn, scal, batch=0):
    ix = idx(batch)
    def loss(arrs):
        out = fn(arrs, scal)
        return sum(jnp.sum(out[g][ix] ** 2) for g in SWAP)
    return loss

def check_fd(fn, arrays, scal, tag, batch=0, n_probes=2, eps=1e-6,
             rtol=1e-3):
    loss = interior_loss(fn, scal, batch)
    grad = jax.grad(loss)(arrays)
    rng = np.random.default_rng(7)
    for g, a in arrays.items():
        a = np.asarray(a)
        for _ in range(n_probes):
            ix = ((int(rng.integers(0, a.shape[0])),) if batch else ()) \\
                + tuple(int(rng.integers(O, O + s)) for s in SHAPE)
            ap, am = a.copy(), a.copy()
            ap[ix] += eps
            am[ix] -= eps
            fd = (float(loss({**arrays, g: jnp.asarray(ap)}))
                  - float(loss({**arrays, g: jnp.asarray(am)}))) / (2 * eps)
            ad = float(np.asarray(grad[g])[ix])
            err = abs(ad - fd) / max(abs(fd), abs(ad), 1e-8)
            assert err < rtol, (tag, g, ix, ad, fd, err)
"""


def test_grad_vs_fd_across_exchange_depths():
    """Central-FD gradient checks on the 4-device mesh across the
    exchange-depth grid: per-step exchanges (1,1), device-level time
    skewing (2,1), and inner temporal blocking (1,2) — each with a
    fuse window that exercises both the fori_loop group path and an
    unrolled remainder group."""
    _run_in_subprocess(PRELUDE + """
for ts, tb in ((1, 1), (2, 1), (1, 2)):
    inner = st.pallas(time_block=tb) if tb > 1 else st.xla()
    be = st.distributed(grid_axes=("data", None), time_steps=ts,
                        inner=inner)
    arrays, halos = make_arrays()
    eng = engine(be, halos)
    fn = adjoint.differentiable_run(eng, 5)   # fuse 3 -> windows (3, 2)
    check_fd(fn, arrays, {}, f"depth {ts}x{tb}")
    print("OK fd", ts, "x", tb)
""")


def test_matches_single_device_adjoint():
    """Primal interiors bit-for-bit (per-step exchange schedule) and
    gradients to machine precision against the single-device xla adjoint
    on the same problem.  Depth-2 time skewing recomputes boundary shells
    redundantly — a different XLA fusion schedule whose last-bit
    reassociation may differ — so it is pinned at 1-ulp instead."""
    _run_in_subprocess(PRELUDE + """
arrays, halos = make_arrays()
eng_x = tl.TimeloopEngine(K.ir, halos, SHAPE, st.xla(), swap=SWAP,
                          differentiable=True)
fn_x = adjoint.differentiable_run(eng_x, 6, fuse_steps=2)
ix = idx()
out_x = fn_x(arrays, {})
g_x = jax.grad(interior_loss(fn_x, {}))(arrays)

for ts in (1, 2):
    be = st.distributed(grid_axes=("data", None), time_steps=ts)
    fn_d = adjoint.differentiable_run(engine(be, halos), 6, fuse_steps=2)
    out_d = fn_d(arrays, {})
    for g in K.ir.grid_params:
        a, b = np.asarray(out_d[g][ix]), np.asarray(out_x[g][ix])
        if ts == 1:
            assert np.array_equal(a, b), g      # bit-for-bit
        else:
            np.testing.assert_allclose(a, b, rtol=1e-14, atol=1e-15,
                                       err_msg=g)
    print("OK primal", "bit-exact" if ts == 1 else "1-ulp", "ts", ts)

    g_d = jax.grad(interior_loss(fn_d, {}))(arrays)
    for g in K.ir.grid_params:
        np.testing.assert_allclose(np.asarray(g_d[g][ix]),
                                   np.asarray(g_x[g][ix]),
                                   rtol=1e-9, atol=1e-12, err_msg=g)
    print("OK grads match single-device ts", ts)
""")


def test_sharded_coefficient_and_scalar_grads_batched():
    """The FWI surface under sharding: gradients reach a sharded
    coefficient grid (velocity-model analogue) and per-scenario scalars
    of a batched distributed engine, matching the batched xla adjoint;
    per-scenario gradients stay isolated."""
    _run_in_subprocess("""
import jax
from repro.launch.mesh import make_mesh
jax.config.update("jax_enable_x64", True)
import numpy as np, jax.numpy as jnp
from repro.core import adjoint, dsl as st
from repro.core import timeloop as tl

MESH = make_mesh((4,), ("data",))

@st.kernel
def heat(u: st.grid, v: st.grid, c: st.grid, a: st.f32):
    v.at(0, 0).set(u.at(0, 0) + a * c.at(0, 0) * (
        u.at(-1, 0) + u.at(1, 0) + u.at(0, -1) + u.at(0, 1)
        - 4.0 * u.at(0, 0)))

B, SHAPE, STEPS = 2, (16, 10), 4
grids = {g: st.grid(dtype=jnp.float64, shape=SHAPE, order=1,
                    batch=B).randomize(i)
         for i, g in enumerate(("u", "v", "c"))}
halos = {n: g.halo for n, g in grids.items()}
arrays = {n: jnp.asarray(g.data) for n, g in grids.items()}
scal = {"a": jnp.asarray([0.1, 0.15])}          # per-scenario scalar
ix = (slice(None),) + tuple(slice(1, 1 + s) for s in SHAPE)

def build(backend, mesh):
    eng = tl.TimeloopEngine(heat.ir, halos, SHAPE, backend, swap=("v", "u"),
                            mesh=mesh, batch=B, differentiable=True)
    return adjoint.differentiable_run(eng, STEPS, fuse_steps=2)

fn_d = build(st.distributed(grid_axes=("data", None)), MESH)
fn_x = build(st.xla(), None)

def loss_of(fn):
    return lambda a_, s_: jnp.sum(fn(a_, s_)["v"][ix] ** 2)

ga_d, gs_d = jax.grad(loss_of(fn_d), argnums=(0, 1))(arrays, scal)
ga_x, gs_x = jax.grad(loss_of(fn_x), argnums=(0, 1))(arrays, scal)
for g in arrays:
    np.testing.assert_allclose(np.asarray(ga_d[g][ix]),
                               np.asarray(ga_x[g][ix]),
                               rtol=1e-9, atol=1e-12, err_msg=g)
assert float(jnp.linalg.norm(ga_d["c"][ix])) > 0   # velocity grid gets grad
np.testing.assert_allclose(np.asarray(gs_d["a"]), np.asarray(gs_x["a"]),
                           rtol=1e-9)
assert np.asarray(gs_d["a"]).shape == (B,)          # per-scenario
print("OK sharded coeff+scalar grads")

# per-scenario isolation: a loss over scenario 1 only leaves scenario 0
# gradients exactly zero
g1 = jax.grad(lambda a_: jnp.sum(fn_d(a_, scal)["v"][1][1:-1, 1:-1] ** 2))(
    arrays)
assert float(jnp.linalg.norm(g1["u"][0])) == 0.0
assert float(jnp.linalg.norm(g1["u"][1])) > 0.0
print("OK per-scenario isolation")
""")


def test_masked_freeze_under_sharding():
    """Masked serving windows under sharding: the distributed masked
    adjoint freezes masked cells and budget-exhausted scenarios exactly
    like the batched xla masked adjoint."""
    _run_in_subprocess(PRELUDE + """
B, STEPS = 2, 4
arrays, halos = make_arrays(batch=B)
mask = np.ones((B,) + SHAPE, bool)
mask[1, :, 6:] = False                  # scenario 1: right half frozen
limits = np.asarray([STEPS, 2], np.int32)   # scenario 1 stops at step 2

be = st.distributed(grid_axes=("data", None))
fn_d = adjoint.differentiable_run(engine(be, halos, batch=B), STEPS,
                                  fuse_steps=2,
                                  domain_mask=jnp.asarray(mask),
                                  step_limits=jnp.asarray(limits))
eng_x = tl.TimeloopEngine(K.ir, halos, SHAPE, st.xla(), swap=SWAP,
                          batch=B, differentiable=True)
fn_x = adjoint.differentiable_run(eng_x, STEPS, fuse_steps=2,
                                  domain_mask=jnp.asarray(mask),
                                  step_limits=jnp.asarray(limits))

ix = idx(batch=B)
out_d, out_x = fn_d(arrays, {}), fn_x(arrays, {})
for g in K.ir.grid_params:
    assert np.array_equal(np.asarray(out_d[g][ix]),
                          np.asarray(out_x[g][ix])), g
g_d = jax.grad(interior_loss(fn_d, {}, batch=B))(arrays)
g_x = jax.grad(interior_loss(fn_x, {}, batch=B))(arrays)
for g in K.ir.grid_params:
    np.testing.assert_allclose(np.asarray(g_d[g][ix]),
                               np.asarray(g_x[g][ix]),
                               rtol=1e-9, atol=1e-12, err_msg=g)
print("OK masked adjoint matches xla")

# a frozen cell deep inside the masked half passes through untouched, so
# its gradient is exactly 2*value (identity through every window)
out = fn_d(arrays, {})
frozen = (1, O + 4, O + 8)
for g in SWAP:
    np.testing.assert_allclose(
        float(np.asarray(g_d[g])[frozen]),
        2.0 * float(np.asarray(out[g])[frozen]), rtol=1e-12)
print("OK frozen-cell identity")

check_fd(fn_d, arrays, {}, "masked", batch=B, n_probes=1)
print("OK masked fd")
""")


def test_resume_mid_backward_resilience(tmp_path):
    """A distributed backward pass killed mid-segment resumes from the
    on-disk snapshot and produces the same value and gradients — and the
    uninterrupted resilient run equals the plain in-memory adjoint."""
    _run_in_subprocess(PRELUDE + f"""
from repro.train.fault_tolerance import FailureInjector

STEPS, FUSE = 6, 2          # W=3 windows, stride 1 -> 3 backward segments
be = st.distributed(grid_axes=("data", None), time_steps=2)
arrays, halos = make_arrays()
ix = idx()

def loss(arrs):
    return jnp.sum(arrs["v"][ix] ** 2)

ref = tl.run_resilient(engine(be, halos), dict(arrays), {{}}, STEPS, FUSE,
                       ckpt_dir={str(tmp_path / 'ok')!r}, loss=loss)

# unit 5 is the second backward segment (units: 0-2 fwd, 3 seed, 4-6 bwd)
got = tl.run_resilient(engine(be, halos), dict(arrays), {{}}, STEPS, FUSE,
                       ckpt_dir={str(tmp_path / 'fail')!r}, loss=loss,
                       injector=FailureInjector([5]))

assert np.array_equal(np.asarray(ref["value"]), np.asarray(got["value"]))
for g in ref["grad_arrays"]:
    assert np.array_equal(np.asarray(ref["grad_arrays"][g]),
                          np.asarray(got["grad_arrays"][g])), g
print("OK resume-mid-backward bit-exact")

# the uninterrupted resilient gradient equals the in-memory adjoint
fn = adjoint.differentiable_run(engine(be, halos), STEPS, fuse_steps=FUSE)
want_v, want_g = jax.value_and_grad(lambda a: loss(fn(a, {{}})))(arrays)
assert float(want_v) == float(ref["value"])
for g in want_g:
    np.testing.assert_allclose(np.asarray(ref["grad_arrays"][g]),
                               np.asarray(want_g[g]), rtol=1e-12, atol=0)
print("OK resilient == in-memory adjoint")
""")
