"""Batched (vmapped scenario) timeloop: B-scenario runs must equal B
independent serial runs — across backends/templates × temporal depths ×
2D/3D — including per-scenario scalar parameters, hook cadence, and the
masked serving windows (spatial sub-domain freeze + per-scenario step
budgets)."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import dsl as st, suite
from repro.core.timeloop import TimeloopEngine

B = 3
STEPS = 6


def _inits(k, shape, seed=0):
    rng = np.random.default_rng(seed)
    return {g: rng.standard_normal((B,) + shape).astype(np.float32)
            for g in k.ir.grid_params}


def _serial(k, shape, inits, backend, time_block=1, fuse=None, steps=STEPS,
            scalars=()):
    outs = []
    for b in range(B):
        gs = {g: st.grid(st.f32, shape, k.info.order)
              for g in k.ir.grid_params}
        for g in gs:
            gs[g].interior = inits[g][b]
        args = [gs[g] for g in k.ir.grid_params] + [s[b] for s in scalars]

        def run():
            st.timeloop(steps, swap=suite.swap_pair(k.name)
                        if not scalars else ("v", "u"),
                        fuse_steps=fuse)(k)(*args)
        st.launch(backend=backend, time_block=time_block)(run)()
        outs.append({g: np.asarray(gs[g].interior) for g in gs})
    return outs


def _batched(k, shape, inits, backend, time_block=1, fuse=None, steps=STEPS,
             scalars=()):
    gs = {g: st.grid(st.f32, shape, k.info.order, batch=B)
          for g in k.ir.grid_params}
    for g in gs:
        gs[g].interior = inits[g]
    args = [gs[g] for g in k.ir.grid_params] + [jnp.asarray(s) for s in scalars]

    def run():
        st.timeloop(steps, swap=suite.swap_pair(k.name)
                    if not scalars else ("v", "u"),
                    fuse_steps=fuse, batch=B)(k)(*args)
    st.launch(backend=backend, time_block=time_block)(run)()
    return {g: np.asarray(gs[g].interior) for g in gs}


def _assert_equal(bat, ser, label):
    for g in bat:
        for b in range(B):
            np.testing.assert_allclose(
                bat[g][b], ser[b][g], rtol=1e-5, atol=1e-6,
                err_msg=f"{label} {g} scenario={b}")


# ---- equivalence: templates × temporal depth × dimensionality --------------
@pytest.mark.parametrize("time_block", (1, 4))
@pytest.mark.parametrize("template", ("gmem", "smem", "shift"))
def test_batched_matches_serial_pallas_2d(template, time_block):
    k = suite.get_kernel("star2d1r")
    shape = (12, 18)
    inits = _inits(k, shape)
    be = st.pallas(template=template)
    ser = _serial(k, shape, inits, be, time_block)
    bat = _batched(k, shape, inits, be, time_block)
    _assert_equal(bat, ser, f"{template}/k={time_block}")


@pytest.mark.parametrize("time_block", (1, 4))
def test_batched_matches_serial_pallas_3d(time_block):
    k = suite.get_kernel("star3d1r")
    shape = (6, 8, 10)
    inits = _inits(k, shape)
    be = st.pallas(template="gmem")
    ser = _serial(k, shape, inits, be, time_block, steps=4)
    bat = _batched(k, shape, inits, be, time_block, steps=4)
    _assert_equal(bat, ser, f"3d/k={time_block}")


@pytest.mark.parametrize("shape,name", [((12, 18), "star2d1r"),
                                        ((6, 8, 10), "star3d1r")])
def test_batched_matches_serial_xla(shape, name):
    k = suite.get_kernel(name)
    inits = _inits(k, shape)
    ser = _serial(k, shape, inits, st.xla(), fuse=2)
    bat = _batched(k, shape, inits, st.xla(), fuse=2)
    _assert_equal(bat, ser, f"xla/{name}")


# ---- per-scenario scalar parameters ----------------------------------------
@st.kernel
def _damped(u: st.grid, v: st.grid, a: st.f32):
    v.at(0, 0).set(a * u.at(0, 0)
                   + 0.1 * (u.at(-1, 0) + u.at(1, 0)
                            + u.at(0, -1) + u.at(0, 1)))


def test_batched_per_scenario_scalars():
    """(B,) scalar args give each scenario its own parameter value."""
    shape = (10, 14)
    inits = _inits(_damped, shape)
    a = np.array([0.3, 0.5, 0.7], np.float32)
    ser = _serial(_damped, shape, inits, st.xla(), scalars=(a,))
    bat = _batched(_damped, shape, inits, st.xla(), scalars=(a,))
    _assert_equal(bat, ser, "per-scenario scalar")
    # distinct parameters must produce distinct fields
    assert not np.allclose(bat["v"][0], bat["v"][1])


def test_batched_broadcast_scalar():
    """A python float is shared across scenarios."""
    shape = (10, 14)
    inits = _inits(_damped, shape)
    a = np.array([0.5, 0.5, 0.5], np.float32)
    ser = _serial(_damped, shape, inits, st.xla(), scalars=(a,))

    gs = {g: st.grid(st.f32, shape, 1, batch=B) for g in ("u", "v")}
    for g in gs:
        gs[g].interior = inits[g]
    st.launch(backend=st.xla())(lambda: st.timeloop(
        STEPS, swap=("v", "u"), batch=B)(_damped)(gs["u"], gs["v"], 0.5))()
    bat = {g: np.asarray(gs[g].interior) for g in gs}
    _assert_equal(bat, ser, "broadcast scalar")


# ---- hook cadence ----------------------------------------------------------
def test_batched_between_hook_cadence():
    """The between hook fires at exactly the window boundaries and sees
    the batched grids; injecting per-scenario sources stays equivalent to
    serial runs doing the same."""
    k = suite.get_kernel("star2d1r")
    shape = (10, 12)
    inits = _inits(k, shape)
    hits = []

    def mk_between(amps):
        def between(t, grids):
            hits.append(t)
            u = grids["u"]
            inj = np.zeros(u.interior.shape, np.float32)
            inj[..., 4, 5] = amps if np.ndim(amps) else float(amps)
            u.interior = u.interior + inj
        return between

    amps = np.array([1.0, 2.0, 3.0], np.float32)
    gs = {g: st.grid(st.f32, shape, k.info.order, batch=B)
          for g in k.ir.grid_params}
    for g in gs:
        gs[g].interior = inits[g]
    st.launch(backend=st.xla())(lambda: st.timeloop(
        STEPS, swap=("v", "u"), fuse_steps=2, batch=B,
        between=mk_between(amps))(k)(gs["u"], gs["v"]))()
    assert hits == [2, 4]      # every fuse window boundary except the last
    bat = {g: np.asarray(gs[g].interior) for g in gs}

    ser = []
    for b in range(B):
        hits.clear()
        g1 = {g: st.grid(st.f32, shape, k.info.order)
              for g in k.ir.grid_params}
        for g in g1:
            g1[g].interior = inits[g][b]
        st.launch(backend=st.xla())(lambda: st.timeloop(
            STEPS, swap=("v", "u"), fuse_steps=2,
            between=mk_between(amps[b]))(k)(g1["u"], g1["v"]))()
        assert hits == [2, 4]
        ser.append({g: np.asarray(g1[g].interior) for g in g1})
    _assert_equal(bat, ser, "between hook")


# ---- masked serving windows ------------------------------------------------
def _engine(k, shape, backend=None, batch=B):
    halos = {g: (k.info.order,) * k.info.ndim for g in k.ir.grid_params}
    return TimeloopEngine(k.ir, halos, shape, backend or st.xla(),
                          swap=suite.swap_pair(k.name), batch=batch)


def test_masked_step_limits_and_subdomain():
    """One wave: full-domain scenario, early-stopping scenario, and an
    embedded smaller sub-domain — each equals its serial reference."""
    k = suite.get_kernel("star2d1r")
    shape, sub, order = (12, 18), (8, 10), k.info.order
    inits = _inits(k, shape)
    eng = _engine(k, shape)
    arrays = {}
    for g in k.ir.grid_params:
        full = np.zeros((B,) + tuple(s + 2 * order for s in shape),
                        np.float32)
        full[:2, order:order + shape[0], order:order + shape[1]] = \
            inits[g][:2]
        # scenario 2: zero outside the sub-domain = the small grid's halos
        full[2, order:order + sub[0], order:order + sub[1]] = \
            inits[g][2][:sub[0], :sub[1]]
        arrays[g] = jnp.asarray(full)
    mask = np.zeros((B,) + shape, bool)
    mask[0] = mask[1] = True
    mask[2, :sub[0], :sub[1]] = True
    limits = np.array([STEPS, 2, STEPS], np.int32)
    out = eng.run(arrays, {}, STEPS, 3, domain_mask=jnp.asarray(mask),
                  step_limits=jnp.asarray(limits))

    def ref(b, steps, shp):
        gs = {g: st.grid(st.f32, shp, order) for g in k.ir.grid_params}
        for g in gs:
            gs[g].interior = inits[g][b][tuple(slice(0, e) for e in shp)]
        if steps:
            st.launch(backend=st.xla())(lambda: st.timeloop(
                steps, swap=("v", "u"))(k)(gs["u"], gs["v"]))()
        return {g: np.asarray(gs[g].interior) for g in gs}

    for b, steps, shp in [(0, STEPS, shape), (1, 2, shape), (2, STEPS, sub)]:
        want = ref(b, steps, shp)
        for g in k.ir.grid_params:
            idx = (b,) + tuple(slice(order, order + e) for e in shp)
            np.testing.assert_allclose(
                np.asarray(out[g][idx]), want[g], rtol=1e-5, atol=1e-6,
                err_msg=f"masked scenario={b} {g}")


def test_masked_frozen_cells_keep_values():
    """Cells outside every mask stay bit-identical to their inputs."""
    k = suite.get_kernel("star2d1r")
    shape, order = (8, 8), k.info.order
    inits = _inits(k, shape)
    eng = _engine(k, shape)
    arrays = {g: jnp.asarray(np.pad(inits[g],
                                    [(0, 0), (order, order), (order, order)]))
              for g in k.ir.grid_params}
    mask = np.zeros((B,) + shape, bool)
    mask[:, :4, :4] = True
    out = eng.run(arrays, {}, 4, domain_mask=jnp.asarray(mask))
    for g in k.ir.grid_params:
        got = np.asarray(out[g][:, order:order + 8, order:order + 8])
        np.testing.assert_array_equal(got[:, 6:, 6:], inits[g][:, 6:, 6:])


# ---- validation ------------------------------------------------------------
def test_grid_batch_views():
    g = st.grid(st.f32, (4, 6), order=2, batch=5).randomize(1)
    assert g.data.shape == (5, 8, 10)
    assert g.interior.shape == (5, 4, 6)
    assert "batch=5" in repr(g)
    c = g.copy()
    assert c.batch == 5 and c.data.shape == g.data.shape
    # distinct scenarios get distinct random fields
    assert not np.allclose(np.asarray(g.interior[0]),
                           np.asarray(g.interior[1]))


def test_batch_mismatch_raises():
    k = suite.get_kernel("star2d1r")
    u = st.grid(st.f32, (8, 8), 1, batch=2)
    v = st.grid(st.f32, (8, 8), 1, batch=3)
    with pytest.raises(ValueError, match="batch"):
        st.timeloop(2, swap=("v", "u"), batch=2)(k)(u, v)
    v2 = st.grid(st.f32, (8, 8), 1)
    with pytest.raises(ValueError, match="batch"):
        st.timeloop(2, swap=("v", "u"), batch=2)(k)(u, v2)


def test_map_rejects_batched_grids():
    k = suite.get_kernel("star2d1r")
    u = st.grid(st.f32, (8, 8), 1, batch=2)
    v = st.grid(st.f32, (8, 8), 1, batch=2)
    with pytest.raises(ValueError, match="batched"):
        st.map(e=u.shape)(k)(u, v)


def test_masked_requires_batched_xla():
    k = suite.get_kernel("star2d1r")
    eng = _engine(k, (8, 8), batch=0)
    arrays = {g: jnp.zeros((10, 10)) for g in k.ir.grid_params}
    with pytest.raises(ValueError, match="batched xla"):
        eng.run(arrays, {}, 2, step_limits=jnp.array([1]))
    peng = _engine(k, (8, 8), backend=st.pallas(template="gmem"))
    parrs = {g: jnp.zeros((B, 10, 10)) for g in k.ir.grid_params}
    with pytest.raises(ValueError, match="batched xla"):
        peng.run(parrs, {}, 2,
                 domain_mask=jnp.ones((B, 8, 8), bool))


@pytest.mark.parametrize("time_steps,fuse", [(1, 2), (2, 6)])
def test_distributed_batched_matches_serial(time_steps, fuse):
    """Batched grids thread through the fused sharded timeloop: the batch
    axis rides unsharded ahead of the mesh-decomposed grid axes (a vmap
    inside the single shard_mapped program), so B scenarios on a mesh must
    equal B independent runs.  Single-device mesh — the multi-device
    variant lives in test_distributed.py's subprocess harness."""
    import jax
    from repro.launch.mesh import make_mesh
    k = suite.get_kernel("star2d1r")
    shape = (12, 18)
    inits = _inits(k, shape)
    mesh = make_mesh((1,), ("data",))
    be = st.distributed(grid_axes=("data", None), time_steps=time_steps)

    ser = _serial(k, shape, inits, st.xla(), fuse=fuse)

    gs = {g: st.grid(st.f32, shape, k.info.order, batch=B)
          for g in k.ir.grid_params}
    for g in gs:
        gs[g].interior = inits[g]

    def run():
        st.timeloop(STEPS, swap=suite.swap_pair(k.name),
                    fuse_steps=fuse, batch=B)(k)(*gs.values())
    st.launch(backend=be, mesh=mesh)(run)()
    bat = {g: np.asarray(gs[g].interior) for g in gs}
    _assert_equal(bat, ser, f"dist/ts={time_steps}/fuse={fuse}")
