"""Differentiable timeloop tests: gradient-vs-central-finite-difference
across the template suite (xla + pallas interpret, time_block 1 and 4),
per-scenario batched gradients, masked-window adjoint freezes, the O(√T)
checkpoint bound, primal equivalence with the forward engine, and the
donation-under-AD regression (``_donate_ok``)."""
import math

import jax
from repro.launch.mesh import make_mesh
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import adjoint, dsl as st, suite
from repro.core import timeloop as tl

TEMPLATES = ("gmem", "smem", "f4", "shift", "unroll", "semi")
# shape indivisible by the default block axes (same as test_timeloop's
# temporal-blocking geometry) so block-overhang masks are in play
SHAPE = (13, 21)
NAME = "star2d2r"


def _engine(backend, dtype=jnp.float64, shape=SHAPE, name=NAME, batch=0):
    k = suite.get_kernel(name)
    grids = {g: st.grid(dtype=dtype, shape=shape, order=k.info.order,
                        batch=batch or None).randomize(i)
             for i, g in enumerate(k.ir.grid_params)}
    arrays = {n: jnp.asarray(g.data, dtype) for n, g in grids.items()}
    eng = tl.TimeloopEngine(k.ir, {n: g.halo for n, g in grids.items()},
                            shape, backend, swap=suite.swap_pair(name),
                            batch=batch, differentiable=True)
    return eng, arrays


def _check_grad_vs_fd(fn, arrays, scal, tag, n_probes=2, eps=1e-6,
                      rtol=1e-3):
    """Central-FD check of d(sum of squares of outputs)/d(arrays) at a few
    randomly chosen input cells per grid (f64)."""
    def loss(arrs):
        out = fn(arrs, scal)
        return sum(jnp.sum(o ** 2) for o in out.values())

    grad = jax.grad(loss)(arrays)
    rng = np.random.default_rng(7)
    for g, a in arrays.items():
        a = np.asarray(a)
        for _ in range(n_probes):
            idx = tuple(int(rng.integers(0, s)) for s in a.shape)
            ap, am = a.copy(), a.copy()
            ap[idx] += eps
            am[idx] -= eps
            fd = (float(loss({**arrays, g: jnp.asarray(ap)}))
                  - float(loss({**arrays, g: jnp.asarray(am)}))) / (2 * eps)
            ad = float(np.asarray(grad[g])[idx])
            err = abs(ad - fd) / max(abs(fd), abs(ad), 1e-8)
            assert err < rtol, (f"{tag}/{g}{idx}: AD {ad} vs FD {fd} "
                                f"(rel err {err:.2e})")


# ---- gradient == finite differences: xla ----------------------------------
@pytest.mark.parametrize("fuse", (1, 4, None))
def test_grad_vs_fd_xla(fuse):
    with jax.enable_x64():
        eng, arrays = _engine(st.xla())
        fn = adjoint.differentiable_run(eng, 5, fuse_steps=fuse)
        _check_grad_vs_fd(fn, arrays, {}, f"xla/fuse={fuse}")


# ---- gradient == finite differences: every pallas template × time_block ---
@pytest.mark.parametrize("template", TEMPLATES)
@pytest.mark.parametrize("time_block", (1, 4))
def test_grad_vs_fd_pallas_templates(template, time_block):
    with jax.enable_x64():
        backend = st.pallas(template=template, interpret=True,
                            time_block=time_block)
        eng, arrays = _engine(backend)
        # fuse 5 at time_block=4: one 4-deep blocked group + a single-step
        # remainder inside the window, both on the adjoint's replay path
        fn = adjoint.differentiable_run(
            eng, 5, fuse_steps=5 if time_block == 4 else None)
        _check_grad_vs_fd(fn, arrays, {},
                          f"pallas/{template}/tb={time_block}", n_probes=1)


# ---- scalar + coefficient-grid gradients (the FWI surface) ----------------
def test_grad_flows_to_scalars_and_coefficient_grid():
    with jax.enable_x64():
        @st.kernel
        def heat(u: st.grid, v: st.grid, c: st.grid, a: st.f32):
            v.at(0, 0).set(u.at(0, 0) + a * c.at(0, 0) * (
                u.at(-1, 0) + u.at(1, 0) + u.at(0, -1) + u.at(0, 1)
                - 4.0 * u.at(0, 0)))

        shape = (8, 9)
        grids = {g: st.grid(dtype=jnp.float64, shape=shape,
                            order=1).randomize(i)
                 for i, g in enumerate(("u", "v", "c"))}
        eng = tl.TimeloopEngine(heat.ir,
                                {n: g.halo for n, g in grids.items()},
                                shape, st.xla(), swap=("v", "u"),
                                differentiable=True)
        fn = adjoint.differentiable_run(eng, 6)
        arrays = {n: jnp.asarray(g.data) for n, g in grids.items()}
        scal = {"a": jnp.float64(0.1)}

        def loss(arrs, s):
            return jnp.sum(fn(arrs, s)["v"] ** 2)

        g_arr, g_scal = jax.grad(loss, argnums=(0, 1))(arrays, scal)
        # coefficient grid (velocity-model analogue) gets a real gradient
        assert float(jnp.linalg.norm(g_arr["c"])) > 0
        # scalar gradient matches central FD
        eps = 1e-6
        fd = (float(loss(arrays, {"a": jnp.float64(0.1 + eps)}))
              - float(loss(arrays, {"a": jnp.float64(0.1 - eps)}))) \
            / (2 * eps)
        ad = float(g_scal["a"])
        assert abs(ad - fd) / max(abs(fd), 1e-8) < 1e-3, (ad, fd)


# ---- batched scenarios differentiate per-scenario -------------------------
def test_batched_grads_are_per_scenario():
    with jax.enable_x64():
        B = 3
        eng, arrays = _engine(st.xla(), shape=(8, 10), batch=B)
        fn = adjoint.differentiable_run(eng, 4)

        def loss(arrs):
            return jnp.sum(fn(arrs, {})["v"][1] ** 2)  # scenario 1 only

        scal = {}
        g = jax.grad(loss)(arrays)
        norms = [float(jnp.linalg.norm(g["u"][i])) for i in range(B)]
        assert norms[1] > 0
        assert norms[0] == 0 and norms[2] == 0
        _check_grad_vs_fd(fn, arrays, scal, "batched", n_probes=2)


# ---- masked windows: adjoint freezes masked cells and exhausted steps -----
def test_masked_window_grads_freeze_masked_cells():
    with jax.enable_x64():
        B = 2
        shape = (8, 10)
        eng, arrays = _engine(st.xla(), shape=shape, batch=B)
        mask = np.ones((B,) + shape, bool)
        mask[1, :, 5:] = False                 # scenario 1: right half frozen
        limits = np.array([4, 2], np.int32)    # scenario 1 stops at step 2
        fn = adjoint.differentiable_run(eng, 4, domain_mask=jnp.asarray(mask),
                                        step_limits=jnp.asarray(limits))
        _check_grad_vs_fd(fn, arrays, {}, "masked", n_probes=2)

        # a frozen interior cell's value passes straight through: its
        # cotangent is exactly the output cotangent (identity), and it gets
        # no contribution from neighbours (taps never propagate INTO the
        # frozen region's interior beyond the halo depth)
        def loss(arrs):
            out = fn(arrs, {})
            return jnp.sum(out["u"][1] ** 2) + jnp.sum(out["v"][1] ** 2)

        g = jax.grad(loss)(arrays)
        out = fn(arrays, {})
        o = eng.halos["u"][0]
        # deep inside the frozen half (beyond tap reach of active cells)
        frozen = (1, o + 4, o + 8)
        for gr in ("u", "v"):
            np.testing.assert_allclose(
                float(np.asarray(g[gr])[frozen]),
                2.0 * float(np.asarray(out[gr])[frozen]), rtol=1e-12)


# ---- O(√T) checkpoint bound ----------------------------------------------
@pytest.mark.parametrize("steps", (7, 16, 36, 100))
def test_checkpoint_count_is_sqrt_bounded(steps):
    eng, arrays = _engine(st.xla(), dtype=jnp.float32, shape=(6, 8))
    bound = adjoint.ceil_sqrt(steps) + 1
    # default schedule and a forced fine window cadence both stay √T
    for fuse in (None, 1):
        fn = adjoint.differentiable_run(eng, steps, fuse_steps=fuse)
        assert fn.schedule["checkpoints"] <= bound, fn.schedule
        adjoint.reset_stats()
        jax.grad(lambda a: jnp.sum(fn(a, {})["v"] ** 2))(arrays)
        assert adjoint.CHECKPOINT_STATS["checkpoints"] <= bound
        # backward touched every window exactly once
        assert (adjoint.CHECKPOINT_STATS["vjp_windows"]
                == len(fn.schedule["windows"]))


def test_ceil_sqrt_and_schedule_helpers():
    for n in (0, 1, 2, 3, 4, 8, 9, 15, 16, 17, 100):
        assert adjoint.ceil_sqrt(n) == int(math.ceil(math.sqrt(n)))
    sizes, starts = adjoint.window_schedule(10, 4)
    assert sizes == (4, 4, 2) and starts == (0, 4, 8)
    # stride thins T windows back to ~√T checkpoints
    assert adjoint.checkpoint_stride(100, 100) == 10
    assert adjoint.checkpoint_stride(10, 100) == 1


# ---- primal equivalence with the forward engine ---------------------------
@pytest.mark.parametrize("backend", (st.xla(),
                                     st.pallas(template="gmem",
                                               interpret=True)))
def test_primal_matches_engine_run(backend):
    eng, arrays = _engine(backend, dtype=jnp.float32, shape=(9, 11))
    fn = adjoint.differentiable_run(eng, 5, fuse_steps=2)
    want = eng.run(dict(arrays), {}, 5, fuse_steps=2)
    got = fn(arrays, {})
    for g in arrays:
        np.testing.assert_array_equal(np.asarray(got[g]),
                                      np.asarray(want[g]), err_msg=g)


def test_between_hook_is_differentiated():
    with jax.enable_x64():
        eng, arrays = _engine(st.xla(), shape=(6, 8))

        def between(t, arrs):
            out = dict(arrs)
            out["u"] = out["u"] * 1.01       # pure, traceable
            return out

        fn = adjoint.differentiable_run(eng, 5, fuse_steps=1,
                                        between=between)
        _check_grad_vs_fd(fn, arrays, {}, "between", n_probes=1)


# ---- guard rails ----------------------------------------------------------
def test_requires_differentiable_engine():
    eng, _ = _engine(st.xla(), dtype=jnp.float32, shape=(6, 8))
    eng.differentiable = False
    with pytest.raises(ValueError, match="differentiable=True"):
        adjoint.differentiable_run(eng, 4)


def test_masked_requires_batched_xla():
    eng, _ = _engine(st.xla(), dtype=jnp.float32, shape=(6, 8))
    with pytest.raises(ValueError, match="batched xla"):
        adjoint.differentiable_run(eng, 4, domain_mask=np.ones((6, 8), bool))


# ---- DSL entry point ------------------------------------------------------
def test_dsl_differentiable_timeloop_jits_and_matches_timeloop():
    k = suite.get_kernel("star2d1r")
    grids = {g: st.grid(dtype=st.f32, shape=(10, 12), order=1).randomize(i)
             for i, g in enumerate(k.ir.grid_params)}
    fn = st.differentiable_timeloop(k, grids["u"], grids["v"], steps=6,
                                    swap=("v", "u"))
    ref_grids = {n: g.copy() for n, g in grids.items()}
    st.launch(backend=st.xla())(
        lambda u, v: st.timeloop(6, swap=("v", "u"))(k)(u, v))(
        ref_grids["u"], ref_grids["v"])
    out = fn()
    for g in grids:
        np.testing.assert_allclose(np.asarray(out[g]),
                                   np.asarray(ref_grids[g].data), atol=1e-6)
    # grad is jittable end-to-end
    gfn = jax.jit(jax.grad(lambda a: jnp.sum(fn(a, {})["v"] ** 2)))
    g = gfn(fn.arrays)
    assert all(bool(jnp.isfinite(v).all()) for v in g.values())


def test_dsl_distributed_backend_differentiates():
    """The distributed backend is no longer forward-only: on a (1-device)
    mesh the DSL entry builds a shard_mapped adjoint whose primal and
    interior gradients match the single-device xla path.  Interiors only:
    the distributed carry convention keeps grid-halo cells fixed at zero
    (not differentiable inputs), while the full-buffer xla window also
    cotangents the halo ring.  (Real multi-device coverage lives in
    tests/test_distributed_adjoint.py.)"""
    k = suite.get_kernel("star2d1r")
    mesh = make_mesh((1,), ("data",))
    grids = {g: st.grid(dtype=st.f32, shape=(8, 8), order=1).randomize(i)
             for i, g in enumerate(k.ir.grid_params)}
    fn = st.differentiable_timeloop(
        k, grids["u"], grids["v"], steps=4, swap=("v", "u"),
        backend=st.distributed(grid_axes=("data", None)), mesh=mesh)

    ref_grids = {n: g.copy() for n, g in grids.items()}
    fn_ref = st.differentiable_timeloop(
        k, ref_grids["u"], ref_grids["v"], steps=4, swap=("v", "u"))

    ix = (slice(1, -1), slice(1, -1))

    def loss(f, a):
        return jnp.sum(f(a, {})["v"][ix] ** 2)

    out = fn(fn.arrays)
    want = fn_ref(fn_ref.arrays)
    for g in out:
        np.testing.assert_array_equal(np.asarray(out[g][ix]),
                                      np.asarray(want[g][ix]), err_msg=g)
    g_dist = jax.grad(lambda a: loss(fn, a))(fn.arrays)
    g_xla = jax.grad(lambda a: loss(fn_ref, a))(fn_ref.arrays)
    for g in g_dist:
        np.testing.assert_allclose(np.asarray(g_dist[g][ix]),
                                   np.asarray(g_xla[g][ix]),
                                   rtol=1e-5, atol=1e-6, err_msg=g)


def test_dsl_distributed_backend_requires_mesh():
    k = suite.get_kernel("star2d1r")
    grids = {g: st.grid(dtype=st.f32, shape=(8, 8), order=1).randomize(i)
             for i, g in enumerate(k.ir.grid_params)}
    fn = st.differentiable_timeloop(
        k, grids["u"], grids["v"], steps=4, swap=("v", "u"),
        backend=st.distributed(grid_axes=("data", None)))
    with pytest.raises(ValueError, match="mesh"):
        fn(fn.arrays)


# ---- donation gating under differentiation (regression) -------------------
def test_donate_ok_disabled_when_differentiable(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    assert tl._donate_ok() is True
    assert tl._donate_ok(differentiable=True) is False


def test_donate_ok_disabled_under_trace(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    seen = {}

    def probe(x):
        seen["donate"] = tl._donate_ok(arrays={"u": x})
        return x

    jax.make_jaxpr(probe)(jnp.zeros(1))
    assert seen["donate"] is False


def test_differentiable_engine_windows_do_not_donate(monkeypatch):
    # on a donating backend, a differentiable engine must still compile
    # its windows without donate_argnums — otherwise fwd-pass residual
    # buffers would be invalidated
    captured = {}
    real_jit = jax.jit

    def spy_jit(*a, **kw):
        captured["donate"] = kw.get("donate_argnums", ())
        return real_jit(*a, **kw)

    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    monkeypatch.setattr(jax, "jit", spy_jit)
    eng, arrays = _engine(st.xla(), dtype=jnp.float32, shape=(6, 8))
    eng.run(arrays, {}, 2)
    assert captured["donate"] == ()
