"""Fused time-loop engine tests: equivalence with per-step execution on
the accuracy suite (xla + pallas interpret), the one-pad-per-window layout
invariant, window-boundary hooks, and the fuse_steps autotuner knobs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autotune, dsl as st, suite
from repro.kernels.stencil import codegen, ops

STEPS = 5


def _mk_grids(name, seed=0):
    k = suite.get_kernel(name)
    shape = (16, 24) if k.info.ndim == 2 else (8, 10, 16)
    return {g: st.grid(dtype=st.f32, shape=shape,
                       order=k.info.order).randomize(seed + i)
            for i, g in enumerate(k.ir.grid_params)}


def _per_step_reference(name, steps=STEPS):
    """Per-step st.map loop with the name-rotation (data-swap) convention."""
    k = suite.get_kernel(name)
    grids = _mk_grids(name)

    def tgt(u, v):
        for _ in range(steps):
            st.map(e=u.shape)(k)(u, v)
            (u.data, v.data) = (v.data, u.data)

    st.launch(backend=st.xla())(tgt)(grids["u"], grids["v"])
    return {n: np.asarray(g.data) for n, g in grids.items()}


def _fused(name, backend, fuse, steps=STEPS):
    k = suite.get_kernel(name)
    grids = _mk_grids(name)
    st.launch(backend=backend)(
        lambda u, v: st.timeloop(steps, swap=suite.swap_pair(name),
                                 fuse_steps=fuse)(k)(u, v))(
        grids["u"], grids["v"])
    return {n: np.asarray(g.data) for n, g in grids.items()}


# ---- fused == per-step across the whole accuracy suite (xla) --------------
@pytest.mark.parametrize("name", suite.KERNEL_NAMES)
def test_fused_matches_per_step_xla_suite(name):
    want = _per_step_reference(name)
    for fuse in (1, 2, STEPS):
        got = _fused(name, st.xla(), fuse)
        for g in ("u", "v"):
            np.testing.assert_allclose(got[g], want[g], atol=1e-6,
                                       err_msg=f"{name}/xla/fuse={fuse}/{g}")


# ---- fused == per-step on pallas(interpret) templates ---------------------
@pytest.mark.parametrize("name", ("star2d2r", "box2d1r", "star3d2r",
                                  "box3d1r", "j2d5pt", "j3d27pt"))
@pytest.mark.parametrize("template", ("gmem", "shift"))
def test_fused_matches_per_step_pallas(name, template):
    want = _per_step_reference(name)
    got = _fused(name, st.pallas(template=template), fuse=STEPS)
    for g in ("u", "v"):
        np.testing.assert_allclose(got[g], want[g], atol=1e-6,
                                   err_msg=f"{name}/{template}/{g}")


@pytest.mark.parametrize("template", ("smem", "f4", "unroll", "semi"))
def test_fused_all_templates_star2d2r(template):
    want = _per_step_reference("star2d2r")
    got = _fused("star2d2r", st.pallas(template=template), fuse=2)
    for g in ("u", "v"):
        np.testing.assert_allclose(got[g], want[g], atol=1e-6,
                                   err_msg=f"star2d2r/{template}/{g}")


# ---- multi-statement kernel with scalars + coefficient grids --------------
def test_fused_acoustic_matches_per_step():
    from repro.core import acoustic
    shape = (12, 12, 16)
    ref, _ = acoustic.run(shape=shape, iters=6, with_source=False)
    for backend in (st.xla(), st.pallas(template="gmem")):
        got, _ = acoustic.run(shape=shape, iters=6, with_source=False,
                              backend=backend, fuse_steps=6)
        np.testing.assert_allclose(np.asarray(got.interior),
                                   np.asarray(ref.interior), atol=1e-6)


# ---- layout invariant: ONE halo pad per grid per fusion window ------------
def test_pallas_one_pad_per_grid_per_window():
    name = "star2d1r"
    k = suite.get_kernel(name)
    codegen.reset_pad_count()
    # 12 steps in windows of 4 → 3 windows; star kernels pad u and v
    _fused(name, st.pallas(template="gmem"), fuse=4, steps=12)
    assert codegen.PAD_COUNT["u"] == 3, dict(codegen.PAD_COUNT)
    assert codegen.PAD_COUNT["v"] == 3, dict(codegen.PAD_COUNT)
    assert codegen.PAD_COUNT["total"] == 6, dict(codegen.PAD_COUNT)
    codegen.reset_pad_count()


def test_fused_window_program_has_no_pad_ops():
    """The compiled fusion-window program itself must contain zero pad ops:
    the single layout pad per grid happens eagerly at the window boundary,
    and steps inside the window write in-place in padded layout."""
    k = suite.get_kernel("star2d1r")
    halos = {g: k.info.halo for g in k.ir.grid_params}
    interior = (16, 24)
    plan = codegen.plan_pallas(k.ir, halos, interior,
                               st.pallas(template="gmem"), swap=("v", "u"))
    rng = np.random.default_rng(0)
    arrays = {g: jnp.asarray(rng.standard_normal(
        tuple(s + 2 * h for s, h in zip(interior, halos[g]))), jnp.float32)
        for g in k.ir.grid_params}
    padded = plan.to_padded(arrays)

    def window(p):
        def body(_, q):
            out = plan.step(q, {})
            return dict(out, u=out["v"], v=out["u"])
        return jax.lax.fori_loop(0, 8, body, p)

    txt = jax.jit(window).lower(padded).as_text()
    assert txt.count(" pad(") == 0, "fused window repacks the layout"


def test_fused_operands_deduplicated():
    """Each padded grid is passed once per step, not once per neighbor
    delta: the fused pallas step takes one operand per grid (+ scalars)."""
    k = suite.get_kernel("box3d2r")        # box: 27 deltas in the legacy path
    halos = {g: k.info.halo for g in k.ir.grid_params}
    plan = codegen.plan_pallas(k.ir, halos, (8, 10, 16),
                               st.pallas(template="gmem"), swap=("v", "u"))
    assert len(plan.opnd_grids) == 2       # u (input) + v (output)


# ---- window-boundary hook -------------------------------------------------
def test_between_hook_runs_at_window_boundaries():
    k = suite.get_kernel("star2d1r")
    grids = _mk_grids("star2d1r")
    seen = []

    def hook(t, gs):
        seen.append(t)
        assert set(gs) == {"u", "v"}

    st.timeloop(10, swap=("v", "u"), fuse_steps=3, between=hook)(k)(
        grids["u"], grids["v"])
    assert seen == [3, 6, 9]               # not after the final window


def test_launch_fuse_steps_default_threads_to_timeloop():
    k = suite.get_kernel("star2d1r")
    grids = _mk_grids("star2d1r")
    res = st.launch(backend=st.xla(), fuse_steps=2)(
        lambda u, v: st.timeloop(6, swap=("v", "u"))(k)(u, v))(
        grids["u"], grids["v"])
    assert res.value.fuse_steps == 2
    assert res.value.windows == 3


# ---- array-level API ------------------------------------------------------
def test_stencil_timeloop_array_api():
    name = "star2d2r"
    k = suite.get_kernel(name)
    want = _per_step_reference(name)
    grids = _mk_grids(name)
    arrays = {n: g.data for n, g in grids.items()}
    got = ops.stencil_timeloop(k, arrays, STEPS, swap=("v", "u"),
                               template="gmem")
    for g in ("u", "v"):
        np.testing.assert_allclose(np.asarray(got[g]), want[g], atol=1e-6)


# ---- swap validation ------------------------------------------------------
def test_swap_must_contain_output_grid():
    k = suite.get_kernel("star2d1r")
    grids = _mk_grids("star2d1r")
    with pytest.raises(ValueError, match="output grid"):
        st.timeloop(2, swap=("u", "u"))(k)(grids["u"], grids["v"])


# ---- grid.randomize dtype fix ---------------------------------------------
def test_randomize_preserves_dtype():
    g = st.grid(dtype=st.bf16, shape=(8, 8), order=1).randomize(3)
    assert g.data.dtype == jnp.bfloat16
    assert g.interior.dtype == jnp.bfloat16
    # halo stays zero
    assert np.all(np.asarray(g.data, np.float32)[0] == 0)


# ---- in-kernel temporal blocking (time_block=k) ---------------------------
# shape chosen indivisible by every default block axis (8 and 128): blocks
# overhang the interior on both axes, exercising the valid-region masks
TB_SHAPE = (13, 21)


def _mk_grids_shape(name, shape, seed=0):
    k = suite.get_kernel(name)
    return {g: st.grid(dtype=st.f32, shape=shape,
                       order=k.info.order).randomize(seed + i)
            for i, g in enumerate(k.ir.grid_params)}


def _per_step_reference_shape(name, shape, steps=STEPS):
    k = suite.get_kernel(name)
    grids = _mk_grids_shape(name, shape)

    def tgt(u, v):
        for _ in range(steps):
            st.map(e=u.shape)(k)(u, v)
            (u.data, v.data) = (v.data, u.data)

    st.launch(backend=st.xla())(tgt)(grids["u"], grids["v"])
    return {n: np.asarray(g.data) for n, g in grids.items()}


@pytest.mark.parametrize("template", ("gmem", "smem", "f4", "shift",
                                      "unroll", "semi"))
@pytest.mark.parametrize("time_block", (1, 2, 4))
def test_time_block_matches_per_step_all_templates(template, time_block):
    """k steps per kernel invocation == k per-step applications, on a shape
    not divisible by the block, for every template; the outermost k·h cells
    (where the shrinking shells meet the grid halo) are checked explicitly."""
    name = "star2d2r"                      # h=2 → k·h=8 fits the 8-row block
    steps = 5                              # not a multiple of k: remainder
    want = _per_step_reference_shape(name, TB_SHAPE, steps)
    k = suite.get_kernel(name)
    grids = _mk_grids_shape(name, TB_SHAPE)
    st.launch(backend=st.pallas(template=template, time_block=time_block))(
        lambda u, v: st.timeloop(steps, swap=("v", "u"))(k)(u, v))(
        grids["u"], grids["v"])
    got = {n: np.asarray(g.data) for n, g in grids.items()}
    kh = time_block * k.info.order
    for g in ("u", "v"):
        np.testing.assert_allclose(
            got[g], want[g], atol=1e-6,
            err_msg=f"{name}/{template}/k={time_block}/{g}")
        # explicit boundary ring: outermost k·h interior cells on each side
        o = k.info.order
        for ax in range(2):
            for sl in (slice(o, o + kh), slice(-o - kh, -o or None)):
                idx = tuple(sl if a == ax else slice(None) for a in range(2))
                np.testing.assert_allclose(
                    got[g][idx], want[g][idx], atol=1e-6,
                    err_msg=f"{name}/{template}/k={time_block}/{g}/"
                            f"boundary ax{ax}")


@pytest.mark.parametrize("name", ("star2d2r", "box2d1r", "star3d2r",
                                  "box3d1r", "j2d5pt", "j3d27pt"))
def test_time_block4_matches_per_step_suite(name):
    """Acceptance: time_block=4 matches the per-step reference across the
    stencil suite (2D/3D, star/box/Jacobi)."""
    want = _per_step_reference(name)
    got = _fused(name, st.pallas(template="gmem", time_block=4), fuse=4)
    for g in ("u", "v"):
        np.testing.assert_allclose(got[g], want[g], atol=1e-6,
                                   err_msg=f"{name}/time_block=4/{g}")


@pytest.mark.parametrize("name,block", [("star3d2r", (8, 8, 64)),
                                        ("star3d2r", (16, 12, 128)),
                                        ("star2d2r", (12, 64))])
@pytest.mark.parametrize("time_block", (1, 2))
def test_unaligned_user_block_matches_per_step(name, block, time_block):
    """A user's block that is not a multiple of the (8, 128) tile keeps
    exact windows on the unaligned axes (no tile rounding there), so the
    fused path still matches the per-step reference."""
    want = _per_step_reference(name)
    got = _fused(name, st.pallas(template="gmem", block=block,
                                 time_block=time_block), fuse=STEPS)
    for g in ("u", "v"):
        np.testing.assert_allclose(got[g], want[g], atol=1e-6,
                                   err_msg=f"{name}/{block}/k={time_block}")


def test_time_block_acoustic_matches_per_step():
    """Multi-grid kernel (coefficient fields + scalar) through the temporal
    path."""
    from repro.core import acoustic
    shape = (12, 12, 16)
    ref, _ = acoustic.run(shape=shape, iters=6, with_source=False)
    got, _ = acoustic.run(shape=shape, iters=6, with_source=False,
                          backend=st.pallas(template="gmem", time_block=2),
                          fuse_steps=6)
    np.testing.assert_allclose(np.asarray(got.interior),
                               np.asarray(ref.interior), atol=1e-6)


def test_time_block_reduces_counted_traffic():
    """Acceptance: counted grid reads/writes per step drop ≥2× at k=4."""
    name = "star2d1r"

    def ratio(tb):
        codegen.reset_traffic_count()
        _fused(name, st.pallas(template="gmem", time_block=tb),
               fuse=8, steps=8)
        t = dict(codegen.TRAFFIC_COUNT)
        return t["grid_reads"] / t["steps"], t["grid_writes"] / t["steps"]

    r1, w1 = ratio(1)
    r4, w4 = ratio(4)
    codegen.reset_traffic_count()
    assert r1 / r4 >= 2, (r1, r4)
    assert w1 / w4 >= 2, (w1, w4)
    # the plan's static model agrees
    k = suite.get_kernel(name)
    halos = {g: k.info.halo for g in k.ir.grid_params}
    p1 = codegen.plan_pallas(k.ir, halos, (16, 24),
                             st.pallas(template="gmem"), swap=("v", "u"))
    p4 = codegen.plan_pallas(k.ir, halos, (16, 24),
                             st.pallas(template="gmem", time_block=4),
                             swap=("v", "u"))
    assert p1.grid_reads_per_step / p4.grid_reads_per_step >= 2
    assert p1.hbm_bytes_per_step() > p4.hbm_bytes_per_step()


def _extent(spec):
    """Block extent of a BlockSpec whose dims may be ``pl.Element``."""
    return tuple(getattr(d, "block_size", d) for d in spec.block_shape)


def test_time_block_outputs_never_alias_read_windows():
    """The k>1 kernel reads k·h-deep windows that overlap *neighboring*
    blocks' output interiors; on real TPU the grid runs sequentially, so
    outputs must alias only the dedicated block-sized destination operands
    (double buffering), never the window operands — otherwise later blocks
    would fetch halo data already advanced k steps (interpret mode reads
    inputs functionally and hides the hazard)."""
    k = suite.get_kernel("star2d2r")
    halos = {g: k.info.halo for g in k.ir.grid_params}
    plan = codegen.plan_pallas(k.ir, halos, (16, 24),
                               st.pallas(template="gmem", time_block=4),
                               swap=("v", "u"))
    n_win = len(plan.opnd_grids)
    # outputs alias the destination operands appended after the windows
    assert set(plan._aliases) == {n_win, n_win + 1}, plan._aliases
    # destinations are block-sized: each program instance only donates the
    # block it writes, nothing another instance's window reads
    for i in plan._aliases:
        assert tuple(plan._in_specs[i].block_shape) == tuple(plan.B)
    # every read window keeps its expanded (tile-aligned) halo and is
    # never aliased
    for gi, g in enumerate(plan.opnd_grids):
        assert gi not in plan._aliases
        assert _extent(plan._in_specs[gi]) == tuple(
            plan.B[ax] + 2 * plan.wa[g][ax] for ax in range(plan.ndim))
        assert all(plan.wa[g][ax] >= plan.wf[g][ax]
                   for ax in range(plan.ndim))
    # the double-buffered stage refuses to run without destinations
    with pytest.raises(ValueError, match="double-buffer"):
        plan.step({g: jnp.zeros(plan.padded_shape, jnp.float32)
                   for g in plan.opnd_grids}, {})
    # the k=1 plan still aliases in place — legal because its outputs are
    # center-only-tapped (window == block)
    p1 = codegen.plan_pallas(k.ir, halos, (16, 24),
                             st.pallas(template="gmem"), swap=("v", "u"))
    for gi in p1._aliases:
        assert _extent(p1._in_specs[gi]) == tuple(p1.B)


def test_defaulted_fuse_keeps_between_cadence():
    """A defaulted fuse_steps ('fuse the whole loop') must not be rounded
    to the temporal depth: steps=10, k=4 runs ONE window of 10 (two k-step
    invocations + two singles) and the between hook never fires — enabling
    time_block must not change source-injection timing."""
    name = "star2d1r"
    k = suite.get_kernel(name)
    want = _per_step_reference(name, steps=10)
    grids = _mk_grids(name)
    seen = []
    res = st.launch(backend=st.pallas(template="gmem", time_block=4))(
        lambda u, v: st.timeloop(10, swap=("v", "u"),
                                 between=lambda t, gs: seen.append(t))(k)(
            u, v))(grids["u"], grids["v"])
    assert res.value.fuse_steps == 10
    assert res.value.windows == 1
    assert seen == []
    got = {n: np.asarray(g.data) for n, g in grids.items()}
    for g in ("u", "v"):
        np.testing.assert_allclose(got[g], want[g], atol=1e-6)


@pytest.mark.parametrize("time_block", (3, 5))
def test_time_block_odd_rotation_parity(time_block):
    """Odd temporal depths exercise the k%2 branch of the fused-loop carry
    (output names AND spare destinations must rotate together)."""
    name = "star2d1r"
    steps = 7                              # k-invocations + remainder
    want = _per_step_reference_shape(name, TB_SHAPE, steps)
    k = suite.get_kernel(name)
    grids = _mk_grids_shape(name, TB_SHAPE)
    st.launch(backend=st.pallas(template="gmem", time_block=time_block))(
        lambda u, v: st.timeloop(steps, swap=("v", "u"))(k)(u, v))(
        grids["u"], grids["v"])
    got = {n: np.asarray(g.data) for n, g in grids.items()}
    for g in ("u", "v"):
        np.testing.assert_allclose(got[g], want[g], atol=1e-6,
                                   err_msg=f"k={time_block}/{g}")


def test_explicit_whole_loop_fuse_not_rounded():
    """An explicit fuse_steps >= steps covers the whole loop and must not
    be rounded to the temporal depth either — same cadence invariant as
    the defaulted window."""
    name = "star2d1r"
    k = suite.get_kernel(name)
    grids = _mk_grids(name)
    seen = []
    res = st.launch(backend=st.pallas(template="gmem", time_block=4))(
        lambda u, v: st.timeloop(10, swap=("v", "u"), fuse_steps=16,
                                 between=lambda t, gs: seen.append(t))(k)(
            u, v))(grids["u"], grids["v"])
    assert res.value.fuse_steps == 10
    assert res.value.windows == 1
    assert seen == []


def test_autotune_expansion_keeps_user_time_block():
    """A user-pinned time_block on a plain space entry must be measured,
    not silently overwritten by the time_block_space expansion."""
    b = st.pallas(template="gmem", time_block=8)
    cands = autotune._normalize_space([b], 2, (16, 24), ("v", "u"),
                                      steps=8, fuse_space=(8,),
                                      time_block_space=(1, 2))
    tbs = [getattr(bb, "time_block", 1) for bb, _ in cands]
    assert tbs == [8, 1, 2], cands


def test_time_block_plan_names_the_body_it_runs():
    """At ``time_block>1`` the plan carries the template and memory type
    its kernel really runs (3-D blocked templates stream plane by plane
    through 'vmem' shift; every 2-D template runs one whole-block body), and
    the tuner measures each such program once."""
    k3 = suite.get_kernel("star3d2r")
    halos = {g: k3.info.halo for g in k3.ir.grid_params}
    for t, want in (("gmem", ("shift", "vmem")), ("f4", ("shift", "vmem")),
                    ("semi", ("semi", "vmem"))):
        plan = codegen.plan_pallas(
            k3.ir, halos, (8, 10, 16),
            st.pallas(template=t, mem_type="vmem" if t == "semi" else None,
                      time_block=2), swap=("v", "u"))
        assert (plan.template, plan.mem_type) == want, t
    k2 = suite.get_kernel("star2d2r")
    halos2 = {g: k2.info.halo for g in k2.ir.grid_params}
    plan = codegen.plan_pallas(k2.ir, halos2, (16, 24),
                               st.pallas(template="smem", time_block=2),
                               swap=("v", "u"))
    assert plan.template == "gmem"
    space = [st.pallas(template=t) for t in ("gmem", "smem", "f4")]
    space.append(st.pallas(template="shift", mem_type="vmem"))
    cands = autotune._normalize_space(space, 3, (8, 10, 16), ("v", "u"),
                                      steps=4, fuse_space=(4,),
                                      time_block_space=(1, 2))
    deep = [b for b, _ in cands if b.time_block == 2]
    assert [(b.template, b.mem_type) for b in deep] == [("shift", "vmem")]
    assert len(cands) == 5, cands


def test_distributed_window_decomposition_keeps_inner_depth():
    """A distributed window indivisible by the inner temporal depth must
    split into (largest multiple, remainder) sub-programs — not silently
    run the whole window with the depth disabled."""
    from repro.core import timeloop as tl
    assert tl.window_parts(10, 4) == [8, 2]
    assert tl.window_parts(8, 4) == [8]       # exact multiple: one program
    assert tl.window_parts(3, 4) == [3]       # below the depth: as-is
    assert tl.window_parts(10, 1) == [10]     # no inner depth
    assert tl.window_parts(9, 4) == [8, 1]    # single-step remainder


def test_autotune_norm_fuse_matches_engine_window():
    """Autotune normalizes candidate windows exactly like the engine
    (shared timeloop.normalize_fuse): requests ≥ steps collapse to one
    whole-loop window and deduplicate; sub-loop windows are honored as
    requested (never rounded to the temporal depth)."""
    b = st.distributed(inner=st.pallas(template="gmem", time_block=2))
    cands = autotune._normalize_space(
        [b, (b, 9)], 2, (16, 24), ("v", "u"), steps=8, fuse_space=(8,))
    # expansion gives (b, 8); the explicit pair collapses 9 -> 8 (whole
    # loop) and deduplicates against it
    assert [f for _, f in cands] == [8], cands
    p = st.pallas(template="gmem", time_block=4)
    cands = autotune._normalize_space(
        [(p, 6)], 2, (16, 24), ("v", "u"), steps=20, fuse_space=())
    assert [f for _, f in cands] == [6], cands   # not rounded to 4


def test_time_block_one_pad_per_grid_per_window():
    """Temporal blocking keeps the one-pad-per-window layout invariant."""
    codegen.reset_pad_count()
    _fused("star2d1r", st.pallas(template="gmem", time_block=2),
           fuse=4, steps=12)
    assert codegen.PAD_COUNT["u"] == 3, dict(codegen.PAD_COUNT)
    assert codegen.PAD_COUNT["v"] == 3, dict(codegen.PAD_COUNT)
    codegen.reset_pad_count()


def test_time_block_halo_growth_block_geometry():
    """Default block geometry grows so the k·h expanded halo fits."""
    k = suite.get_kernel("star2d4r")       # h=4; k=4 → k·h=16 > default 8
    halos = {g: k.info.halo for g in k.ir.grid_params}
    plan = codegen.plan_pallas(k.ir, halos, (32, 32),
                               st.pallas(template="gmem", time_block=4),
                               swap=("v", "u"))
    assert plan.B[0] >= 16
    assert plan.wf["u"] == (16, 16)


def test_time_block_validation():
    k = suite.get_kernel("star2d2r")
    halos = {g: k.info.halo for g in k.ir.grid_params}
    # user-pinned block too small for k·h
    with pytest.raises(ValueError, match="k·h <= block"):
        codegen.plan_pallas(k.ir, halos, (16, 24),
                            st.pallas(template="gmem", time_block=8,
                                      block=(8, 128)), swap=("v", "u"))
    # temporal blocking needs the leapfrog swap pair
    with pytest.raises(ValueError, match="swap"):
        codegen.plan_pallas(k.ir, halos, (16, 24),
                            st.pallas(template="gmem", time_block=2))
    # the per-application path advances one step
    grids = _mk_grids("star2d2r")
    with pytest.raises(ValueError, match="fused time-loop"):
        st.launch(backend=st.pallas(template="gmem", time_block=2))(
            lambda u, v: st.map(e=u.shape)(k)(u, v))(grids["u"], grids["v"])
    with pytest.raises(ValueError):
        st.pallas(time_block=0)
    # a launch-level override that cannot apply must not be silently
    # ignored (the user would measure the plain fused loop believing the
    # temporal depth is active)
    g2 = _mk_grids("star2d2r")
    with pytest.raises(ValueError, match="pallas backend"):
        st.launch(backend=st.xla(), time_block=2)(
            lambda u, v: st.timeloop(2, swap=("v", "u"))(k)(u, v))(
            g2["u"], g2["v"])


def test_launch_time_block_override_honors_window():
    """st.launch(time_block=k) overrides the backend knob; the requested
    fusion window is honored exactly (each window runs ⌊kw/k⌋ k-step
    invocations plus single-step remainder), never rounded to k."""
    name = "star2d1r"
    k = suite.get_kernel(name)
    want = _per_step_reference(name, steps=10)
    grids = _mk_grids(name)
    seen = []
    res = st.launch(backend=st.pallas(template="gmem"), time_block=2)(
        lambda u, v: st.timeloop(10, swap=("v", "u"), fuse_steps=3,
                                 between=lambda t, gs: seen.append(t))(k)(
            u, v))(grids["u"], grids["v"])
    assert res.value.fuse_steps == 3       # cadence exactly as requested
    assert res.value.windows == 4
    assert seen == [3, 6, 9]
    got = {n: np.asarray(g.data) for n, g in grids.items()}
    for g in ("u", "v"):
        np.testing.assert_allclose(got[g], want[g], atol=1e-6)


def test_time_block_never_stretches_between_cadence():
    """fuse_steps below the temporal depth is honored (runs as single
    steps): the between hook keeps its exact per-window cadence."""
    name = "star2d1r"
    k = suite.get_kernel(name)
    want = _per_step_reference(name, steps=4)
    grids = _mk_grids(name)
    seen = []
    res = st.launch(backend=st.pallas(template="gmem", time_block=4))(
        lambda u, v: st.timeloop(4, swap=("v", "u"), fuse_steps=1,
                                 between=lambda t, gs: seen.append(t))(k)(
            u, v))(grids["u"], grids["v"])
    assert res.value.fuse_steps == 1
    assert seen == [1, 2, 3]
    got = {n: np.asarray(g.data) for n, g in grids.items()}
    for g in ("u", "v"):
        np.testing.assert_allclose(got[g], want[g], atol=1e-6)


def test_autotune_searches_time_block():
    k = suite.get_kernel("star2d1r")
    grids = _mk_grids("star2d1r")
    autotune.clear_cache()
    res = autotune.tune(k, grids, iters=1,
                        space=[st.pallas(template="gmem")],
                        swap=("v", "u"), steps=8, fuse_space=(8,),
                        time_block_space=(1, 2))
    assert len(res.trials) == 2
    tbs = {getattr(b, "time_block", 1) for b, _, _ in res.trials}
    assert tbs == {1, 2}
    assert res.seconds < float("inf")
    # winner is launchable with its time_block riding on the backend
    g2 = _mk_grids("star2d1r")
    st.launch(backend=res.backend, fuse_steps=res.fuse_steps)(
        lambda u, v: st.timeloop(4, swap=("v", "u"))(k)(u, v))(
        g2["u"], g2["v"])
    autotune.clear_cache()


def test_autotune_dedups_overlapping_space():
    """A custom space overlapping the fuse/time_block expansion must not
    measure the same (backend, fuse_steps) twice."""
    k = suite.get_kernel("star2d1r")
    grids = _mk_grids("star2d1r")
    autotune.clear_cache()
    res = autotune.tune(
        k, grids, iters=1,
        space=[st.pallas(template="gmem"),
               (st.pallas(template="gmem", time_block=2), 4)],
        swap=("v", "u"), steps=8, fuse_space=(4,),
        time_block_space=(1, 2))
    # expansion: (tb=1, 4), (tb=2, 4); the explicit pair duplicates the
    # latter → 2 unique candidates, not 3
    assert len(res.trials) == 2, [(b, f) for b, f, _ in res.trials]
    autotune.clear_cache()


# ---- autotune cache key + fuse_steps search -------------------------------
def test_autotune_cache_key_includes_space_and_iters():
    k = suite.get_kernel("star2d1r")
    grids = _mk_grids("star2d1r")
    autotune.clear_cache()
    a = autotune.tune(k, grids, iters=1, space=[st.xla()])
    b = autotune.tune(k, grids, iters=1,
                      space=[st.pallas(template="gmem")])
    assert a.backend.kind == "xla"
    assert b.backend.kind == "pallas"      # not the stale cached xla result
    assert autotune.tune(k, grids, iters=1, space=[st.xla()]) is a  # memoized
    autotune.clear_cache()
    assert autotune.tune(k, grids, iters=1, space=[st.xla()]) is not a


def test_autotune_searches_fuse_steps():
    k = suite.get_kernel("star2d1r")
    grids = _mk_grids("star2d1r")
    autotune.clear_cache()
    res = autotune.tune(k, grids, iters=1, space=[st.xla()],
                        swap=("v", "u"), steps=8, fuse_space=(1, 8))
    assert len(res.trials) == 2
    assert res.fuse_steps in (1, 8)
    assert res.seconds < float("inf")
    # tuner result is launchable through the fused path
    g2 = _mk_grids("star2d1r")
    st.launch(backend=res.backend, fuse_steps=res.fuse_steps)(
        lambda u, v: st.timeloop(4, swap=("v", "u"))(k)(u, v))(
        g2["u"], g2["v"])
    autotune.clear_cache()


def test_launch_autotune_picks_backend_and_fuse():
    """st.launch(autotune=True) replaces the fixed backend with the tuned
    winner and applies the tuned window when fuse is unspecified."""
    autotune.clear_cache()
    autotune.reset_measure_count()
    k = suite.get_kernel("star2d1r")
    grids = _mk_grids("star2d1r")

    def tgt(u, v):
        return st.timeloop(8, swap=("v", "u"))(k)(u, v)

    run = st.launch(autotune=True, autotune_space=[st.xla()],
                    autotune_steps=4, autotune_fuse_space=(1, 4),
                    autotune_time_block_space=(1,))
    res = run(tgt)(grids["u"], grids["v"])
    # 2 candidates <= default top_k=3: no pruning, both measured
    assert autotune.MEASURE_COUNT["measured_candidates"] == 2
    assert autotune.MEASURE_COUNT["pruned_candidates"] == 0
    assert res.value.fuse_steps in (1, 4, 8)
    # a second launch hits the in-process tune cache
    g2 = _mk_grids("star2d1r")
    run(tgt)(g2["u"], g2["v"])
    assert autotune.MEASURE_COUNT["measured_candidates"] == 2
    autotune.clear_cache()


def test_launch_autotune_prunes_with_injected_model():
    from repro.core import cost_model as cm
    autotune.clear_cache()
    autotune.reset_measure_count()
    k = suite.get_kernel("star2d1r")
    grids = _mk_grids("star2d1r")

    def tgt(u, v):
        return st.timeloop(8, swap=("v", "u"))(k)(u, v)

    run = st.launch(autotune=True,
                    autotune_space=[st.xla(), st.pallas(template="gmem")],
                    autotune_top_k=2, autotune_steps=4,
                    autotune_fuse_space=(1, 2, 4),
                    autotune_time_block_space=(1, 2),
                    autotune_cost_model=cm.CostModel(calibrate=False))
    run(tgt)(grids["u"], grids["v"])
    # 9 candidates, shortlist of 2
    assert autotune.MEASURE_COUNT["measured_candidates"] == 2
    assert autotune.MEASURE_COUNT["pruned_candidates"] == 7
    autotune.clear_cache()


def test_launch_autotune_explicit_fuse_wins():
    autotune.clear_cache()
    k = suite.get_kernel("star2d1r")
    grids = _mk_grids("star2d1r")

    def tgt(u, v):
        return st.timeloop(8, swap=("v", "u"), fuse_steps=2)(k)(u, v)

    run = st.launch(autotune=True, autotune_space=[st.xla()],
                    autotune_steps=4, autotune_fuse_space=(1, 4),
                    autotune_time_block_space=(1,))
    res = run(tgt)(grids["u"], grids["v"])
    assert res.value.fuse_steps == 2   # timeloop's own fuse overrides
    autotune.clear_cache()


def test_launch_autotune_skips_batched_timeloop():
    """Batched grids fall through to the fixed backend unchanged."""
    autotune.clear_cache()
    autotune.reset_measure_count()
    k = suite.get_kernel("star2d1r")
    grids = {g: st.grid(st.f32, (8, 8), k.info.order, batch=2).randomize(i)
             for i, g in enumerate(k.ir.grid_params)}

    def tgt(u, v):
        return st.timeloop(4, swap=("v", "u"))(k)(u, v)

    run = st.launch(autotune=True, autotune_space=[st.xla()])
    run(tgt)(grids["u"], grids["v"])
    assert autotune.MEASURE_COUNT["measured_candidates"] == 0
    autotune.clear_cache()
