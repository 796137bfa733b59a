"""Analytical cost model and the two-stage autotune search: byte
accounting matches the plan geometry, predictions order candidates
sensibly, calibration persists, and pruning measures exactly the
shortlist."""
import json
import math
import os

import pytest

from repro.core import autotune as at
from repro.core import cost_model as cm
from repro.core import dsl as st, suite

F32 = st.f32


def _grids(name="star2d1r", shape=(16, 16)):
    k = suite.get_kernel(name)
    return k, {g: st.grid(F32, shape, k.info.order).randomize(i)
               for i, g in enumerate(k.ir.grid_params)}


def _model():
    """Deterministic model: no probe timing, default rates."""
    return cm.CostModel(calibrate=False)


@pytest.fixture(autouse=True)
def _fresh():
    at.clear_cache()
    at.reset_measure_count()
    cm.reset_default_models()
    yield
    at.clear_cache()
    at.reset_measure_count()
    cm.reset_default_models()


# -- byte accounting -------------------------------------------------------
def test_pallas_step_bytes_match_plan():
    from repro.kernels.stencil import codegen
    k, grids = _grids()
    halos = {n: g.halo for n, g in grids.items()}
    interior = (16, 16)
    backend = st.pallas(template="gmem", time_block=2)
    plan = codegen.plan_pallas(k.ir, halos, interior, backend,
                               swap=("v", "u"))
    sb = _model().step_bytes(k, halos, interior, backend, ("v", "u"), F32)
    assert sb is not None
    per_step, per_window = sb
    assert per_step == plan.hbm_bytes_per_step(4)
    assert per_window == plan.layout_bytes_per_window(4)
    assert per_step > 0 and per_window > 0


def test_infeasible_pallas_plan_costs_inf():
    # star3d4r order-4 halo with an explicit 2-wide block: h=4 > B=2,
    # plan_pallas raises, the model charges inf (never wins, like a
    # measured compile failure)
    k, grids = _grids("star3d4r", shape=(8, 8, 8))
    halos = {n: g.halo for n, g in grids.items()}
    backend = st.pallas(template="gmem", block=(2, 2, 2))
    sb = _model().step_bytes(k, halos, (8, 8, 8), backend, ("v", "u"), F32)
    assert sb is not None and math.isinf(sb[0])
    p = _model().predict(k, grids, backend, 4, 8, ("v", "u"))
    assert math.isinf(p)


def test_xla_step_bytes_positive_and_memoized():
    k, grids = _grids()
    halos = {n: g.halo for n, g in grids.items()}
    model = _model()
    sb = model.step_bytes(k, halos, (16, 16), st.xla(), ("v", "u"), F32)
    assert sb is not None
    assert 0 < sb[0] < float("inf") and sb[1] == 0.0
    assert len(model._bytes_memo) == 1
    again = model.step_bytes(k, halos, (16, 16), st.xla(), ("v", "u"), F32)
    assert again == sb and len(model._bytes_memo) == 1


# -- prediction ------------------------------------------------------------
def test_larger_fuse_predicts_cheaper():
    k, grids = _grids()
    model = _model()
    backend = st.pallas(template="gmem")
    p1 = model.predict(k, grids, backend, 1, 8, ("v", "u"))
    p8 = model.predict(k, grids, backend, 8, 8, ("v", "u"))
    assert p8 < p1  # fewer windows => less layout traffic + overhead


def test_distributed_backend_is_unpredictable():
    # without a mesh the geometry is unknown: no execution class, no price
    k, grids = _grids()
    backend = st.distributed(grid_axes=("data", None))
    assert cm.exec_key(backend) is None
    assert _model().predict(k, grids, backend, 1, 8, ("v", "u")) is None


# -- distributed pricing (mesh-aware) ---------------------------------------
def test_distributed_predict_finite_with_mesh():
    k, grids = _grids(shape=(32, 32))
    backend = st.distributed(grid_axes=("data", None), time_steps=2)
    p = _model().predict(k, grids, backend, 4, 8, ("v", "u"),
                         mesh={"data": 4})
    assert p is not None and math.isfinite(p) and p > 0


def test_distributed_predict_infeasible_geometry_is_inf():
    model = _model()
    # indivisible decomposition
    k, grids = _grids(shape=(30, 30))
    be = st.distributed(grid_axes=("data", None))
    p = model.predict(k, grids, be, 1, 8, ("v", "u"), mesh={"data": 4})
    assert math.isinf(p)
    # k·h deeper than the shard: local 16/8 = 2 < depth 4 × h 1
    k2, grids2 = _grids(shape=(16, 16))
    be2 = st.distributed(grid_axes=("data", None), time_steps=4)
    p2 = model.predict(k2, grids2, be2, 8, 8, ("v", "u"), mesh={"data": 8})
    assert math.isinf(p2)


def test_deeper_skewing_predicts_fewer_group_overheads():
    # equal steps and traffic volume, but time_steps=4 pays 2 exchange
    # groups per window where time_steps=1 pays 8 → cheaper on the link
    k, grids = _grids(shape=(64, 64))
    model = _model()
    be1 = st.distributed(grid_axes=("data", None), time_steps=1)
    be4 = st.distributed(grid_axes=("data", None), time_steps=4)
    p1 = model.predict(k, grids, be1, 8, 8, ("v", "u"), mesh={"data": 4})
    p4 = model.predict(k, grids, be4, 8, 8, ("v", "u"), mesh={"data": 4})
    assert p4 < p1


def test_link_rate_falls_back_without_probeable_mesh():
    # no mesh / shape-only mapping / single-device mesh: nothing a
    # ppermute probe could exercise — fixed default, and nothing cached
    # (a later real-mesh call must still be allowed to probe)
    model = _model()
    assert model.rate_for("link", F32) == cm.DEFAULT_RATES["link"]
    assert model.rate_for("link", F32, mesh={"data": 4}) \
        == cm.DEFAULT_RATES["link"]
    import jax
    from repro.launch.mesh import make_mesh
    mesh1 = make_mesh((1,), ("data",))
    assert model.rate_for("link", F32, mesh=mesh1) \
        == cm.DEFAULT_RATES["link"]
    assert not any(k.startswith("link") for k in model._rates)


def test_link_rate_keyed_by_device_count():
    # a pre-seeded measured rate for the mesh's device count is used and
    # a calibrate=False model never probes past it
    class FakeMesh:
        import numpy as _np
        devices = _np.empty((4,), object)

    seeded = cm.Rate(bytes_per_s=7e9, overhead_s=1e-5)
    model = cm.CostModel(calibrate=False,
                         rates={"link@4/float32": seeded})
    assert model.rate_for("link", F32, mesh=FakeMesh()) == seeded
    # unseeded count falls back to the default (calibrate=False)
    class FakeMesh8:
        import numpy as _np
        devices = _np.empty((8,), object)

    assert model.rate_for("link", F32, mesh=FakeMesh8()) \
        == cm.DEFAULT_RATES["link"]


def test_link_probe_measures_and_persists(tmp_path):
    """Real ppermute ring probe on 4 forced host devices (subprocess, like
    test_distributed.py): the measured rate replaces the default, lands in
    the version-gated roofline JSON, and reloads."""
    import subprocess
    import sys
    import textwrap
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    code = textwrap.dedent(f"""
        import os, numpy as np, jax
        from jax.sharding import Mesh
        from repro.core import cost_model as cm
        d = {str(tmp_path)!r}
        mesh = Mesh(np.array(jax.devices()).reshape(4), ("x",))
        m = cm.CostModel(cache_dir=d, calibrate=True)
        r = m.rate_for("link", np.float32, mesh=mesh)
        assert r != cm.DEFAULT_RATES["link"], r
        assert r.bytes_per_s > 0 and r.overhead_s > 0
        assert "link@4/float32" in m._rates
        # reload from disk without probing
        m2 = cm.CostModel(cache_dir=d, calibrate=False)
        assert m2.rate_for("link", np.float32, mesh=mesh) == r
        files = [f for f in os.listdir(d) if f.startswith("roofline-")]
        assert len(files) == 1 and f"v{{cm.CALIBRATION_VERSION}}" in files[0]
        print("probed", r.bytes_per_s)
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=300)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "probed" in r.stdout


def test_batch_scales_predicted_traffic():
    k = suite.get_kernel("star2d1r")
    model = _model()
    g1 = {g: st.grid(F32, (16, 16), k.info.order).randomize(i)
          for i, g in enumerate(k.ir.grid_params)}
    g4 = {g: st.grid(F32, (16, 16), k.info.order, batch=4).randomize(i)
          for i, g in enumerate(k.ir.grid_params)}
    p1 = model.predict(k, g1, st.xla(), 8, 8, ("v", "u"))
    p4 = model.predict(k, g4, st.xla(), 8, 8, ("v", "u"))
    assert p4 > p1


# -- calibration persistence ----------------------------------------------
def test_rates_persist_next_to_cache(tmp_path):
    cdir = str(tmp_path)
    r = cm.Rate(bytes_per_s=3e9, overhead_s=5e-5)
    m = cm.CostModel(cache_dir=cdir, calibrate=False)
    m._rates[cm._rate_key("xla", F32)] = r
    m._store_rates()
    files = [f for f in os.listdir(cdir) if f.startswith("roofline-")]
    assert len(files) == 1
    assert f"v{cm.CALIBRATION_VERSION}" in files[0]
    m2 = cm.CostModel(cache_dir=cdir, calibrate=False)
    assert m2.rate_for("xla", F32) == r


def test_stale_calibration_version_ignored(tmp_path):
    cdir = str(tmp_path)
    m = cm.CostModel(cache_dir=cdir, calibrate=False)
    m._rates[cm._rate_key("xla", F32)] = cm.Rate(3e9, 5e-5)
    m._store_rates()
    path = m._cal_path()
    with open(path) as f:
        blob = json.load(f)
    blob["version"] = cm.CALIBRATION_VERSION + 1
    with open(path, "w") as f:
        json.dump(blob, f)
    m2 = cm.CostModel(cache_dir=cdir, calibrate=False)
    assert m2.rate_for("xla", F32) == cm.DEFAULT_RATES["xla"]


# -- two-stage search ------------------------------------------------------
SPACE = [st.xla(), st.pallas(template="gmem")]


def _tune(top_k, model, iters=1, **kw):
    k, grids = _grids()
    return at.tune(k, grids, iters=iters, space=SPACE, swap=("v", "u"),
                   steps=4, fuse_space=(1, 2, 4), time_block_space=(1, 2),
                   top_k=top_k, cost_model=model, **kw)


def test_two_stage_measures_exactly_top_k():
    # space: xla x 3 fuse + gmem x 3 fuse x 2 tb = 9 candidates
    res = _tune(3, _model())
    assert len(res.predicted) == 9
    assert res.measured_candidates == 3
    assert res.pruned_candidates == 6
    assert at.MEASURE_COUNT["measured_candidates"] == 3
    assert at.MEASURE_COUNT["pruned_candidates"] == 6
    assert res.top_k == 3
    # every predicted entry for this space is numeric
    assert all(p is not None for _, _, p in res.predicted)


def test_exhaustive_when_top_k_none():
    res = _tune(None, _model())
    assert res.measured_candidates == 9
    assert res.pruned_candidates == 0
    assert res.top_k is None
    assert len(res.predicted) == 9  # explicit model still predicts all


def test_no_model_no_predictions_when_not_pruning():
    res = _tune(None, None)
    assert res.predicted == []
    assert res.rank_error is None
    assert res.measured_candidates == 9


def test_rank_error_within_shortlist():
    res = _tune(3, _model())
    # the measured best was one of the 3 measured, all drawn from the
    # top of the predicted order
    assert res.rank_error is not None and res.rank_error < 3


def test_two_stage_winner_close_to_exhaustive():
    # iters=3 + a generous bound: µs-scale host timing jitters far more
    # than the model's ranking error on these tiny grids
    model = _model()
    exhaustive = _tune(None, model, iters=3)
    at.clear_cache()
    pruned = _tune(3, model, iters=3)
    ex = {(b.cache_key(), f): dt for b, f, dt in exhaustive.trials}
    in_ex = ex[(pruned.backend.cache_key(), pruned.fuse_steps)]
    assert in_ex <= exhaustive.seconds * 1.5


def test_top_k_zero_rejected():
    with pytest.raises(ValueError):
        _tune(0, _model())


# -- shortlist helper ------------------------------------------------------
def test_shortlist_keeps_cheapest_and_unpredictable():
    preds = [5.0, 1.0, None, 3.0, 2.0, None]
    assert at.shortlist_indices(preds, 2) == [1, 2, 4, 5]
    assert at.shortlist_indices(preds, 1) == [1, 2, 5]
    assert at.shortlist_indices([None, None], 1) == [0, 1]
    assert at.shortlist_indices([], 3) == []


def test_shortlist_tie_break_is_original_order():
    assert at.shortlist_indices([1.0, 1.0, 1.0], 2) == [0, 1]


def test_shortlist_inf_ranks_last():
    preds = [float("inf"), 2.0, 1.0]
    assert at.shortlist_indices(preds, 2) == [1, 2]


# -- mesh-aware tuning ------------------------------------------------------
def _mesh_space():
    return [st.xla(), st.pallas(template="gmem"),
            (st.distributed(grid_axes=("data", None)), 1),
            (st.distributed(grid_axes=("data", None), time_steps=2), 4)]


def test_tune_with_mesh_prunes_distributed_candidates():
    """Acceptance: over a mesh-inclusive space the tuner predicts EVERY
    row (distributed included — the mesh makes them priceable) and
    measures at most top_k, so distributed candidates participate in
    pruning instead of forcing exhaustive measurement."""
    import jax
    from repro.launch.mesh import make_mesh
    k, grids = _grids()
    mesh = make_mesh((1,), ("data",))
    res = at.tune(k, grids, iters=1, space=_mesh_space(), swap=("v", "u"),
                  steps=4, fuse_space=(1,), time_block_space=(1,),
                  top_k=2, cost_model=_model(), mesh=mesh)
    assert len(res.predicted) == 4
    assert all(p is not None for _, _, p in res.predicted)
    assert res.measured_candidates == 2
    assert res.pruned_candidates == 2
    # rank check extends to mesh rows: the winner came from the shortlist
    assert res.rank_error is not None and res.rank_error < 2


def test_mesh_results_skip_disk_cache(tmp_path):
    """Mesh-tuned results stay in-memory: the disk key carries no mesh
    descriptor, so persisting them would leak one mesh's winner into
    every other topology."""
    import jax
    from repro.launch.mesh import make_mesh
    k, grids = _grids()
    mesh = make_mesh((1,), ("data",))
    at.tune(k, grids, iters=1, space=[st.xla()], swap=("v", "u"),
            steps=2, fuse_space=(1,), top_k=None, cost_model=_model(),
            cache_dir=str(tmp_path), mesh=mesh)
    assert not os.listdir(tmp_path)
    at.clear_cache()
    at.tune(k, grids, iters=1, space=[st.xla()], swap=("v", "u"),
            steps=2, fuse_space=(1,), top_k=None, cost_model=_model(),
            cache_dir=str(tmp_path))
    assert os.listdir(tmp_path)
