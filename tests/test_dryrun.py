"""Dry-run machinery tests.

The full 512-device production dry-run runs out-of-process (it must set
XLA_FLAGS before jax init); here we validate the same code path on an
8-device subprocess mesh for a fast arch × every shape kind, plus the HLO
analysis pass on synthetic HLO text.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout[-3000:]}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


def test_lower_compile_all_kinds_small_mesh():
    """train/prefill/decode cells lower+compile on a (4,2) mesh with the
    exact dryrun.lower_cell code path (tiny config, reduced shapes)."""
    out = _run("""
    import dataclasses, jax, jax.numpy as jnp
    from jax.sharding import NamedSharding
    from repro import configs, sharding
    from repro.launch.mesh import make_mesh
    from repro.configs.shapes import ShapeSpec, input_specs
    from repro.models import api
    from repro.serving.serve_loop import make_serve_step
    from repro.train import train_loop
    from repro.train.optimizer import OptConfig

    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = configs.tiny(configs.get("granite-8b"))
    for kind, seq, gb in (("train", 64, 8), ("prefill", 64, 8),
                          ("decode", 64, 8)):
        shape = ShapeSpec("t", kind, seq, gb)
        specs = input_specs(cfg, shape)
        pshapes = api.param_shapes(cfg)
        pshard = sharding.param_shardings(cfg, mesh, pshapes)
        if kind == "train":
            tc = train_loop.TrainConfig(opt=OptConfig(), n_microbatches=2)
            with mesh:
                lowered, _ = train_loop.compile_train_step(cfg, tc, mesh,
                                                           specs)
        elif kind == "prefill":
            from repro.launch.dryrun import make_prefill_step
            fn = make_prefill_step(cfg)
            bshard = sharding.batch_shardings(cfg, mesh, specs)
            out_spec = sharding.resolve(("batch", None, "vocab"),
                                        (gb, 1, cfg.vocab), mesh)
            with mesh, sharding.use_activation_mesh(mesh):
                lowered = jax.jit(fn, in_shardings=(pshard, bshard),
                                  out_shardings=NamedSharding(mesh, out_spec)
                                  ).lower(pshapes, specs)
        else:
            step = make_serve_step(cfg)
            cshard = sharding.cache_shardings(cfg, mesh, specs["cache"])
            tshard = NamedSharding(mesh,
                                   sharding.resolve(("batch", None),
                                                    (gb, 1), mesh))
            kshard = sharding.scalar_sharding(mesh)
            with mesh, sharding.use_activation_mesh(mesh):
                lowered = jax.jit(
                    step, in_shardings=(pshard, cshard, tshard, kshard),
                    out_shardings=(tshard, cshard), donate_argnums=(1,)
                ).lower(pshapes, specs["cache"],
                        jax.ShapeDtypeStruct((gb, 1), jnp.int32),
                        jax.ShapeDtypeStruct((2,), jnp.uint32))
        compiled = lowered.compile()
        c = compiled.cost_analysis()
        print("OK", kind, bool(c))
    """)
    assert out.count("OK") == 3


def test_sharded_train_matches_single_device():
    """One sharded train step on a (2,2) mesh == single-device step."""
    out = _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro import configs, sharding
    from repro.configs.shapes import ShapeSpec
    from repro.train import data, train_loop
    from repro.train.optimizer import OptConfig

    cfg = configs.tiny(configs.get("phi3-mini-3.8b"))
    shape = ShapeSpec("t", "train", 32, 8)
    batch = {k: jnp.asarray(v)
             for k, v in data.make_batch_fn(cfg, shape)(0).items()}
    tc = train_loop.TrainConfig(opt=OptConfig(lr=1e-3), n_microbatches=2)
    step = train_loop.make_train_step(cfg, tc)

    state0 = train_loop.init_state(cfg, jax.random.PRNGKey(0))
    ref_state, ref_m = jax.jit(step)(jax.tree.map(jnp.copy, state0), batch)

    mesh = make_mesh((2, 2), ("data", "model"))
    st_shard = train_loop.state_shardings(cfg, mesh)
    b_shard = sharding.batch_shardings(
        cfg, mesh, jax.tree.map(lambda x: x, batch))
    with sharding.use_activation_mesh(mesh):
        sh_state, sh_m = jax.jit(
            step, in_shardings=(st_shard, b_shard))(
            jax.device_put(state0, st_shard), batch)
    assert abs(float(ref_m["loss"]) - float(sh_m["loss"])) < 1e-3, \
        (float(ref_m["loss"]), float(sh_m["loss"]))
    for a, b in zip(jax.tree.leaves(ref_state["params"]),
                    jax.tree.leaves(sh_state["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=5e-3, atol=5e-5)
    print("MATCH")
    """, devices=4)
    assert "MATCH" in out


def test_hlo_analysis_trip_counts():
    from repro.launch import hlo_analysis as H
    hlo = """
HloModule test

%cond.1 (arg.1: (s32[], f32[8,8])) -> pred[] {
  %arg.1 = (s32[], f32[8,8]) parameter(0)
  %gte.1 = s32[] get-tuple-element(%arg.1), index=0
  %c.1 = s32[] constant(5)
  ROOT %cmp.1 = pred[] compare(%gte.1, %c.1), direction=LT
}

%body.1 (arg.2: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %arg.2 = (s32[], f32[8,8]) parameter(0)
  %gte.2 = s32[] get-tuple-element(%arg.2), index=0
  %gte.3 = f32[8,8] get-tuple-element(%arg.2), index=1
  %ar.1 = f32[8,8] all-reduce(%gte.3), replica_groups=[4,2]<=[8], to_apply=%sum.1
  %dot.1 = f32[8,8] dot(%ar.1, %gte.3), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %c.2 = s32[] constant(1)
  %add.1 = s32[] add(%gte.2, %c.2)
  ROOT %t.1 = (s32[], f32[8,8]) tuple(%add.1, %dot.1)
}

%sum.1 (x.1: f32[], y.1: f32[]) -> f32[] {
  %x.1 = f32[] parameter(0)
  %y.1 = f32[] parameter(1)
  ROOT %a.1 = f32[] add(%x.1, %y.1)
}

ENTRY %main (p.1: f32[8,8]) -> (s32[], f32[8,8]) {
  %p.1 = f32[8,8] parameter(0)
  %c.3 = s32[] constant(0)
  %t.2 = (s32[], f32[8,8]) tuple(%c.3, %p.1)
  ROOT %w.1 = (s32[], f32[8,8]) while(%t.2), condition=%cond.1, body=%body.1
}
"""
    st = H.analyze(hlo, 8)
    # 5 trips × one dot of 2·64·8 flops
    assert st.dot_flops == 5 * 2 * 64 * 8, st.dot_flops
    # 5 trips × all-reduce of 256 bytes, group 2: 2·256·(1/2) = 256
    assert st.coll_counts["all-reduce"] == 5
    assert st.coll_bytes["all-reduce"] == 5 * 256.0, st.coll_bytes


def test_baseline_artifacts_complete_if_present():
    """If the production dry-run artifacts exist, every non-skipped cell
    must have compiled ok on both meshes (40 cells - 6 skips = 34 ok per
    mesh)."""
    art = os.path.join(_REPO, "benchmarks", "artifacts")
    if not os.path.isdir(art):
        pytest.skip("no artifacts yet")
    merged = os.path.join(art, "dryrun_baseline.json")
    if os.path.exists(merged):
        records = json.load(open(merged))
    else:
        records = []
        for fn in os.listdir(art):
            if fn.startswith("dryrun_") and fn.endswith(".json"):
                records.extend(json.load(open(os.path.join(art, fn))))
    if not records:
        pytest.skip("no artifacts yet")
    for mesh in ("single", "multi"):
        cells = [r for r in records
                 if r["mesh"] == mesh and r.get("kind") != "stencil"]
        errs = [r for r in cells if r["status"] == "error"]
        assert not errs, [(r["arch"], r["shape"], r["error"]) for r in errs]
        assert sum(r["status"] == "ok" for r in cells) == 34, len(cells)
        assert sum(r["status"] == "skipped" for r in cells) == 6
        stencil = [r for r in records
                   if r["mesh"] == mesh and r.get("kind") == "stencil"]
        assert all(r["status"] == "ok" for r in stencil)
