"""Training-substrate tests: optimizer, microbatching-equivalence, data
determinism, checkpoint-restart bitwise reproducibility, elastic re-shard,
failure injection, gradient compression."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.shapes import ShapeSpec
from repro.models import api
from repro.train import checkpoint, data, fault_tolerance, optimizer, train_loop

CFG = configs.tiny(configs.get("granite-8b"))
SHAPE = ShapeSpec("smoke", "train", seq_len=32, global_batch=8)


def _tc(n_mb=1, steps=50):
    return train_loop.TrainConfig(
        opt=optimizer.OptConfig(lr=1e-3, warmup_steps=5, total_steps=steps),
        n_microbatches=n_mb)


def _batch(step=0):
    return {k: jnp.asarray(v)
            for k, v in data.make_batch_fn(CFG, SHAPE, seed=0)(step).items()}


# -- optimizer ----------------------------------------------------------------
def test_schedule_warmup_cosine():
    oc = optimizer.OptConfig(lr=1e-2, warmup_steps=10, total_steps=100,
                             min_lr_ratio=0.1)
    lrs = [float(optimizer.schedule(oc, jnp.int32(s)))
           for s in (0, 5, 10, 55, 100)]
    assert lrs[0] == 0.0
    assert abs(lrs[1] - 5e-3) < 1e-9
    assert abs(lrs[2] - 1e-2) < 1e-9
    assert lrs[2] > lrs[3] > lrs[4]
    assert abs(lrs[4] - 1e-3) < 1e-6


def test_clip_by_global_norm():
    g = {"a": jnp.full((4,), 3.0), "b": jnp.full((4,), 4.0)}
    clipped, norm = optimizer.clip_by_global_norm(g, 1.0)
    assert abs(float(norm) - 10.0) < 1e-5
    cn = optimizer.global_norm(clipped)
    assert abs(float(cn) - 1.0) < 1e-5


def test_global_norm_empty_tree():
    # jnp.stack([]) used to raise on an empty pytree
    assert float(optimizer.global_norm({})) == 0.0
    assert float(optimizer.global_norm([])) == 0.0
    clipped, norm = optimizer.clip_by_global_norm({}, 1.0)
    assert clipped == {} and float(norm) == 0.0


def test_bare_array_params_skip_weight_decay():
    # a bare 2-D array passed as the whole params tree (the fwi.py velocity
    # grid) is a physical field, not a matmul weight: no decay
    c = optimizer.OptConfig(lr=0.1, warmup_steps=0, total_steps=10,
                            weight_decay=0.5)
    params = jnp.ones((4, 4))
    grads = jnp.zeros((4, 4))
    state = optimizer.init(params)
    new_p, _, _ = optimizer.apply(c, params, grads, state, jnp.int32(0))
    np.testing.assert_allclose(np.asarray(new_p), np.asarray(params))
    # the same matrix inside a tree IS decayed
    new_t, _, _ = optimizer.apply(c, {"w": params}, {"w": grads},
                                  optimizer.init({"w": params}),
                                  jnp.int32(0))
    assert float(jnp.abs(new_t["w"] - params).max()) > 0


def test_bare_array_adamw_descends():
    # end-to-end bare-array usage: minimize ||p - target||² on one grid
    c = optimizer.OptConfig(lr=0.1, warmup_steps=0, total_steps=50,
                            weight_decay=0.1)
    target = jnp.asarray(np.random.default_rng(0).normal(size=(6, 6)),
                         jnp.float32)
    params = jnp.zeros((6, 6))
    state = optimizer.init(params)

    def loss(p):
        return jnp.sum((p - target) ** 2)

    l0 = float(loss(params))
    for s in range(50):
        g = jax.grad(loss)(params)
        params, state, _ = optimizer.apply(c, params, g, state,
                                           jnp.int32(s))
    assert float(loss(params)) < 0.05 * l0


def test_adamw_decreases_loss():
    state = train_loop.init_state(CFG, jax.random.PRNGKey(0))
    step = jax.jit(train_loop.make_train_step(CFG, _tc()))
    losses = []
    for s in range(30):
        state, m = step(state, _batch(s))
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.2, losses


def test_microbatching_matches_full_batch():
    """grad accumulation over 4 microbatches == single big batch update."""
    state1 = train_loop.init_state(CFG, jax.random.PRNGKey(0))
    state4 = jax.tree.map(jnp.copy, state1)
    step1 = jax.jit(train_loop.make_train_step(CFG, _tc(1)))
    step4 = jax.jit(train_loop.make_train_step(CFG, _tc(4)))
    b = _batch(0)
    s1, m1 = step1(state1, b)
    s4, m4 = step4(state4, b)
    assert abs(float(m1["loss"]) - float(m4["loss"])) < 5e-3
    for a, b2 in zip(jax.tree.leaves(s1["params"]),
                     jax.tree.leaves(s4["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b2, np.float32),
                                   rtol=2e-3, atol=2e-5)


# -- data ---------------------------------------------------------------------
def test_data_deterministic_and_stateless():
    fn = data.make_batch_fn(CFG, SHAPE, seed=3)
    a = fn(7)
    b = fn(7)
    c = fn(8)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert (a["tokens"] != c["tokens"]).any()
    # labels are next-token shifted
    fn0 = data.SyntheticLM(data.DataConfig(vocab=CFG.vocab, seq_len=16,
                                           global_batch=2, seed=0, noise=0.0))
    d = fn0.batch(0)
    assert ((5 * d["tokens"][:, 0] + 17) % CFG.vocab
            == d["labels"][:, 0]).all()


# -- checkpointing ------------------------------------------------------------
def test_checkpoint_roundtrip_atomic(tmp_path):
    state = train_loop.init_state(CFG, jax.random.PRNGKey(1))
    d = str(tmp_path / "ck")
    checkpoint.save(d, 10, state)
    checkpoint.save(d, 20, state)
    assert checkpoint.steps(d) == [10, 20]
    restored = checkpoint.restore(d, state, step=10)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    checkpoint.prune(d, keep=1)
    assert checkpoint.steps(d) == [20]


def test_checkpoint_restart_bitwise(tmp_path):
    """kill at step 7, restart → same final params as uninterrupted run."""
    d = str(tmp_path / "ck")
    step_jit = jax.jit(train_loop.make_train_step(CFG, _tc()))

    def init_fn():
        return train_loop.init_state(CFG, jax.random.PRNGKey(0))

    def one(state, step):
        state, _ = step_jit(state, _batch(step))
        return state

    inj = fault_tolerance.FailureInjector([7])
    final = fault_tolerance.run_with_restarts(
        init_fn=init_fn, step_fn=one, n_steps=12, ckpt_dir=d,
        ckpt_every=5, injector=inj)

    ref = init_fn()
    for s in range(12):
        ref = one(ref, s)
    for a, b in zip(jax.tree.leaves(final["params"]),
                    jax.tree.leaves(ref["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_elastic_reshard_restore(tmp_path):
    """checkpoint written unsharded restores onto a different mesh layout."""
    import subprocess, sys, textwrap
    d = str(tmp_path / "ck")
    state = train_loop.init_state(CFG, jax.random.PRNGKey(2))
    checkpoint.save(d, 1, state)
    code = textwrap.dedent(f"""
        import jax, numpy as np
        from repro.launch.mesh import make_mesh
        from repro import configs, sharding
        from repro.train import checkpoint, train_loop
        cfg = configs.tiny(configs.get("granite-8b"))
        mesh = make_mesh((2, 2), ("data", "model"))
        specs = train_loop.state_specs(cfg)
        shard = train_loop.state_shardings(cfg, mesh)
        st = checkpoint.restore({d!r}, specs, shardings=shard)
        leaf = st["params"]["final_norm"]["scale"]
        assert len(leaf.sharding.device_set) >= 1
        total = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(st))
        print("restored", total)
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr
    assert "restored" in r.stdout


def test_watchdog_flags_straggler():
    wd = fault_tolerance.Watchdog(threshold=3.0)
    for s in range(10):
        wd.observe(s, 0.1)
    ev = wd.observe(10, 1.0)
    assert ev is not None and ev.step == 10
    assert len(wd.events) == 1


# -- gradient compression -----------------------------------------------------
def test_int8_compression_roundtrip_and_neutrality():
    g = {"w": jnp.asarray(np.random.default_rng(0).standard_normal((64, 64)),
                          jnp.float32)}
    c = optimizer.compress_int8(g)
    back = optimizer.decompress_int8(c)
    err = float(jnp.max(jnp.abs(back["w"] - g["w"])))
    assert err <= float(jnp.max(jnp.abs(g["w"]))) / 127.0 + 1e-6

    # convergence-neutral on the smoke model: compressed-grad training
    # reaches a loss within 5% of exact-grad training
    def run(compress):
        tc = _tc()
        state = train_loop.init_state(CFG, jax.random.PRNGKey(0))
        base = train_loop.make_train_step(CFG, tc)

        def step(state, batch):
            return base(state, batch)

        if compress:
            grad_fn = jax.value_and_grad(
                lambda p, b: api.loss_fn(CFG, p, b)[0])

            def step(state, batch):  # noqa: F811
                loss, g = grad_fn(state["params"], batch)
                g = optimizer.decompress_int8(optimizer.compress_int8(g))
                new_p, new_o, m = optimizer.apply(
                    tc.opt, state["params"], g, state["opt"], state["step"])
                return ({"params": new_p, "opt": new_o,
                         "step": state["step"] + 1},
                        {"loss": loss, **m})

        step = jax.jit(step)
        for s in range(20):
            state, m = step(state, _batch(s))
        return float(m["loss"])

    exact = run(False)
    comp = run(True)
    assert abs(comp - exact) / abs(exact) < 0.05, (exact, comp)
