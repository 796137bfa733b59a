"""End-to-end training driver: trains a ~100M-param dense LM for a few
hundred steps with the full substrate — synthetic deterministic data,
AdamW + warmup-cosine, microbatched grad accumulation, checkpoint/restart,
straggler watchdog.

    PYTHONPATH=src python examples/train_lm.py --preset smoke   # ~1 min CPU
    PYTHONPATH=src python examples/train_lm.py --preset 100m    # the real
        # ~100M config, a few hundred steps; sized for a single accelerator
        # or a small mesh — on CPU expect hours, on TPU minutes.

The production-scale path (assigned archs, pod meshes) is
``python -m repro.launch.train --preset full``.
"""
import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.configs.base import ModelConfig, register
from repro.configs.shapes import ShapeSpec
from repro.train import data, fault_tolerance, optimizer, train_loop

# ~100M dense transformer (GPT-2-medium-ish, swiglu/rope/rmsnorm)
LM_100M = ModelConfig(
    name="lm-100m", family="dense", n_layers=12, d_model=768, n_heads=12,
    n_kv_heads=12, d_ff=2048, vocab=32768, act="swiglu", remat=False,
    scan_layers=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="smoke", choices=["smoke", "100m"])
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()

    if args.preset == "smoke":
        cfg = dataclasses.replace(
            LM_100M, name="lm-smoke", n_layers=2, d_model=128, n_heads=4,
            n_kv_heads=4, d_ff=256, vocab=512)
        steps = args.steps or 60
        shape = ShapeSpec("train", "train", seq_len=64, global_batch=8)
    else:
        cfg = LM_100M
        steps = args.steps or 300
        shape = ShapeSpec("train", "train", seq_len=512, global_batch=16)

    from repro.models import api
    print(f"{cfg.name}: {api.param_count(cfg) / 1e6:.1f}M params, "
          f"{steps} steps @ {shape.global_batch}×{shape.seq_len}")

    batch_fn = data.make_batch_fn(cfg, shape, seed=0)
    tc = train_loop.TrainConfig(
        opt=optimizer.OptConfig(lr=3e-4, warmup_steps=20, total_steps=steps),
        n_microbatches=args.microbatches)
    step_jit = jax.jit(train_loop.make_train_step(cfg, tc),
                       donate_argnums=(0,))

    def init_fn():
        return train_loop.init_state(cfg, jax.random.PRNGKey(0))

    losses = []

    def one(state, step):
        state, m = step_jit(state, {k: jnp.asarray(v)
                                    for k, v in batch_fn(step).items()})
        loss = float(m["loss"])
        losses.append(loss)
        if step % 10 == 0 or step == steps - 1:
            print(f"step {step:4d}  loss {loss:7.4f}  "
                  f"lr {float(m['lr']):.2e}", flush=True)
        return state

    if args.ckpt_dir:
        wd = fault_tolerance.Watchdog()
        fault_tolerance.run_with_restarts(
            init_fn=init_fn, step_fn=one, n_steps=steps,
            ckpt_dir=args.ckpt_dir, ckpt_every=50, watchdog=wd)
    else:
        state = init_fn()
        for s in range(steps):
            state = one(state, s)

    first, last = np.mean(losses[:10]), np.mean(losses[-10:])
    print(f"loss {first:.3f} → {last:.3f} "
          f"({'improved' if last < first else 'NO IMPROVEMENT'})")
    assert last < first, "training failed to reduce loss"
    print("OK")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
