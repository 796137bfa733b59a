"""Training CLI driver.

Real-hardware entry point (and the smoke path used by examples/tests)::

    python -m repro.launch.train --arch mixtral-8x7b --steps 100 \
        --ckpt-dir /tmp/ckpt --preset smoke

``--preset smoke`` shrinks the arch to its reduced same-family config and
runs on the host devices; ``--preset full`` uses the real config and the
production mesh (requires a pod).
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs, sharding
from repro.configs.shapes import SHAPES
from repro.models import api
from repro.train import checkpoint, data, fault_tolerance, optimizer, train_loop


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x7b")
    ap.add_argument("--preset", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=None)
    ap.add_argument("--global-batch", type=int, default=None)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=1)
    args = ap.parse_args(argv)

    cfg = configs.get(args.arch)
    if args.preset == "smoke":
        cfg = configs.tiny(cfg)
        seq = args.seq_len or 128
        gb = args.global_batch or 8
        mesh = None
    else:
        from repro.launch.mesh import make_production_mesh
        mesh = make_production_mesh()
        seq = args.seq_len or SHAPES["train_4k"].seq_len
        gb = args.global_batch or SHAPES["train_4k"].global_batch

    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=seq,
                                global_batch=gb)
    batch_fn = data.make_batch_fn(cfg, shape, seed=args.seed)

    oc = optimizer.OptConfig(lr=args.lr, warmup_steps=args.warmup,
                             total_steps=max(args.steps, 1))
    tc = train_loop.TrainConfig(opt=oc, n_microbatches=args.microbatches)
    step_fn = train_loop.make_train_step(cfg, tc)
    if mesh is not None:
        st_shard = train_loop.state_shardings(cfg, mesh)
        jitted = jax.jit(step_fn, in_shardings=(st_shard, None),
                         out_shardings=(st_shard, None),
                         donate_argnums=(0,))
    else:
        jitted = jax.jit(step_fn, donate_argnums=(0,))

    def init_fn():
        return train_loop.init_state(cfg, jax.random.PRNGKey(args.seed))

    losses = []

    def one_step(state, step):
        batch = {k: jnp.asarray(v) for k, v in batch_fn(step).items()}
        state, metrics = jitted(state, batch)
        if step % args.log_every == 0:
            loss = float(metrics["loss"])
            losses.append(loss)
            print(f"step {step:5d}  loss {loss:8.4f}  "
                  f"gnorm {float(metrics['grad_norm']):7.3f}  "
                  f"lr {float(metrics['lr']):.2e}", flush=True)
        return state

    t0 = time.perf_counter()
    if args.ckpt_dir:
        wd = fault_tolerance.Watchdog()
        state = fault_tolerance.run_with_restarts(
            init_fn=init_fn, step_fn=one_step, n_steps=args.steps,
            ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every, watchdog=wd)
        if wd.events:
            print(f"straggler events: {len(wd.events)}")
    else:
        state = init_fn()
        for step in range(args.steps):
            state = one_step(state, step)
    dt = time.perf_counter() - t0
    print(f"done: {args.steps} steps in {dt:.1f}s "
          f"({dt / max(args.steps, 1):.2f} s/step)")
    return losses


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
