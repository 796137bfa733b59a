"""Full-waveform inversion through the differentiable fused timeloop.

The inversion loop Devito treats as the point of a stencil DSL: propagate
a source through a *guessed* velocity model with the 2-D acoustic leapfrog

    p_next = 2·p1 − p0 + (vp²·dt²)·Δp1

compare the resulting wavefield against data recorded in the *true*
model, and descend the misfit gradient — obtained by ``jax.grad``
straight through ``st.differentiable_timeloop`` (checkpointed O(√T)
adjoint, ``core/adjoint.py``) — with the repo's own AdamW
(``train/optimizer.py``, which must handle a bare velocity-grid parameter
tree).  The "observed" data come from the same propagator run on the true
model (an inversion crime, but exactly what validates the adjoint):

    PYTHONPATH=src python examples/fwi.py            # full inversion
    PYTHONPATH=src python examples/fwi.py --smoke    # CI: tiny + short
    PYTHONPATH=src python examples/fwi.py --smoke --mesh 4
                                       # same inversion, domain sharded
                                       # over 4 forced host devices

Full mode asserts the final misfit falls below 10% of the initial
misfit; smoke mode (a few iterations on a tiny grid) asserts it
decreases at all.  ``--mesh N`` decomposes the domain's first axis over
N devices (forcing N host devices when the platform has fewer) and runs
the identical forward + adjoint through the shard_mapped distributed
engine — gradients reach the sharded velocity model without gathering
the wavefield.
"""
import argparse
import os
import time

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny grid, few iterations (CI job)")
    ap.add_argument("--n", type=int, default=None, help="interior extent")
    ap.add_argument("--steps", type=int, default=None,
                    help="propagation time steps per shot")
    ap.add_argument("--iters", type=int, default=None,
                    help="optimizer iterations")
    ap.add_argument("--lr", type=float, default=None)
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="shard the domain over N devices (forces N host "
                         "devices if needed) and run the distributed "
                         "forward + adjoint")
    args = ap.parse_args()

    if args.mesh:
        # must precede the first jax import; forced host devices let CI
        # exercise the mesh path on one CPU process
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={args.mesh}")

    n = args.n or (16 if args.smoke else 48)
    steps = args.steps or (20 if args.smoke else 60)
    iters = args.iters or (8 if args.smoke else 120)
    lr = args.lr or 0.03

    import jax
    from repro.launch.mesh import make_mesh
    import jax.numpy as jnp

    from repro.core import acoustic, dsl as st
    from repro.train import optimizer as opt

    @st.kernel
    def wave2d(p0: st.grid, p1: st.grid, vp2: st.grid, dt: st.f32):
        lap = (p1.at(-1, 0) + p1.at(1, 0) + p1.at(0, -1) + p1.at(0, 1)
               - 4.0 * p1.at(0, 0))
        p0.at(0, 0).set(2.0 * p1.at(0, 0) - p0.at(0, 0)
                        + vp2.at(0, 0) * dt * dt * lap)

    shape = (n, n)
    dt = 0.35                                    # CFL-stable for vp ≤ 1.4
    src = (2, n // 2)                            # shot near the surface

    def between(t, grids):
        # Ricker source into the newest buffer (after the swap, "p1") —
        # pure jnp on g.data, so the hook is traceable and differentiable
        g = grids["p1"]
        idx = (g.order + src[0], g.order + src[1])
        g.data = g.data.at[idx].add(
            acoustic.source_wavelet(t, f0=0.06, t0=10))

    # true model: constant background + a fast inclusion to recover
    vp2_true = np.full(shape, 1.0, np.float32)
    yy, xx = np.mgrid[0:n, 0:n]
    blob = ((yy - n // 2) ** 2 + (xx - n // 2) ** 2) < (n // 6) ** 2
    vp2_true[blob] = 1.69                        # vp 1.0 → 1.3 inside

    def grids(vp2_interior):
        p0 = st.grid(st.f32, shape, order=1)
        p1 = st.grid(st.f32, shape, order=1)
        c = st.grid(st.f32, shape, order=1)
        c.interior = vp2_interior
        return p0, p1, c

    backend = mesh = None
    if args.mesh:
        if n % args.mesh:
            raise SystemExit(f"--mesh {args.mesh} must divide n={n}")
        mesh = make_mesh((args.mesh,), ("data",))
        backend = st.distributed(grid_axes=("data", None))

    p0, p1, c = grids(vp2_true)
    # fuse_steps=1: per-step source cadence; the adjoint thins its
    # checkpoints back to O(√steps) carries (fn.schedule shows the plan)
    fwd = st.differentiable_timeloop(wave2d, p0, p1, c, dt, steps=steps,
                                     swap=("p0", "p1"), fuse_steps=1,
                                     between=between,
                                     backend=backend, mesh=mesh)
    if args.mesh:
        print(f"distributed: axis 0 over {args.mesh} devices "
              f"({jax.device_count()} visible)")
    print(f"grid {shape}, {steps} steps, schedule: "
          f"stride={fwd.schedule['stride']} "
          f"checkpoints={fwd.schedule['checkpoints']} "
          f"of {len(fwd.schedule['windows'])} windows")

    observed = fwd()                             # data in the true model
    d_obs = {g: observed[g] for g in ("p0", "p1")}

    def misfit(vp2_interior):
        arrays = dict(fwd.arrays)
        arrays["vp2"] = arrays["vp2"].at[1:-1, 1:-1].set(vp2_interior)
        out = fwd(arrays, fwd.scalars)
        return 0.5 * sum(jnp.sum((out[g] - d_obs[g]) ** 2)
                         for g in ("p0", "p1"))

    cfg = opt.OptConfig(lr=lr, warmup_steps=5, total_steps=iters,
                        min_lr_ratio=0.3, weight_decay=0.1, clip_norm=10.0)
    params = jnp.full(shape, 1.0, jnp.float32)   # start from background
    state = opt.init(params)

    @jax.jit
    def update(params, state, step):
        loss, g = jax.value_and_grad(misfit)(params)
        params, state, metrics = opt.apply(cfg, params, g, state, step)
        return params, state, loss, metrics

    loss0 = None
    t0 = time.perf_counter()
    for i in range(iters):
        params, state, loss, metrics = update(params, state, jnp.int32(i))
        loss = float(loss)
        if loss0 is None:
            loss0 = loss
        if i % 10 == 0 or i == iters - 1:
            print(f"iter {i:4d}  misfit {loss:.6e}  "
                  f"({loss / loss0:6.1%} of initial)  "
                  f"|grad| {float(metrics['grad_norm']):.3e}")
    wall = time.perf_counter() - t0
    print(f"{iters} iterations in {wall:.1f}s")

    model_err0 = float(np.abs(vp2_true - 1.0).mean())
    model_err = float(jnp.abs(jnp.asarray(vp2_true) - params).mean())
    print(f"model error {model_err:.4f} (initial {model_err0:.4f})")

    if args.smoke:
        assert loss < loss0, f"misfit did not decrease: {loss0} -> {loss}"
        print(f"OK (smoke): misfit {loss0:.3e} -> {loss:.3e}")
    else:
        assert loss < 0.10 * loss0, \
            f"final misfit {loss:.3e} not < 10% of initial {loss0:.3e}"
        print(f"OK: final misfit {loss / loss0:.1%} of initial")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
