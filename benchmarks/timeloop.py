"""Fused vs per-step time-loop benchmark (the paper's time-to-solution
metric over many steps, §6.2 Tables 6–8 measured end-to-end).

For star2d1r and the acoustic-ISO 25-point stencil it runs N time steps

  * per-step: the classic ``@st.target`` Python loop — one compiled call,
    one host↔device sync and one dict repack per step, and
  * fused: ``st.timeloop`` — the whole loop traced once into a single
    ``lax.fori_loop`` program (one window),

and reports steps/s and time-to-solution.  The pallas rows (interpret
mode on CPU) sweep the in-kernel temporal-blocking depth ``time_block``
and report the plan's modeled ``hbm_bytes_per_step`` next to wall clock,
so the k× HBM-traffic reduction is visible even where interpret-mode
timing is noisy.  Results are written to ``BENCH_timeloop.json`` so the
perf trajectory is tracked across PRs (CI guards the machine-independent
speedup ratios and the modeled HBM reduction against the committed
baselines — see ``benchmarks/check_regression.py``).

    PYTHONPATH=src python -m benchmarks.timeloop [--fast]
"""
from __future__ import annotations

import json
import os
import time
from typing import Dict

import numpy as np

from repro.core import acoustic, autotune, cost_model, dsl as st, suite
from repro.kernels.stencil import codegen

OUT_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_timeloop.json")


def _bench_star2d1r(steps: int, shape, repeats: int = 3) -> Dict:
    k = suite.get_kernel("star2d1r")
    swap = suite.swap_pair(k.name)

    def mk():
        return suite.make_grids("star2d1r", shape=shape)

    @st.target
    def per_step(u, v, iters):
        for _ in range(iters):
            st.map(e=u.shape)(k)(u, v)
            (u.data, v.data) = (v.data, u.data)

    def fused(u, v, iters):
        return st.timeloop(iters, swap=swap)(k)(u, v)

    run = st.launch(backend=st.xla())

    def time_once(tgt):
        g = mk()
        run(tgt)(*g.values(), 2)             # warmup: codegen + compile
        best = float("inf")
        for _ in range(repeats):
            g = mk()
            t0 = time.perf_counter()
            run(tgt)(*g.values(), steps)
            best = min(best, time.perf_counter() - t0)
        return best

    t_unfused = time_once(per_step)
    t_fused = time_once(fused)
    return {
        "kernel": "star2d1r", "backend": "xla", "shape": list(shape),
        "steps": steps,
        "unfused_seconds": t_unfused,
        "fused_seconds": t_fused,
        "unfused_steps_per_s": steps / t_unfused,
        "fused_steps_per_s": steps / t_fused,
        "speedup": t_unfused / t_fused,
    }


def _bench_pallas_sweep(name: str, steps: int, shape, repeats: int = 5,
                        time_blocks=(1, 2, 4)) -> Dict:
    """Fused pallas path (interpret on CPU) across temporal depths: wall
    clock plus the plan's modeled HBM bytes per step — the k× traffic
    reduction is the column that carries to real TPUs.  Used for the 5-pt
    star2d1r and the paper's headline 25-point star3d4r (whose order-4
    halo needs a domain that admits the k·h=16 expanded window at k=4)."""
    k = suite.get_kernel(name)
    swap = suite.swap_pair(k.name)
    halos = {g: k.info.halo for g in k.ir.grid_params}
    if shape is None:  # the suite's per-order default
        shape = next(iter(suite.make_grids(name).values())).shape
    rows = {}
    for tb in time_blocks:
        backend = st.pallas(template="gmem", time_block=tb)
        plan = codegen.plan_pallas(k.ir, halos, tuple(shape), backend,
                                   swap=swap)

        def fused(*args):
            return st.timeloop(steps, swap=swap)(k)(*args)

        run = st.launch(backend=backend)
        g = suite.make_grids(name, shape=shape)
        run(fused)(*g.values())          # warmup compiles the real window
        best = float("inf")
        for _ in range(repeats):
            g = suite.make_grids(name, shape=shape)
            t0 = time.perf_counter()
            run(fused)(*g.values())
            best = min(best, time.perf_counter() - t0)
        rows[f"time_block_{tb}"] = {
            "kernel": name, "backend": "pallas_interpret",
            "template": "gmem", "time_block": tb, "shape": list(shape),
            "steps": steps,
            "fused_seconds": best,
            "fused_steps_per_s": steps / best,
            "hbm_bytes_per_step": plan.hbm_bytes_per_step(),
            "grid_reads_per_step": plan.grid_reads_per_step,
            "grid_writes_per_step": plan.grid_writes_per_step,
        }
    base = rows.get("time_block_1")
    if base:
        for r in rows.values():
            r["speedup_vs_time_block_1"] = (base["fused_seconds"]
                                            / r["fused_seconds"])
            r["hbm_reduction_vs_time_block_1"] = (
                base["hbm_bytes_per_step"] / r["hbm_bytes_per_step"])
    return rows


def _bench_acoustic(steps: int, shape, repeats: int = 2) -> Dict:
    def time_once(fuse):
        acoustic.run(shape=shape, iters=2, with_source=False,
                     fuse_steps=fuse)   # warmup
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            acoustic.run(shape=shape, iters=steps, with_source=False,
                         fuse_steps=fuse)
            best = min(best, time.perf_counter() - t0)
        return best

    t_unfused = time_once(None)
    t_fused = time_once(steps)
    return {
        "kernel": "acoustic_iso_3d", "backend": "xla", "shape": list(shape),
        "steps": steps,
        "unfused_seconds": t_unfused,
        "fused_seconds": t_fused,
        "unfused_steps_per_s": steps / t_unfused,
        "fused_steps_per_s": steps / t_fused,
        "speedup": t_unfused / t_fused,
    }


def _bench_gradient_throughput(name: str, shape, steps: int,
                               repeats: int = 3) -> Dict:
    """Adjoint cost of the differentiable timeloop: jitted forward vs
    jitted loss+gradient wall clock on the same window schedule, plus the
    schedule's checkpoint count against the ⌈√T⌉ bound.  The
    machine-independent columns CI guards are ``fwd_over_grad`` (the
    checkpointed backward replays each window once and runs its VJP once,
    so grad should stay within a small constant factor of forward — it
    collapses if the adjoint degrades to O(T) residuals or re-replays
    segments) and the ``sqrt_checkpoint_bound`` / ``grad_finite``
    booleans."""
    import jax
    import jax.numpy as jnp
    from repro.core import adjoint, timeloop as tl

    k = suite.get_kernel(name)
    grids = suite.make_grids(name, shape=shape)
    eng = tl.TimeloopEngine(k.ir, {n: g.halo for n, g in grids.items()},
                            tuple(shape), st.xla(),
                            swap=suite.swap_pair(name), differentiable=True)
    fn = adjoint.differentiable_run(eng, steps)
    arrays = {n: g.data for n, g in grids.items()}

    fwd = jax.jit(lambda a: fn(a, {}))
    grad = jax.jit(jax.grad(lambda a: sum(jnp.sum(o ** 2)
                                          for o in fn(a, {}).values())))

    def time_once(f):
        jax.block_until_ready(f(arrays))     # compile + warm
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(f(arrays))
            best = min(best, time.perf_counter() - t0)
        return best

    t_fwd = time_once(fwd)
    t_grad = time_once(grad)
    g = grad(arrays)
    finite = all(bool(np.isfinite(np.asarray(v)).all()) for v in g.values())
    bound = adjoint.ceil_sqrt(steps) + 1
    return {
        "kernel": name, "backend": "xla", "shape": list(shape),
        "steps": steps,
        "fwd_seconds": t_fwd,
        "grad_seconds": t_grad,
        "fwd_steps_per_s": steps / t_fwd,
        "grad_steps_per_s": steps / t_grad,
        "fwd_over_grad": t_fwd / t_grad,
        "checkpoints": fn.schedule["checkpoints"],
        "windows": len(fn.schedule["windows"]),
        "sqrt_checkpoint_bound": bool(fn.schedule["checkpoints"] <= bound),
        "grad_finite": finite,
    }


def _bench_predicted_vs_measured(name: str, shape, steps: int,
                                 space, fuse_space, time_block_space,
                                 top_k: int = 3) -> Dict:
    """Cost-model quality on a real search space: run the exhaustive
    search (top_k=None) and the two-stage pruned search over the same
    candidates, then compare the two winners with the *exhaustive* run's
    measurements (same-run numbers, so the ratio is not same-candidate
    noise).  ``best_in_top_k`` / ``two_stage_within_10pct`` /
    ``measured_at_most_top_k`` are the machine-independent booleans CI
    guards."""
    k = suite.get_kernel(name)
    swap = suite.swap_pair(name)
    model = cost_model.default_model()

    def grids():
        return suite.make_grids(name, shape=shape)

    def search(top):
        autotune.clear_cache()
        autotune.reset_measure_count()
        res = autotune.tune(k, grids(), iters=1, space=space, swap=swap,
                            steps=steps, fuse_space=fuse_space,
                            time_block_space=time_block_space,
                            top_k=top, cost_model=model)
        return res, dict(autotune.MEASURE_COUNT)

    exhaustive, _ = search(None)
    two_stage, counts = search(top_k)

    def trial_key(backend, fuse):
        return (backend.cache_key(), fuse)

    ex_by_key = {trial_key(b, f): dt for b, f, dt in exhaustive.trials}
    ts_in_ex = ex_by_key.get(trial_key(two_stage.backend,
                                       two_stage.fuse_steps))
    ratio = (ts_in_ex / exhaustive.seconds
             if ts_in_ex is not None and exhaustive.seconds > 0 else None)
    n_cands = len(exhaustive.trials)
    rank = exhaustive.rank_error
    return {
        "kernel": name, "shape": list(shape), "steps": steps,
        "candidates": n_cands,
        "top_k": top_k,
        "exhaustive_best_seconds": exhaustive.seconds,
        "exhaustive_best_backend": str(exhaustive.backend),
        "exhaustive_best_fuse": exhaustive.fuse_steps,
        "two_stage_best_seconds": two_stage.seconds,
        "two_stage_best_backend": str(two_stage.backend),
        "two_stage_best_fuse": two_stage.fuse_steps,
        "two_stage_best_seconds_in_exhaustive": ts_in_ex,
        "two_stage_vs_exhaustive": ratio,
        "two_stage_within_10pct": bool(ratio is not None and ratio <= 1.10),
        "rank_of_measured_best": rank,
        "best_in_top_k": bool(rank is not None and rank < top_k),
        "measured_candidates_two_stage": counts["measured_candidates"],
        "pruned_candidates": counts["pruned_candidates"],
        "measured_at_most_top_k": bool(
            counts["measured_candidates"] <= top_k
            + sum(1 for _, _, p in two_stage.predicted if p is None)),
    }


def run(fast: bool = False, verbose: bool = True) -> Dict[str, Dict]:
    steps = 30 if fast else 100
    results = {
        "star2d1r": _bench_star2d1r(steps, (128, 128) if fast else (256, 256)),
        "acoustic_iso_3d": _bench_acoustic(
            steps, (24, 24, 24) if fast else (48, 48, 48)),
        "star2d1r_pallas": _bench_pallas_sweep(
            "star2d1r", 10 if fast else 24,
            (64, 64) if fast else (128, 128)),
        # the paper's headline 25-point star: suite default (32, 32, 64)
        # admits the full time_block ∈ {1, 2, 4} sweep (k·h = 16 ≤ block)
        "star3d4r_pallas": _bench_pallas_sweep(
            "star3d4r", 4 if fast else 8, None, repeats=1 if fast else 2),
        # adjoint throughput: forward vs checkpointed gradient (CI guards
        # fwd_over_grad and the √T-checkpoint / finite-grad booleans)
        "gradient_throughput": {
            "star2d1r": _bench_gradient_throughput(
                "star2d1r", (64, 64) if fast else (128, 128),
                16 if fast else 64),
        },
        # two-stage autotuner quality: exhaustive vs cost-model-pruned
        # search over mixed xla/pallas spaces (CI guards the booleans)
        "predicted_vs_measured": {
            "star2d1r": _bench_predicted_vs_measured(
                "star2d1r", (48, 48), 8,
                space=[st.xla(), st.pallas(template="gmem"),
                       st.pallas(template="smem")],
                fuse_space=(1, 8), time_block_space=(1, 2)),
            "star3d4r": _bench_predicted_vs_measured(
                "star3d4r", (16, 16, 32), 4,
                space=[st.xla(), st.pallas(template="gmem")],
                fuse_space=(1, 4), time_block_space=(1, 2)),
        },
    }
    with open(OUT_PATH, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
    if verbose:
        for name, r in results.items():
            if name == "predicted_vs_measured":
                for key, row in sorted(r.items()):
                    print(f"{name:16s} {key:13s} "
                          f"measured {row['measured_candidates_two_stage']}"
                          f"/{row['candidates']} cands  "
                          f"rank-of-best {row['rank_of_measured_best']}  "
                          f"vs exhaustive "
                          f"{row['two_stage_vs_exhaustive']:.3f}x",
                          flush=True)
            elif name == "gradient_throughput":
                for key, row in sorted(r.items()):
                    print(f"{name:16s} {key:13s} "
                          f"fwd {row['fwd_steps_per_s']:8.1f} steps/s  "
                          f"grad {row['grad_steps_per_s']:8.1f} steps/s  "
                          f"({row['fwd_over_grad']:.2f}x, "
                          f"{row['checkpoints']}/{row['windows']} ckpts)",
                          flush=True)
            elif "unfused_steps_per_s" in r:
                print(f"{name:16s} {r['steps']:4d} steps  "
                      f"per-step {r['unfused_steps_per_s']:8.1f} steps/s  "
                      f"fused {r['fused_steps_per_s']:8.1f} steps/s  "
                      f"speedup {r['speedup']:.2f}x", flush=True)
            else:
                for key, row in sorted(r.items()):
                    print(f"{name:16s} {key:13s} "
                          f"{row['fused_steps_per_s']:8.1f} steps/s  "
                          f"hbm/step {row['hbm_bytes_per_step']:10.0f} B  "
                          f"({row.get('speedup_vs_time_block_1', 1.0):.2f}x "
                          "vs k=1)", flush=True)
        print(f"wrote {OUT_PATH}")
    return results


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    return run(fast=args.fast)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
