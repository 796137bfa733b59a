"""Distributed stencil benchmark: the fused sharded timeloop on 8
simulated host devices (``XLA_FLAGS=--xla_force_host_platform_device_count=8``;
the subprocess exists because the main process must keep 1 device per the
dry-run contract).  Emits ``BENCH_distributed.json`` with five sections:

* ``fused_vs_per_window`` — the tentpole ratio: W steps as ONE
  shard_mapped program (fori_loop over exchange groups) vs the same
  steps as one dispatched program *per exchange group* (the old
  per-window path).  Same depth, same numerics, same run — the speedup
  is dimensionless and machine-independent, so CI guards it.
* ``scaling`` — weak and strong ladders over 1/2/4/8-device sub-meshes
  (``launch.mesh.make_scaling_mesh``).  steps/s is absolute (never
  guarded); the modeled collective bytes per window come from
  ``HaloSpec`` and are deterministic, so CI compares them *exactly*.
* ``collective_model`` — the HLO cross-check: compiled-program
  collective traffic (``launch.hlo_analysis``) must equal
  ``HaloSpec.window_collective_bytes`` for several (window, depth)
  schedules.  Booleans, guarded absolutely.
* ``predicted_vs_measured_mesh`` — the distributed cost model in the
  two-stage tuner: over a mesh-inclusive space every candidate is
  predicted, at most top-K are measured, and distributed rows are
  pruned analytically instead of forcing measurement.
* ``gradient_scaling`` — the distributed adjoint: same-run forward vs
  checkpointed-gradient throughput of ``st.differentiable_timeloop``'s
  engine over 1/2/4/8-device sub-meshes (CI guards the dimensionless
  ``fwd_over_grad`` ratio plus the finite-gradient / √T-checkpoint
  booleans), and the adjoint HLO cross-check: the compiled backward
  program's collective bytes must equal the *transposed* exchange
  geometry's model (``fn.spec_T.window_collective_bytes``) exactly —
  the reverse-ppermute slabs are the forward slabs, direction
  inverted, so the modeled series is guarded byte-exact like the
  forward one.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from typing import Dict

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(_ROOT, "src")
OUT_PATH = os.path.join(_ROOT, "BENCH_distributed.json")

_CODE = """
import json, time
import jax, numpy as np, jax.numpy as jnp
from repro.core import dsl as st, suite, autotune, cost_model
from repro.core import distributed as dist
from repro.launch import hlo_analysis
from repro.launch.mesh import make_scaling_mesh

FAST = {fast}
KERNEL = "star2d2r"
k = suite.get_kernel(KERNEL)
SWAP = suite.swap_pair(KERNEL)
HALOS = {{g: k.info.halo for g in k.ir.grid_params}}
ITEM = 4
REPS = 2 if FAST else 3
STEPS = 8 if FAST else 16
WINDOW, TS = 4, 2
STRONG = (128, 128) if FAST else (256, 256)
WEAK_LOCAL = (16, 128) if FAST else (32, 128)

assert len(jax.devices()) == 8, jax.devices()


def mk_arrays(shape, seed=0):
    gs = {{g: st.grid(np.float32, shape, k.info.order).randomize(seed + i)
          for i, g in enumerate(k.ir.grid_params)}}
    return {{g: jnp.asarray(v.data) for g, v in gs.items()}}


def time_best(fn):
    fn()                                   # warmup: compile + first run
    best = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(list(out.values()))
        best = min(best, time.perf_counter() - t0)
    return best, out


# -- 1. fused single-program window vs per-group dispatch -------------------
mesh8 = make_scaling_mesh(8)
be_fused = st.distributed(grid_axes=("data", None), time_steps=TS)
fused_fn = dist.lower_distributed_window(k.ir, STRONG, be_fused, mesh8,
                                         SWAP, WINDOW)
be_group = st.distributed(grid_axes=("data", None), time_steps=TS,
                          swap=SWAP)
group_fn = dist.lower_distributed(k.ir, HALOS, STRONG, None, be_group, mesh8)
arrays = mk_arrays(STRONG)
scal = {{}}

def run_fused():
    a = dict(arrays)
    for _ in range(STEPS // WINDOW):
        a = fused_fn(a, scal)
    return a

def run_per_group():
    a = dict(arrays)
    for _ in range(STEPS // TS):       # one dispatched program per group
        a = group_fn(a, scal)
    return a

t_fused, out_f = time_best(run_fused)
t_group, out_g = time_best(run_per_group)
err = max(float(jnp.abs(out_f[g] - out_g[g]).max()) for g in SWAP)
assert err < 1e-5, err
fused_vs_per_window = {{
    "kernel": KERNEL, "shape": list(STRONG), "steps": STEPS,
    "window": WINDOW, "depth": TS, "devices": 8,
    "fused_seconds": t_fused, "per_window_seconds": t_group,
    "fused_steps_per_s": STEPS / t_fused,
    "per_window_steps_per_s": STEPS / t_group,
    "speedup": t_group / t_fused,
    "max_err_fused_vs_per_window": err,
}}
print("fused vs per-window:", round(fused_vs_per_window["speedup"], 2), "x",
      flush=True)


# -- 2. weak/strong scaling over 1/2/4/8-device sub-meshes ------------------
def scale_row(n, shape):
    mesh = make_scaling_mesh(n)
    fn = dist.lower_distributed_window(
        k.ir, shape, st.distributed(grid_axes=("data", None), time_steps=TS),
        mesh, SWAP, WINDOW)
    a0 = mk_arrays(shape)

    def run():
        a = dict(a0)
        for _ in range(STEPS // WINDOW):
            a = fn(a, scal)
        return a

    secs, _ = time_best(run)
    return {{
        "devices": n, "global_shape": list(shape),
        "local_shape": list(fn.local_shape),
        "seconds": secs, "steps_per_s": STEPS / secs,
        "modeled_collective_bytes_per_window":
            fn.spec.window_collective_bytes(WINDOW, ITEM),
        "modeled_collective_bytes_per_step":
            fn.spec.window_collective_bytes(WINDOW, ITEM) / WINDOW,
    }}

scaling = {{"strong": {{}}, "weak": {{}}}}
for n in (1, 2, 4, 8):
    scaling["strong"][str(n)] = scale_row(n, STRONG)
    scaling["weak"][str(n)] = scale_row(n, (WEAK_LOCAL[0] * n, WEAK_LOCAL[1]))
    print(f"scaling n={{n}}: strong "
          f"{{scaling['strong'][str(n)]['steps_per_s']:.1f}} steps/s, weak "
          f"{{scaling['weak'][str(n)]['steps_per_s']:.1f}} steps/s",
          flush=True)


# -- 3. modeled vs compiled-HLO collective bytes ----------------------------
def hlo_row(window, ts):
    be = st.distributed(grid_axes=("data", None), time_steps=ts)
    fn = dist.lower_distributed_window(k.ir, STRONG, be, mesh8, SWAP, window)
    a0 = mk_arrays(STRONG)
    interiors = {{g: a[tuple(slice(k.info.order, k.info.order + s)
                             for s in STRONG)]
                 for g, a in a0.items()}}
    hlo = fn.jitted.lower(interiors, scal).compile().as_text()
    measured = hlo_analysis.op_stats(hlo, n_devices=8).collective_bytes
    modeled = fn.spec.window_collective_bytes(window, ITEM)
    return {{"window": window, "depth": fn.depth,
             "modeled_bytes": modeled, "hlo_bytes": measured,
             "match": bool(measured == modeled)}}

collective_model = {{
    "w4_d2": hlo_row(4, 2),
    "w5_d2": hlo_row(5, 2),          # indivisible: unrolled remainder group
    "w6_d3": hlo_row(6, 3),
}}
for name, row in sorted(collective_model.items()):
    print(f"collective model {{name}}: modeled={{row['modeled_bytes']}} "
          f"hlo={{row['hlo_bytes']}} match={{row['match']}}", flush=True)


# -- 4. two-stage tuning over a mesh-inclusive space ------------------------
autotune.clear_cache()
autotune.reset_measure_count()
model = cost_model.CostModel(calibrate=False)
tune_shape = (64, 64)
grids = {{g: st.grid(st.f32, tune_shape, k.info.order).randomize(i)
         for i, g in enumerate(k.ir.grid_params)}}
dax = ("data", None)
space = [st.xla(),
         (st.distributed(grid_axes=dax), 8),
         (st.distributed(grid_axes=dax, time_steps=2), 8),
         (st.distributed(grid_axes=dax, time_steps=4), 8)]
TOP_K = 2
res = autotune.tune(k, grids, iters=1, space=space, swap=SWAP, steps=8,
                    fuse_space=(1, 8), time_block_space=(1,), top_k=TOP_K,
                    cost_model=model, mesh=mesh8)
counts = dict(autotune.MEASURE_COUNT)
measured_keys = {{(b.cache_key(), f) for b, f, _dt in res.trials}}
dist_rows = [(b, f, p) for b, f, p in res.predicted
             if getattr(b, "kind", None) == "distributed"]
dist_pruned = sum(1 for b, f, _p in dist_rows
                  if (b.cache_key(), f) not in measured_keys)
predicted_vs_measured_mesh = {{
    "kernel": KERNEL, "shape": list(tune_shape), "steps": 8,
    "candidates": len(res.predicted), "top_k": TOP_K,
    "distributed_candidates": len(dist_rows),
    "distributed_pruned": dist_pruned,
    "measured_candidates": counts["measured_candidates"],
    "pruned_candidates": counts["pruned_candidates"],
    "rank_of_measured_best": res.rank_error,
    "best_backend": str(res.backend),
    "all_candidates_predicted":
        bool(all(p is not None for _b, _f, p in res.predicted)),
    "best_in_top_k": bool(res.rank_error is not None
                          and res.rank_error < TOP_K),
    "measured_at_most_top_k":
        bool(counts["measured_candidates"] <= TOP_K),
    "distributed_pruning_active":
        bool(dist_pruned > 0
             and all(p is not None for _b, _f, p in res.predicted)),
}}
print("mesh tune: measured", counts["measured_candidates"], "of",
      len(res.predicted), "rank-of-best", res.rank_error, flush=True)


# -- 5. distributed adjoint: fwd vs gradient over sub-meshes ----------------
from repro.core import adjoint, timeloop as tl

GRAD_STEPS = 8 if FAST else 16
GRAD_WINDOW = 2


def grad_row(n):
    mesh = make_scaling_mesh(n)
    eng = tl.TimeloopEngine(
        k.ir, HALOS, STRONG,
        st.distributed(grid_axes=("data", None), time_steps=TS),
        swap=SWAP, mesh=mesh, differentiable=True)
    fn = adjoint.differentiable_run(eng, GRAD_STEPS, GRAD_WINDOW)
    arrays = mk_arrays(STRONG)

    fwd = jax.jit(lambda a: fn(a, {{}}))
    grad = jax.jit(jax.grad(lambda a: sum(jnp.sum(o ** 2)
                                          for o in fn(a, {{}}).values())))

    def time_once(f):
        jax.block_until_ready(f(arrays))       # compile + warm
        best = float("inf")
        for _ in range(REPS):
            t0 = time.perf_counter()
            jax.block_until_ready(f(arrays))
            best = min(best, time.perf_counter() - t0)
        return best

    t_fwd = time_once(fwd)
    t_grad = time_once(grad)
    g = grad(arrays)
    finite = all(bool(np.isfinite(np.asarray(v)).all()) for v in g.values())
    bound = adjoint.ceil_sqrt(GRAD_STEPS // GRAD_WINDOW) + 1
    return {{
        "devices": n, "global_shape": list(STRONG), "steps": GRAD_STEPS,
        "window": GRAD_WINDOW, "depth": TS,
        "fwd_seconds": t_fwd, "grad_seconds": t_grad,
        "fwd_steps_per_s": GRAD_STEPS / t_fwd,
        "grad_steps_per_s": GRAD_STEPS / t_grad,
        "fwd_over_grad": t_fwd / t_grad,
        "checkpoints": fn.schedule["checkpoints"],
        "windows": len(fn.schedule["windows"]),
        "sqrt_checkpoint_bound": bool(fn.schedule["checkpoints"] <= bound),
        "grad_finite": finite,
    }}


def adjoint_hlo_row(window, ts):
    # collective bytes of the compiled BACKWARD program vs the transposed
    # spec's model; for this linear kernel XLA DCEs the primal chain the
    # vjp re-linearizes, leaving exactly the reverse-ppermute exchanges
    be = st.distributed(grid_axes=("data", None), time_steps=ts)
    fn = dist.lower_distributed_window(k.ir, STRONG, be, mesh8, SWAP,
                                       window, differentiable=True)
    a0 = mk_arrays(STRONG)
    interiors = {{g: a[tuple(slice(k.info.order, k.info.order + s)
                             for s in STRONG)]
                 for g, a in a0.items()}}
    cot = {{g: interiors[g] for g in SWAP}}
    hlo = fn.bwd_jitted.lower(interiors, cot, scal).compile().as_text()
    measured = hlo_analysis.op_stats(hlo, n_devices=8).collective_bytes
    modeled = fn.spec_T.window_collective_bytes(window, ITEM)
    return {{"window": window, "depth": fn.depth,
             "modeled_adjoint_bytes": modeled, "hlo_bytes": measured,
             "match": bool(measured == modeled)}}


gradient_scaling = {{
    "throughput": {{}},
    "adjoint_collective_model": {{
        "w4_d2": adjoint_hlo_row(4, 2),
        "w5_d2": adjoint_hlo_row(5, 2),
        "w6_d3": adjoint_hlo_row(6, 3),
    }},
}}
for n in (1, 2, 4, 8):
    row = grad_row(n)
    gradient_scaling["throughput"][str(n)] = row
    print(f"gradient n={{n}}: fwd {{row['fwd_steps_per_s']:.1f}} steps/s, "
          f"grad {{row['grad_steps_per_s']:.1f}} steps/s "
          f"({{row['fwd_over_grad']:.2f}}x)", flush=True)
for name, row in sorted(gradient_scaling["adjoint_collective_model"].items()):
    print(f"adjoint collective model {{name}}: "
          f"modeled={{row['modeled_adjoint_bytes']}} "
          f"hlo={{row['hlo_bytes']}} match={{row['match']}}", flush=True)

print("JSON_RESULT " + json.dumps({{
    "fused_vs_per_window": fused_vs_per_window,
    "scaling": scaling,
    "collective_model": collective_model,
    "predicted_vs_measured_mesh": predicted_vs_measured_mesh,
    "gradient_scaling": gradient_scaling,
}}))
"""


def run(fast: bool = False, verbose: bool = True) -> Dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = _SRC
    code = textwrap.dedent(_CODE.format(fast=repr(bool(fast))))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=1800)
    if r.returncode != 0:
        raise RuntimeError(f"distributed benchmark failed:\n{r.stdout}\n"
                           f"{r.stderr}")
    if verbose:
        for line in r.stdout.splitlines():
            if not line.startswith("JSON_RESULT"):
                print(line, flush=True)
    payload = [l for l in r.stdout.splitlines()
               if l.startswith("JSON_RESULT")]
    results = json.loads(payload[0][len("JSON_RESULT "):])
    with open(OUT_PATH, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
    return results


def main():
    return run()


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
