"""Paper §6.1 / Table 4: numerical accuracy of generated code.

Runs the 20-kernel suite (star/box × 2D/3D × order 1–4 + Jacobi) through
every backend/template/mem-type variant and reports max-error + RMSD
against the reference lowering (the paper's OpenMP-reference analogue).
The paper's acceptance bar: max err ~1e-7, RMSD ~1e-8 (f32).
"""
from __future__ import annotations

import itertools
import time
from typing import Dict, List

import jax.numpy as jnp
import numpy as np

from repro.core import dsl as st, suite
from repro.kernels.stencil import ops, ref

SHAPE_2D = (40, 56)
SHAPE_3D = (16, 24, 40)
TEMPLATES = ("gmem", "smem", "f4", "shift", "unroll", "semi")


def _arrays(kernel, interior, seed=0):
    rng = np.random.default_rng(seed)
    halos = {g: kernel.info.halo for g in kernel.ir.grid_params}
    arrays = {}
    for g in kernel.ir.grid_params:
        full = tuple(s + 2 * h for s, h in zip(interior, halos[g]))
        arrays[g] = jnp.asarray(rng.standard_normal(full), jnp.float32)
    return arrays, halos


def variants_for(kernel):
    """Code-version pool per kernel (backend × template × mem-type ×
    block), mirroring the paper's 1383-version sweep at reduced size."""
    out = [("xla", None, None)]
    for t in TEMPLATES:
        if t in ("shift", "unroll", "semi"):
            for m in ("registers", "vmem"):
                out.append(("pallas", t, m))
        else:
            out.append(("pallas", t, None))
    return out


def run(kernels=None, verbose=True) -> List[Dict]:
    rows = []
    names = kernels or suite.KERNEL_NAMES
    for name in names:
        k = suite.get_kernel(name)
        interior = SHAPE_2D if k.info.ndim == 2 else SHAPE_3D
        arrays, halos = _arrays(k, interior)
        want = ref.reference_apply(k.ir, halos, interior, dict(arrays))
        for backend, template, mem in variants_for(k):
            t0 = time.perf_counter()
            if backend == "xla":
                got = want
            else:
                got = ops.stencil_apply(k, dict(arrays), halos=halos,
                                        template=template, mem_type=mem)
            dt = time.perf_counter() - t0
            errs = []
            for g in k.ir.output_grids():
                e = np.abs(np.asarray(got[g], np.float64)
                           - np.asarray(want[g], np.float64))
                errs.append(e)
            e = np.concatenate([x.ravel() for x in errs])
            rows.append({
                "kernel": name, "backend": backend,
                "template": template or "-", "mem": mem or "-",
                "ndim": k.info.ndim, "shape": k.info.shape,
                "order": k.info.order,
                "flops_per_point": k.info.flops_per_point,
                "max_err": float(e.max()),
                "rmsd": float(np.sqrt((e ** 2).mean())),
                "seconds": dt,
            })
            if verbose:
                r = rows[-1]
                print(f"{name:12s} {backend:6s} {r['template']:7s} "
                      f"{r['mem']:9s} max={r['max_err']:.2e} "
                      f"rmsd={r['rmsd']:.2e}", flush=True)
    return rows


def main():
    rows = run()
    worst = max(rows, key=lambda r: r["max_err"])
    n_versions = len(rows)
    print(f"\n{n_versions} code versions validated; "
          f"worst max_err={worst['max_err']:.2e} "
          f"({worst['kernel']}/{worst['template']}), "
          f"all rmsd ≤ {max(r['rmsd'] for r in rows):.2e}")
    assert worst["max_err"] < 1e-4, "accuracy regression"
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
