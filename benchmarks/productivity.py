"""Paper §6.3 / Table 11: developer-productivity metrics — lines of code
for the same stencil expressed in the DSL vs what the framework generates
and vs a hand-written backend-level implementation.

The paper compares 285 LoC of StencilPy against 1034–1480 LoC of
hand-crafted CUDA/HIP/SYCL/STX.  Our backend-level artifact is the
generated HLO (per template); we report DSL source LoC, HLO line counts,
and the LoC of the hand-rolled jnp reference implementation shipped in
this repo.
"""
from __future__ import annotations

import inspect
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import acoustic, dsl as st, suite
from repro.kernels.stencil import codegen

TEMPLATES = ("gmem", "smem", "f4", "shift", "unroll", "semi")


def _loc(src: str) -> int:
    return sum(1 for l in src.splitlines()
               if l.strip() and not l.strip().startswith("#"))


def dsl_loc(kernel) -> int:
    src = getattr(kernel.fn, "__stencil_source__", None)
    if src is None:
        src = inspect.getsource(kernel.fn)
    return _loc(src)


def generated_hlo_lines(kernel, template: str, interior) -> int:
    halos = {g: kernel.info.halo for g in kernel.ir.grid_params}
    backend = st.pallas(template=template)
    fn = codegen.lower_pallas(kernel.ir, halos, interior, None, backend)
    arrays = {g: jax.ShapeDtypeStruct(
        tuple(s + 2 * h for s, h in zip(interior, halos[g])), jnp.float32)
        for g in kernel.ir.grid_params}
    scalars = {n: jax.ShapeDtypeStruct((), jnp.float32)
               for n, _ in kernel.ir.scalar_params}
    lowered = jax.jit(fn).lower(arrays, scalars)
    return len(lowered.as_text().splitlines())


def run(verbose=True) -> List[Dict]:
    rows = []
    cases = [("acoustic_iso", acoustic.acoustic_iso_kernel, (16, 16, 128)),
             ("star2d4r", suite.get_kernel("star2d4r"), (32, 128)),
             ("box3d2r", suite.get_kernel("box3d2r"), (16, 16, 128))]
    for name, k, interior in cases:
        d = dsl_loc(k)
        for t in TEMPLATES:
            g = generated_hlo_lines(k, t, interior)
            rows.append({"kernel": name, "template": t, "dsl_loc": d,
                         "generated_lines": g,
                         "leverage": round(g / max(d, 1), 1)})
            if verbose:
                r = rows[-1]
                print(f"{name:14s} {t:7s} DSL={d:3d} LoC → "
                      f"{g:5d} generated lines ({r['leverage']}×)",
                      flush=True)
    # framework-level comparison (paper Table 11's '285 vs 1034-1480')
    import repro.core.lowering as lowering_mod
    import repro.kernels.stencil.codegen as codegen_mod
    hand = _loc(inspect.getsource(lowering_mod)) \
        + _loc(inspect.getsource(codegen_mod))
    if verbose:
        print(f"\nbackend implementation (shared by ALL kernels): "
              f"{hand} LoC — amortized once, vs per-kernel hand-porting")
    return rows


def main():
    return run()


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
