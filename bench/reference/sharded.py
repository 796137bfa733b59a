"""The plain reference's leapfrog on grids sharded in slabs over a mesh.

``stencil.leapfrog`` jitted on sharded grids leaves the exchange to XLA's
partitioner, which gathers every grid whole on each device for the
kernel's interior write: at 1024×1024×512 that needs more than one chip's
memory.  Here each shard applies the same ``stencil.Kernel`` (the
configuration's own DSL source, evaluated with plain jnp) to its own
slab, with the ``order`` rows of each neighbour fetched by
``lax.ppermute`` before every step and zeros beyond the domain's edge, as
the zero grid halo gives.  Nothing of the system under test is imported:
the exchange is this module's own, independent of ``core/halo.py``.
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .stencil import Kernel


def _neighbour_rows(a, h: int, axis: str, n: int):
    """(rows from the lower shard, rows from the upper shard), ``h`` each
    along axis 0; zeros where there is no neighbour."""
    if n == 1:
        z = jnp.zeros((h,) + a.shape[1:], a.dtype)
        return z, z
    lower = lax.ppermute(a[-h:], axis, [(i, i + 1) for i in range(n - 1)])
    upper = lax.ppermute(a[:h], axis, [(i + 1, i) for i in range(n - 1)])
    return lower, upper


def leapfrog(kernel: Kernel, swap: Tuple[str, str], steps: int, mesh,
             axis: str, *, dtype=jnp.float32) -> Callable:
    """Jitted ``run(arrays, scalars) -> arrays``, as ``stencil.leapfrog``
    without a source, on halo-padded grids whose axis 0 is split over the
    mesh axis ``axis``; the results are sharded the same way and their
    halos are zero."""
    written, other = swap
    h = kernel.order
    n = mesh.shape[axis]
    rows = NamedSharding(mesh, P(axis))

    def local(arrays, scalars):
        # each shard: its slab of the interior, padded on axes 1.. only
        def padded(a):
            lo, hi = _neighbour_rows(a, h, axis, n)
            return jnp.concatenate([lo, a, hi], axis=0)

        def crop(a):
            return a[h:-h]

        const = {g: padded(a) for g, a in arrays.items() if g not in swap}

        def body(_, carry):
            arrs = dict(const)
            arrs.update({g: padded(a) for g, a in carry.items()})
            out, _, _ = kernel.apply(arrs, scalars)
            return {written: crop(out[other]), other: crop(out[written])}
        return lax.fori_loop(0, steps, body, {g: arrays[g] for g in swap})

    step = jax.shard_map(local, mesh=mesh, in_specs=(P(axis), P()),
                         out_specs=P(axis), check_vma=False)

    def run(arrays, scalars):
        arrays = {g: jnp.asarray(a, dtype) for g, a in arrays.items()}
        scalars = {s: jnp.asarray(v, dtype) for s, v in scalars.items()}
        inner = {}
        for g, a in arrays.items():
            # interior rows only on axis 0 (the slabs), halos kept elsewhere
            inner[g] = lax.with_sharding_constraint(a[h:-h], rows)
        out = step(inner, scalars)
        res = dict(arrays)
        for g, a in out.items():
            res[g] = jnp.pad(a, [(h, h)] + [(0, 0)] * (a.ndim - 1))
        return {g: lax.with_sharding_constraint(a.astype(jnp.float32), rows)
                for g, a in res.items()}

    return jax.jit(run)


def rel_errs(got: Dict[str, object], want: Dict[str, object], grids,
             order: int) -> float:
    """``stencil.arrays_rel_err`` reduced in one jitted program, so that
    no interior of the global extent is gathered on one device."""
    def maxes(g, w):
        idx = tuple(slice(order, s - order) for s in g.shape)
        g = g[idx].astype(jnp.float32)
        w = w[idx].astype(jnp.float32)
        return jnp.max(jnp.abs(g - w)), jnp.max(jnp.abs(w))

    fn = jax.jit(maxes)
    out = 0.0
    for g in grids:
        num, den = fn(got[g], want[g])
        out = max(out, float(num) / max(float(den), 1e-30))
    return out
