"""Distributed stencil runtime: shard_map domain decomposition + halo
exchange (beyond-paper — the paper is single-node; this layer is what makes
the technique runnable on pods).

Design
------
* The stencil grid's axes are mapped onto mesh axes (``backend.grid_axes``,
  e.g. ``('pod', 'data', 'model')`` splits a 3-D domain across all 512 chips
  of the multi-pod mesh).
* Each shard holds its local interior block.  Before applying the kernel,
  each decomposed axis exchanges ``h``-wide edge slabs with its mesh
  neighbors via ``lax.ppermute`` (devices at the global boundary receive
  zeros — matching the zero-filled grid halo).
* ``overlap=True`` splits the local update into an interior pass (which
  does *not* depend on the exchanged halos) and boundary-strip passes
  (which do).  XLA's latency-hiding scheduler can then overlap the
  ppermute transfers with the interior compute — the stencil analogue of
  the compute/comm overlap used in large-scale LM training.
* The per-shard compute reuses the single-device lowerings (XLA or Pallas),
  so ``distributed(inner=pallas(...))`` composes the paper's templates with
  the pod-level decomposition.
* The fused engine path (``lower_distributed_window``) goes further: the
  ENTIRE fusion window — halo exchange, boundary bands, interior compute
  and the leapfrog swap for every step — lives inside ONE jitted
  shard_map'd ``lax.fori_loop``, so a window costs a single program
  dispatch and the latency-hiding scheduler overlaps each group's
  ppermutes with the deep-interior pre-pass across steps, not just
  within one.  All exchange geometry comes from ``core.halo.HaloSpec``.

Halo traffic per step per shard is ``h · (local surface)`` — the classic
reason stencils scale to thousands of nodes: the collective term shrinks
relative to compute as local volume grows.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import analysis, ir, lowering
from . import halo as _halo
from . import timeloop as _tl


def _halo_exchange(local: jnp.ndarray, axis: int, mesh_axis: str,
                   h: int, mesh: Mesh) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Return (left_halo, right_halo) slabs of width ``h`` for ``local``,
    fetched from mesh neighbors along ``mesh_axis`` (zeros at the ends)."""
    k = mesh.shape[mesh_axis]
    ndim = local.ndim

    def edge(lo, hi):
        idx = tuple(slice(lo, hi) if a == axis else slice(None)
                    for a in range(ndim))
        return local[idx]

    if k == 1:
        zero = jnp.zeros_like(edge(0, h))
        return zero, zero
    # my right edge → right neighbor's left halo
    left_halo = lax.ppermute(edge(local.shape[axis] - h, local.shape[axis]),
                             mesh_axis, [(i, i + 1) for i in range(k - 1)])
    # my left edge → left neighbor's right halo
    right_halo = lax.ppermute(edge(0, h), mesh_axis,
                              [(i + 1, i) for i in range(k - 1)])
    return left_halo, right_halo


def lower_distributed(kernel: ir.StencilIR,
                      halos: Mapping[str, Tuple[int, ...]],
                      interior_shape: Tuple[int, ...],
                      region,
                      backend,
                      mesh: Optional[Mesh]):
    """Build ``fn(arrays, scalars) -> arrays`` running the kernel
    domain-decomposed over ``mesh``.

    Constraints: ``region`` must be None (whole-domain; use coefficient
    masks for PML in the distributed path — see regions.py) and global
    grid halos are treated as zero.
    """
    if mesh is None:
        raise ValueError("distributed backend requires launch(mesh=...)")
    if region is not None:
        raise ValueError("distributed backend updates the whole domain; "
                         "express PML via coefficient masks (regions.py)")
    info = analysis.analyze(kernel)
    ndim = kernel.ndim
    grid_axes = tuple(backend.grid_axes)
    in_grids = info.input_grids
    out_grids = info.output_grids
    all_grids = tuple(kernel.grid_params)
    gh = {g: info.halo_per_grid.get(g, (0,) * ndim) for g in all_grids}
    kernel_halos = {g: gh[g] for g in all_grids}

    # geometry + validation (divisibility, axis mapping) via HaloSpec
    spec = _halo.HaloSpec.build(gh, grid_axes, interior_shape,
                                dict(mesh.shape), depth=1)
    local_shape = spec.local_shape

    _k_inner = _tl.backend_time_block(backend)
    if (getattr(backend, "time_steps", 1) > 1
            or (_k_inner > 1 and getattr(backend, "swap", None) is not None)):
        return _lower_time_skewed(kernel, info, interior_shape, backend,
                                  mesh, grid_axes, local_shape, gh)

    inner = getattr(backend, "inner", None)
    if inner is not None and inner.kind == "pallas":
        from repro.kernels.stencil import codegen as _codegen

        def make_inner(reg):
            return _codegen.lower_pallas(kernel, kernel_halos, local_shape,
                                         reg, inner)
    else:
        def make_inner(reg):
            return lowering.lower_jax(kernel, kernel_halos, local_shape, reg)

    inner_full = make_inner(None)

    # boundary strips (per decomposed axis, both ends) for the overlap path
    strip_regions = []
    for ax, m in enumerate(grid_axes):
        if m is None:
            continue
        h = max(gh[g][ax] for g in all_grids)
        if h == 0:
            continue
        full = tuple((0, local_shape[a]) for a in range(ndim))
        lo = tuple((0, h) if a == ax else full[a] for a in range(ndim))
        hi = tuple((local_shape[a] - h, local_shape[a]) if a == ax else full[a]
                   for a in range(ndim))
        strip_regions.append(lo)
        strip_regions.append(hi)
    inner_strips = [make_inner(r) for r in strip_regions] if backend.overlap \
        else []

    specs = P(*grid_axes)

    def pad_with_halos(local_arrays):
        """Exchange halos and return per-grid halo-padded local arrays."""
        padded = {}
        for g, loc in local_arrays.items():
            arr = loc
            for ax in range(ndim):
                h = gh[g][ax]
                if h == 0:
                    continue
                m = grid_axes[ax]
                if m is None:
                    zshape = list(arr.shape)
                    zshape[ax] = h
                    lh = jnp.zeros(zshape, arr.dtype)
                    rh = lh
                else:
                    # halo slabs are exchanged on the *unpadded* axis
                    # extents of already-padded other axes — pad order is
                    # axis-by-axis so earlier axes are already padded; the
                    # exchange covers the padded extent of those axes.
                    lh, rh = _halo_exchange(arr, ax, m, h, mesh)
                arr = jnp.concatenate([lh, arr, rh], axis=ax)
            padded[g] = arr
        return padded

    def interior_only_pad(local_arrays):
        padded = {}
        for g, loc in local_arrays.items():
            pads = [(gh[g][ax], gh[g][ax]) for ax in range(ndim)]
            padded[g] = jnp.pad(loc, pads)
        return padded

    def crop(arr, g):
        idx = tuple(slice(gh[g][ax], gh[g][ax] + local_shape[ax])
                    for ax in range(ndim))
        return arr[idx]

    def sharded_step(local_arrays: Dict[str, jnp.ndarray],
                     scalars: Dict[str, jnp.ndarray]):
        if backend.overlap and inner_strips:
            # 1) interior pass on zero-halo padding — no comm dependency, so
            #    XLA can overlap it with the ppermutes issued below.
            pad0 = interior_only_pad(local_arrays)
            out0 = inner_full(pad0, scalars)
            final = {g: crop(out0[g], g) for g in out_grids}
            # 2) exchanged halos → recompute boundary strips from the
            #    *pristine* inputs (outputs may alias inputs via center
            #    reads) and patch them into the interior-pass result.
            pad1 = pad_with_halos(local_arrays)
            for strip_fn, reg in zip(inner_strips, strip_regions):
                sres = strip_fn(pad1, scalars)
                for g in out_grids:
                    loc = tuple(slice(b, e) for b, e in reg)
                    padd = tuple(slice(gh[g][ax] + b, gh[g][ax] + e)
                                 for ax, (b, e) in enumerate(reg))
                    final[g] = final[g].at[loc].set(sres[g][padd])
            return final
        padded = pad_with_halos(local_arrays)
        out = inner_full(padded, scalars)
        return {g: crop(out[g], g) for g in out_grids}

    shmapped = jax.shard_map(
        sharded_step, mesh=mesh,
        in_specs=({g: specs for g in all_grids}, P()),
        out_specs={g: specs for g in out_grids},
        check_vma=False)

    jitted = jax.jit(shmapped)

    def fn(arrays: Dict[str, jnp.ndarray], scalars: Dict[str, jnp.ndarray]):
        """arrays are *full* (grid-halo'd) host arrays; the grid halo is
        assumed zero in the distributed path."""
        interiors = {}
        for g in all_grids:
            o = (np.asarray(arrays[g].shape) - np.asarray(interior_shape)) // 2
            idx = tuple(slice(int(o[ax]), int(o[ax]) + interior_shape[ax])
                        for ax in range(ndim))
            interiors[g] = arrays[g][idx]
        scal = {n: jnp.asarray(v, jnp.float32) for n, v in scalars.items()}
        out = jitted(interiors, scal)
        result = dict(arrays)
        for g in out_grids:
            o = (np.asarray(arrays[g].shape) - np.asarray(interior_shape)) // 2
            idx = tuple(slice(int(o[ax]), int(o[ax]) + interior_shape[ax])
                        for ax in range(ndim))
            result[g] = arrays[g].at[idx].set(out[g])
        return result

    fn.jitted = jitted
    fn.shmapped = shmapped
    fn.mesh = mesh
    fn.partition_spec = specs
    fn.local_shape = local_shape
    fn.spec = spec
    return fn


# ---------------------------------------------------------------------------
# overlapped tiling / time skewing (paper §3) at pod level
# ---------------------------------------------------------------------------
def _lower_time_skewed(kernel, info, interior_shape, backend, mesh,
                       grid_axes, local_shape, gh):
    """k kernel applications per ONE (k·h)-wide halo exchange.

    Each shard exchanges halos of width ext[g] = (k−1)·h_max + h_g, then
    computes k steps on regions shrinking by h_max per step — the shells
    between k·h and the interior are computed redundantly by both
    neighbors (the classic redundant-compute/communication trade).  At
    global boundaries the (zero) grid-halo condition is re-imposed on the
    shells between steps so fused results match k separate exchanged
    steps exactly (validated in tests/test_distributed.py).

    A pallas ``inner`` carrying ``time_block=k_inner`` composes with the
    device-level skewing: ``time_steps`` then counts k_inner-deep temporal
    groups, so one exchange is k_outer·k_inner·h wide and covers
    k_outer·k_inner applications (the per-shard sub-steps currently run
    through the XLA shrinking-region lowering, which has the identical
    halo/shell geometry as the in-kernel Pallas temporal blocks).
    """
    k_inner = _tl.backend_time_block(backend)
    k = backend.time_steps * k_inner
    swap = backend.swap
    if swap is None:
        raise ValueError("time_steps > 1 requires swap=(older, newer)")
    ndim = kernel.ndim
    all_grids = tuple(kernel.grid_params)
    out_grids = info.output_grids
    if len(out_grids) != 1 or out_grids[0] != swap[0]:
        raise ValueError("time skewing supports single-output kernels "
                         "writing swap[0]")

    # the whole exchange geometry — pad widths ((k−1)·h_max + h_g per
    # coefficient axis, uniform k·h_max for the swap pair), feasibility
    # (k·h ≤ local extent), zero-fill axes — is HaloSpec's job
    spec = _halo.HaloSpec.build(gh, grid_axes, interior_shape,
                                dict(mesh.shape), depth=k, swap=swap)
    h_max = spec.h_max
    ext = {g: spec.ext_of(g) for g in all_grids}

    def pad_wide(local_arrays):
        padded = {}
        for g, arr in local_arrays.items():
            for ax in range(ndim):
                e = ext[g][ax]
                if e == 0:
                    continue
                m = grid_axes[ax]
                if m:
                    lh, rh = _halo_exchange(arr, ax, m, e, mesh)
                else:
                    zshape = list(arr.shape)
                    zshape[ax] = e
                    lh = jnp.zeros(zshape, arr.dtype)
                    rh = lh
                arr = jnp.concatenate([lh, arr, rh], axis=ax)
            padded[g] = arr
        return padded

    def zero_outside_global(arr, g):
        """Re-impose the zero grid-halo beyond the global boundary (edge
        shards only) — the shells an edge shard 'computes' there must not
        leak into later steps."""
        for ax in range(ndim):
            m = grid_axes[ax]
            e = ext[g][ax]
            if not m or e == 0:
                continue
            idx = lax.axis_index(m)
            n = mesh.shape[m]
            coord = jnp.arange(arr.shape[ax])
            inside_lo = (idx > 0) | (coord >= e)
            inside_hi = (idx < n - 1) | (coord < arr.shape[ax] - e)
            keep = (inside_lo & inside_hi)
            shape = [1] * ndim
            shape[ax] = arr.shape[ax]
            arr = arr * keep.reshape(shape).astype(arr.dtype)
        return arr

    def sharded_k_steps(local_arrays, scalars):
        padded = pad_wide(local_arrays)
        padded = {g: zero_outside_global(a, g) for g, a in padded.items()}
        older, newer = swap
        for i in range(k):
            step_fn = lowering.lower_jax(kernel, ext, local_shape,
                                         spec.step_region(i))
            out = step_fn(padded, scalars)
            new_field = zero_outside_global(out[older], older)
            padded = dict(padded)
            padded[older], padded[newer] = padded[newer], new_field
        # crop interiors; final field lives in `newer` after the last swap
        def crop(arr, g):
            idx = tuple(slice(ext[g][ax], ext[g][ax] + local_shape[ax])
                        for ax in range(ndim))
            return arr[idx]
        return {older: crop(padded[older], older),
                newer: crop(padded[newer], newer)}

    specs = P(*grid_axes)
    shmapped = jax.shard_map(
        sharded_k_steps, mesh=mesh,
        in_specs=({g: specs for g in all_grids}, P()),
        out_specs={swap[0]: specs, swap[1]: specs},
        check_vma=False)
    jitted = jax.jit(shmapped)

    def fn(arrays, scalars):
        interiors = {}
        for g in all_grids:
            o = (np.asarray(arrays[g].shape)
                 - np.asarray(interior_shape)) // 2
            idx = tuple(slice(int(o[ax]), int(o[ax]) + interior_shape[ax])
                        for ax in range(ndim))
            interiors[g] = arrays[g][idx]
        scal = {n: jnp.asarray(v, jnp.float32) for n, v in scalars.items()}
        out = jitted(interiors, scal)
        result = dict(arrays)
        for g in out:
            o = (np.asarray(arrays[g].shape)
                 - np.asarray(interior_shape)) // 2
            idx = tuple(slice(int(o[ax]), int(o[ax]) + interior_shape[ax])
                        for ax in range(ndim))
            result[g] = arrays[g].at[idx].set(out[g])
        return result

    fn.jitted = jitted
    fn.shmapped = shmapped
    fn.mesh = mesh
    fn.partition_spec = specs
    fn.local_shape = local_shape
    fn.spec = spec
    return fn


# ---------------------------------------------------------------------------
# fused sharded timeloop: ONE program per fusion window
# ---------------------------------------------------------------------------
def lower_distributed_window(kernel: ir.StencilIR,
                             interior_shape: Tuple[int, ...],
                             backend,
                             mesh: Optional[Mesh],
                             swap: Tuple[str, str],
                             window: int,
                             batch: int = 0,
                             differentiable: bool = False,
                             masked: bool = False):
    """Build ``fn(arrays, scalars) -> arrays`` advancing ``window``
    leapfrog steps in ONE jitted shard_map'd program.

    The window decomposes into depth-``k`` exchange groups
    (``k = time_steps × inner time_block``; ``HaloSpec.group_depths``):
    ``window // k`` identical groups run as a ``lax.fori_loop`` plus one
    unrolled remainder group — all inside the same XLA program, so a
    window pays a single dispatch instead of one per exchange.  Within a
    group the swap pair exchanges ONE k·h_max-wide halo and then runs k
    kernel applications on shrinking regions; the first application's
    deep interior (``HaloSpec.deep_interior``) is computed from
    local-only, zero-padded data *before* the exchanged slabs are
    consumed, so XLA's latency-hiding scheduler overlaps the ppermutes
    with interior compute, and only the boundary bands
    (``HaloSpec.boundary_bands``) wait for the network.  Coefficient
    grids are exchanged ONCE per window (their slabs are wide enough for
    every group) and carried through the loop as invariants.

    ``batch > 0`` runs B independent scenarios as a leading unsharded
    axis: grids are ``(B, *spatial)`` sharded ``P(None, *grid_axes)``,
    scalars are replicated ``(B,)`` arrays, and every per-shard step
    function is vmapped over the scenario axis — one program advances
    the whole batch on the whole mesh.

    Per-shard sub-steps run through the XLA shrinking-region lowering
    regardless of a Pallas ``inner`` — the inner's ``time_block`` sets
    exchange *depth* (geometry), matching the existing time-skewed path.
    Global grid halos are zero, re-imposed between fused steps at mesh
    edges.  Exchange geometry/traffic live on ``fn.spec`` (a
    ``core.halo.HaloSpec``) for the cost model and tests.

    ``differentiable=True`` makes the returned window reverse-mode
    differentiable: the forward program is wrapped in a ``jax.custom_vjp``
    whose backward pass is a SECOND jitted shard_map program
    (``fn.bwd_jitted``) that re-linearizes the per-shard window body with
    ``jax.vjp`` *inside* the shard_map region and pulls the cotangents
    back through it.  Because the vjp is taken on per-device code, every
    forward ``ppermute`` transposes to the reverse ``ppermute`` — the same
    slab moving the opposite way, accumulating into the neighbor's edge
    cells — i.e. exactly the geometry of ``fn.spec.transpose()`` (attached
    as ``fn.spec_T``).  The wavefront-pipelining structure is reused as
    is: the adjoint of the deep-interior pre-pass is again a deep-interior
    pass with no communication dependency, so the latency hiding works
    identically for cotangents.  Scalar cotangents are ``psum``-reduced
    across the mesh (each shard contributes its local share).  Residuals
    are the window *inputs* only — O(1) carries per window, composing with
    the √T checkpointing of ``core/adjoint.py``.

    ``masked=True`` (requires ``batch``) builds the serving variant
    ``fn(arrays, scalars, mask, start, limits)`` with the exact freeze
    semantics of ``lowering.lower_jax_window_masked`` — per-scenario
    spatial masks and step budgets — under sharding: the mask shards like
    a batched grid, frozen cells keep their values and travel through the
    halo exchange like any other cell, so a masked sharded run equals the
    masked single-device run.  Masked windows exchange at depth 1 (the
    freeze is applied between *every* step, which a depth-k group cannot
    honor).  Composes with ``differentiable``.
    """
    if mesh is None:
        raise ValueError("distributed backend requires launch(mesh=...)")
    if swap is None:
        raise ValueError("the distributed timeloop requires "
                         "swap=(older, newer)")
    info = analysis.analyze(kernel)
    ndim = kernel.ndim
    grid_axes = tuple(backend.grid_axes)
    if len(grid_axes) != ndim:
        raise ValueError(f"grid_axes must have {ndim} entries")
    all_grids = tuple(kernel.grid_params)
    out_grids = info.output_grids
    if len(out_grids) != 1 or out_grids[0] != swap[0]:
        raise ValueError("the distributed timeloop supports single-output "
                         "kernels writing swap[0]")
    gh = {g: info.halo_per_grid.get(g, (0,) * ndim) for g in all_grids}
    window = int(window)
    if window < 1:
        raise ValueError("window must be >= 1")
    mesh_shape = dict(mesh.shape)

    h_max = max((h for hs in gh.values() for h in hs), default=0)
    depth = backend.time_steps * _tl.backend_time_block(backend)
    if h_max == 0:
        if depth > 1:
            raise ValueError("time skewing needs a nonzero stencil halo")
        depth = 1
    if masked:
        if not batch:
            raise ValueError("masked distributed windows require batch=B "
                             "(the serving path)")
        # the spatial/temporal freeze applies between every step, which a
        # depth-k exchange group's shrinking regions cannot express
        depth = 1
    depth = min(depth, window)
    spec = _halo.HaloSpec.build(gh, grid_axes, interior_shape, mesh_shape,
                                depth=depth, swap=swap)   # validates
    local_shape = spec.local_shape
    groups = spec.group_depths(window)
    older, newer = swap
    coeffs = tuple(g for g in all_grids if g not in (older, newer))
    ext_main = {g: spec.ext_of(g) for g in all_grids}
    off = 1 if batch else 0

    def maybe_vmap(f):
        return jax.vmap(f, in_axes=(0, 0)) if batch else f

    def pad_exchanged(arr, widths):
        """Axis-by-axis halo pad: real ppermute slabs on decomposed axes,
        zeros elsewhere (the global zero grid-halo)."""
        for ax in range(ndim):
            e = widths[ax]
            if e == 0:
                continue
            m = grid_axes[ax]
            if m:
                lh, rh = _halo_exchange(arr, ax + off, m, e, mesh)
            else:
                zshape = list(arr.shape)
                zshape[ax + off] = e
                lh = jnp.zeros(zshape, arr.dtype)
                rh = lh
            arr = jnp.concatenate([lh, arr, rh], axis=ax + off)
        return arr

    def pad_zero(arr, widths):
        pads = [(0, 0)] * off + [(w, w) for w in widths]
        return jnp.pad(arr, pads)

    def zero_outside_global(arr, widths):
        """Re-impose the zero grid-halo beyond the global boundary on edge
        shards, so shells 'computed' there never leak into later steps."""
        for ax in range(ndim):
            m = grid_axes[ax]
            e = widths[ax]
            if not m or e == 0:
                continue
            idx = lax.axis_index(m)
            n = mesh_shape[m]
            extent = arr.shape[ax + off]
            coord = jnp.arange(extent)
            keep = (((idx > 0) | (coord >= e))
                    & ((idx < n - 1) | (coord < extent - e)))
            shape = [1] * arr.ndim
            shape[ax + off] = extent
            arr = arr * keep.reshape(shape).astype(arr.dtype)
        return arr

    def crop_local(arr, widths):
        idx = ((slice(None),) * off
               + tuple(slice(widths[ax], widths[ax] + local_shape[ax])
                       for ax in range(ndim)))
        return arr[idx]

    def reg_idx(widths, region):
        return ((slice(None),) * off
                + tuple(slice(w + b, w + e)
                        for w, (b, e) in zip(widths, region)))

    use_overlap = bool(getattr(backend, "overlap", True)) \
        and spec.overlap_feasible()

    def group_fns(d):
        """Step/pre/band functions of one depth-d exchange group."""
        sub = spec if d == spec.depth else spec.with_depth(d)
        # remainder groups keep reading the window-wide coefficient pads
        exts = {g: ext_main[g] for g in coeffs}
        for g in (older, newer):
            exts[g] = sub.ext_of(g)
        step_fns = [maybe_vmap(lowering.lower_jax(kernel, exts, local_shape,
                                                  sub.step_region(i)))
                    for i in range(d)]
        pre_fn = None
        band_fns = []
        if use_overlap:
            pre_fn = maybe_vmap(lowering.lower_jax(kernel, gh, local_shape,
                                                   sub.deep_interior()))
            band_fns = [(maybe_vmap(lowering.lower_jax(
                            kernel, exts, local_shape, breg)), breg)
                        for breg in sub.boundary_bands()]
        return sub, exts, step_fns, pre_fn, band_fns

    def run_group(carry, pcoeffs, zcoeffs, scalars, fns):
        sub, exts, step_fns, pre_fn, band_fns = fns
        ew = exts[older]
        padded = dict(pcoeffs)
        for g in (older, newer):
            padded[g] = zero_outside_global(
                pad_exchanged(carry[g], exts[g]), exts[g])
        for i, step_fn in enumerate(step_fns):
            if i == 0 and pre_fn is not None:
                # deep interior from local-only data — no dependency on the
                # ppermutes above, so the scheduler overlaps them with this
                pre_in = dict(zcoeffs)
                pre_in[older] = pad_zero(carry[older], gh[older])
                pre_in[newer] = pad_zero(carry[newer], gh[newer])
                pre_out = pre_fn(pre_in, scalars)[older]
                deep = sub.deep_interior()
                out_f = padded[older].at[reg_idx(ew, deep)].set(
                    pre_out[reg_idx(gh[older], deep)])
                for band_fn, breg in band_fns:
                    bres = band_fn(padded, scalars)[older]
                    out_f = out_f.at[reg_idx(ew, breg)].set(
                        bres[reg_idx(ew, breg)])
            else:
                out_f = step_fn(padded, scalars)[older]
            new_field = zero_outside_global(out_f, ew)
            padded = dict(padded)
            padded[older], padded[newer] = padded[newer], new_field
        return {older: crop_local(padded[older], ew),
                newer: crop_local(padded[newer], ew)}

    gspec = P(None, *grid_axes) if batch else P(*grid_axes)

    if masked:
        # one full-region step at depth-1 pad widths; freeze applied on the
        # local interiors between steps, exactly as the single-device
        # masked window does it in buffer space
        step_full = maybe_vmap(lowering.lower_jax(kernel, ext_main,
                                                  local_shape, None))
        act_shape = (batch,) + (1,) * ndim

        def sharded_body(local_arrays, scalars, mask, start, limits):
            pcoeffs = {g: zero_outside_global(
                           pad_exchanged(local_arrays[g], ext_main[g]),
                           ext_main[g])
                       for g in coeffs}

            def body(i, carry):
                padded = dict(pcoeffs)
                for g in (older, newer):
                    padded[g] = zero_outside_global(
                        pad_exchanged(carry[g], ext_main[g]), ext_main[g])
                out_i = crop_local(step_full(padded, scalars)[older],
                                   ext_main[older])
                act = ((start + i) < limits).reshape(act_shape)
                # spatial freeze first (masked cells keep the older
                # buffer), then the per-scenario rotation freeze
                frozen = jnp.where(mask, out_i, carry[older])
                return {older: jnp.where(act, carry[newer], carry[older]),
                        newer: jnp.where(act, frozen, carry[newer])}

            carry = {older: local_arrays[older], newer: local_arrays[newer]}
            return lax.fori_loop(0, window, body, carry)

        mask_spec = P(None, *grid_axes)
        shmapped = jax.shard_map(
            sharded_body, mesh=mesh,
            in_specs=({g: gspec for g in all_grids}, P(), mask_spec,
                      P(), P()),
            out_specs={older: gspec, newer: gspec},
            check_vma=False)
    else:
        (m_groups, _), = groups[:1]
        rem = groups[1] if len(groups) > 1 else None
        main_fns = group_fns(depth)
        rem_fns = group_fns(rem[1]) if rem else None

        def sharded_body(local_arrays, scalars):
            # coefficients: exchanged once, loop-invariant through the
            # window
            pcoeffs = {g: zero_outside_global(
                           pad_exchanged(local_arrays[g], ext_main[g]),
                           ext_main[g])
                       for g in coeffs}
            zcoeffs = ({g: pad_zero(local_arrays[g], gh[g]) for g in coeffs}
                       if use_overlap else {})
            carry = {older: local_arrays[older], newer: local_arrays[newer]}
            if m_groups == 1:
                carry = run_group(carry, pcoeffs, zcoeffs, scalars,
                                  main_fns)
            else:
                carry = lax.fori_loop(
                    0, m_groups,
                    lambda _i, c: run_group(c, pcoeffs, zcoeffs, scalars,
                                            main_fns),
                    carry)
            if rem is not None:
                carry = run_group(carry, pcoeffs, zcoeffs, scalars, rem_fns)
            return carry

        shmapped = jax.shard_map(
            sharded_body, mesh=mesh,
            in_specs=({g: gspec for g in all_grids}, P()),
            out_specs={older: gspec, newer: gspec},
            check_vma=False)

    jitted = jax.jit(shmapped)

    # -- adjoint program: jax.vjp of the per-shard body INSIDE shard_map ----
    # (so every forward ppermute transposes to the reverse ppermute — the
    # fn.spec_T geometry — and the deep-interior latency hiding applies to
    # the cotangents too); scalar cotangents psum-reduce across the mesh
    bwd_jitted = None
    if differentiable:
        axes = tuple(mesh.axis_names)

        def _psum_scal(d_scal):
            return {n: lax.psum(v, axes) for n, v in d_scal.items()}

        if masked:
            def sharded_adjoint(local_in, cot, scalars, mask, start,
                                limits):
                def f(a, s):
                    return sharded_body(a, s, mask, start, limits)
                _, vjp_fn = jax.vjp(f, local_in, scalars)
                d_in, d_scal = vjp_fn(dict(cot))
                return d_in, _psum_scal(d_scal)

            bwd_shmapped = jax.shard_map(
                sharded_adjoint, mesh=mesh,
                in_specs=({g: gspec for g in all_grids},
                          {older: gspec, newer: gspec}, P(),
                          P(None, *grid_axes), P(), P()),
                out_specs=({g: gspec for g in all_grids}, P()),
                check_vma=False)
        else:
            def sharded_adjoint(local_in, cot, scalars):
                _, vjp_fn = jax.vjp(sharded_body, local_in, scalars)
                d_in, d_scal = vjp_fn(dict(cot))
                return d_in, _psum_scal(d_scal)

            bwd_shmapped = jax.shard_map(
                sharded_adjoint, mesh=mesh,
                in_specs=({g: gspec for g in all_grids},
                          {older: gspec, newer: gspec}, P()),
                out_specs=({g: gspec for g in all_grids}, P()),
                check_vma=False)
        bwd_jitted = jax.jit(bwd_shmapped)

    if differentiable and not masked:
        @jax.custom_vjp
        def core(interiors, scal):
            return jitted(interiors, scal)

        def _core_fwd(interiors, scal):
            # residuals are the window INPUTS (one carry), not per-step
            # intermediates — the backward program re-linearizes from them
            return jitted(interiors, scal), (interiors, scal)

        def _core_bwd(res, cot):
            interiors, scal = res
            return bwd_jitted(interiors, dict(cot), scal)

        core.defvjp(_core_fwd, _core_bwd)
    else:
        core = jitted

    def _masked_core(mask, start, limits):
        """custom_vjp over (interiors, scalars) with the non-differentiable
        mask/start/limits operands closed over (they are concrete per
        call; the compiled programs underneath are shared)."""
        @jax.custom_vjp
        def core_m(interiors, scal):
            return jitted(interiors, scal, mask, start, limits)

        def fwd(interiors, scal):
            return (jitted(interiors, scal, mask, start, limits),
                    (interiors, scal))

        def bwd(res, cot):
            interiors, scal = res
            return bwd_jitted(interiors, dict(cot), scal, mask, start,
                              limits)

        core_m.defvjp(fwd, bwd)
        return core_m

    def _interior_idx(arr):
        o = (np.asarray(arr.shape[off:]) - np.asarray(interior_shape)) // 2
        return ((slice(None),) * off
                + tuple(slice(int(o[ax]), int(o[ax]) + interior_shape[ax])
                        for ax in range(ndim)))

    def _scal_in(v):
        # floating dtypes pass through (the f64 adjoint path must not be
        # silently truncated); everything else normalizes to f32 as before
        a = jnp.asarray(v)
        return a if jnp.issubdtype(a.dtype, jnp.floating) \
            else a.astype(jnp.float32)

    if masked:
        def fn(arrays: Dict[str, jnp.ndarray],
               scalars: Dict[str, jnp.ndarray],
               mask, start, limits):
            """arrays are *full* (grid-halo'd) host arrays with a leading
            batch axis; the grid halo is assumed zero."""
            interiors = {g: arrays[g][_interior_idx(arrays[g])]
                         for g in all_grids}
            scal = {n: _scal_in(v) for n, v in scalars.items()}
            mask = jnp.asarray(mask, bool)
            start = jnp.asarray(start, jnp.int32)
            limits = jnp.asarray(limits, jnp.int32)
            if differentiable:
                out = _masked_core(mask, start, limits)(interiors, scal)
            else:
                out = jitted(interiors, scal, mask, start, limits)
            result = dict(arrays)
            for g in (older, newer):
                full = jnp.asarray(arrays[g])
                result[g] = full.at[_interior_idx(full)].set(out[g])
            return result
    else:
        def fn(arrays: Dict[str, jnp.ndarray],
               scalars: Dict[str, jnp.ndarray]):
            """arrays are *full* (grid-halo'd) host arrays, optionally with
            a leading batch axis; the grid halo is assumed zero."""
            interiors = {g: arrays[g][_interior_idx(arrays[g])]
                         for g in all_grids}
            scal = {n: _scal_in(v) for n, v in scalars.items()}
            out = core(interiors, scal)
            result = dict(arrays)
            for g in (older, newer):
                full = jnp.asarray(arrays[g])
                result[g] = full.at[_interior_idx(full)].set(out[g])
            return result

    fn.jitted = jitted
    fn.shmapped = shmapped
    fn.bwd_jitted = bwd_jitted
    fn.mesh = mesh
    fn.partition_spec = gspec
    fn.local_shape = local_shape
    fn.spec = spec
    fn.spec_T = spec.transpose()
    fn.depth = depth
    fn.window = window
    fn.groups = groups
    fn.masked = masked
    fn.differentiable = differentiable
    return fn
