"""StencilIR → Pallas TPU kernel code generator (paper §4.5 templates).

Templates (paper Table 2), re-derived for the TPU memory hierarchy:

  gmem   — 3D/2D blocking; each tap is read by concatenating slices of the
           center block and its ±1 neighbor blocks (halo comes from
           *neighbor-block input refs*, the TPU analogue of reading global
           memory through the pipelined block fetch).
  smem   — 3D/2D blocking; the halo'd tile is materialized once in a VMEM
           scratch buffer and taps are static slices of it (the shared-
           memory analogue).
  f4     — gmem with lane-aligned blocks (last dim %128, 2nd-last %8): the
           VPU-vectorization analogue of float4.
  shift  — 2.5D streaming along axis 0: a rolling window of 2h+1 planes is
           carried through a fori_loop (mem_type 'registers' keeps it as
           loop-carried values ⇒ VREGs; 'vmem' streams planes straight from
           the VMEM tile).  Window advanced with jnp.roll.
  unroll — like shift but the window shift is statically unrolled
           (concatenate-rebuild ⇒ fixed VREG assignment).
  semi   — Semi-stencil [de la Cruz & Araya-Polo]: forward-scatter of each
           input plane into a rolling buffer of partial output planes; each
           input plane is touched exactly once, output planes complete with
           lag 2H.  Requires the kernel to be linear in its taps.

Halo handling: inputs are pre-padded by one full block per side (ops-level
wrapper below), so every neighbor-block index `g+1+δ` is in bounds and no
boundary conditionals appear inside the kernel — this is the consolidation
the paper's §6.2.1 'future work' asks for (one set of conditionals → zero).

Two execution modes share the template bodies:

  * ``lower_pallas`` — the original per-application path: pad inputs into
    block-padded layout, run one ``pallas_call``, merge outputs back into
    the unpadded arrays.  One ``jnp.pad`` per grid per application.
  * ``plan_pallas`` → :class:`PallasPlan` — the fused time-loop path.
    Lowering is split into a one-time *layout* stage (``to_padded``: one
    ``jnp.pad`` per grid per fusion window) and a per-invocation *kernel*
    stage (``step``: a single ``pallas_call`` whose outputs are written
    in-place in padded layout via ``input_output_aliases``; positions
    outside the true interior pass the old value through, so the grid halo
    survives across steps with no repacking).  Per-grid operands are
    deduplicated: each padded grid is passed once and fetched as a halo'd
    window (``pl.Element`` block dims, widened to the (8, 128) tile that
    a TPU window DMA must keep) instead of once per neighbor delta.  With
    ``backend.time_block=k`` the kernel stage is *temporally blocked*
    (``_make_body_temporal``): windows carry k·h-deep expanded
    halos and one invocation advances k leapfrog steps in VMEM.  The
    k-step outputs are *double-buffered*: they alias dedicated
    destination operands instead of the read buffers, because the
    expanded windows overlap neighboring blocks' output interiors and the
    TPU grid runs sequentially (see ``PallasPlan``).  Per k steps each
    advanced grid thus costs one expanded-window read, one destination
    fetch and one block write — ~k× less traffic asymptotically, though
    halo growth + the destination fetch can make small depths a net loss
    (``TRAFFIC_COUNT`` tracks the modeled traffic honestly).

The expression evaluator is shared with the XLA lowering
(`repro.core.lowering.eval_expr`), so all backends execute the same IR.
"""
from __future__ import annotations

import collections
import functools
import itertools
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core import analysis, ir, lowering

DEFAULT_BLOCK = {2: (8, 128), 3: (8, 8, 128)}
STREAM_BLOCK = {2: (16, 128), 3: (16, 8, 128)}


# counts eager ``jnp.pad`` layout conversions per grid name; the fused path
# must show exactly one per grid per fusion window (tests/test_timeloop.py)
PAD_COUNT: collections.Counter = collections.Counter()

# modeled HBM traffic of the fused path, accumulated by the time-loop engine
# per executed window: grid-window reads, grid-block writes, and time steps
# covered.  With in-kernel temporal blocking (``time_block=k``) one
# read+write pair covers k steps, so reads/steps drops ~k× vs k=1.
TRAFFIC_COUNT: collections.Counter = collections.Counter()


def reset_pad_count() -> None:
    PAD_COUNT.clear()


def reset_traffic_count() -> None:
    TRAFFIC_COUNT.clear()


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _tile(ax: int, ndim: int) -> int:
    """Alignment a VMEM window must keep on axis ``ax``: the (8, 128) f32
    tile of the last two axes; leading axes are untiled."""
    return (8, 128)[ax - ndim + 2] if ax >= ndim - 2 else 1


#: Mosaic's scoped-VMEM limit on v5e when a kernel sets none ("limit
#: 16.00M" in its refusals)
_VMEM_DEFAULT = 16 << 20
#: VMEM a kernel body takes beyond ``PallasPlan.vmem_bytes``: the compiler
#: asked for 19.00 and 30.25 MiB for star3d4r 512³ and acoustic-ISO 256³ at
#: ``time_block=4``, 1.69 MiB over the model in both; about twice that
_VMEM_SLACK = 4 << 20


def _vmem_limit(modeled: int) -> Optional[int]:
    """``vmem_limit_bytes`` for a kernel whose buffers model ``modeled``
    bytes: ``None`` (the compiler's default) while they fit in it with the
    body's slack, else room for both.  Of the fused plans at full size only
    3-D ``time_block=4`` needs it (``tests/test_chip_compile.py``)."""
    need = modeled + _VMEM_SLACK
    return None if need <= _VMEM_DEFAULT else _round_up(need, 1 << 20)


def temporal_body(template: str, mem_type: Optional[str], ndim: int):
    """``(template, mem_type)`` a ``time_block>1`` kernel really runs.

    A 2-D sub-step computes its whole extended block at once whatever the
    template (a 2-D plane is a sublane row, which Mosaic cannot load at a
    dynamic unaligned offset).  A 3-D sub-step runs plane by plane over
    the VMEM frames, so temporaries stay one plane big: the streaming
    templates keep their own loop, and the blocked ones (gmem, smem, f4),
    whose per-point arithmetic is the same, run the 'vmem' shift loop."""
    if ndim == 2:
        return "gmem", None
    if template in ("shift", "unroll", "semi"):
        return template, mem_type
    return "shift", "vmem"


def _window_crop(wa, wf, B):
    """Static slice taking the logical window (halo ``wf``) out of a
    fetched, tile-aligned window (halo ``wa >= wf``)."""
    return tuple(slice(a - f, a + b + f) for a, f, b in zip(wa, wf, B))


def choose_block(user_block, template: str, ndim: int, region_shape,
                 min_halo=None):
    """Pick the BlockSpec tile.  ``min_halo`` (per-axis) forces the block to
    be at least that wide — temporal blocking fetches a ``k·h``-deep halo
    per side, and the window offset ``B − k·h`` must stay non-negative, so
    the block grows with the time depth (halo-growth geometry)."""
    if user_block is not None:
        if len(user_block) != ndim:
            raise ValueError(f"block must have {ndim} dims")
        return tuple(int(b) for b in user_block)
    base = (STREAM_BLOCK if template in ("shift", "unroll", "semi")
            else DEFAULT_BLOCK)[ndim]
    out = []
    for ax, b in enumerate(base):
        align = 128 if ax == ndim - 1 else 8
        bb = min(b, _round_up(region_shape[ax], align))
        if min_halo is not None and min_halo[ax] > bb:
            bb = _round_up(min_halo[ax], align)
        out.append(bb)
    return tuple(out)


def _deltas_for(tap_offsets: Sequence[Tuple[int, ...]]) -> List[Tuple[int, ...]]:
    """Block-neighbor offsets needed to cover these taps (star → axis
    neighbors only; box → the full needed corner set).  This is the
    shape-directed specialization at the heart of the paper."""
    ds = set()
    for offs in tap_offsets:
        axsets = []
        for o in offs:
            if o < 0:
                axsets.append((-1, 0))
            elif o > 0:
                axsets.append((0, 1))
            else:
                axsets.append((0,))
        ds.update(itertools.product(*axsets))
    return sorted(ds)


# ---------------------------------------------------------------------------
# tile assembly: paste neighbor blocks into a halo'd tile
# ---------------------------------------------------------------------------
def _paste_slices(delta, B, hg, ht):
    """(src_slice, dst_slice) per axis for pasting neighbor block `delta`
    into a tile with per-axis halo ht (ht >= hg; extra stays zero)."""
    src, dst = [], []
    for ax, d in enumerate(delta):
        b, h, t = B[ax], hg[ax], ht[ax]
        if d == -1:
            src.append(slice(b - h, b))
            dst.append(slice(t - h, t))
        elif d == 0:
            src.append(slice(0, b))
            dst.append(slice(t, t + b))
        else:
            src.append(slice(0, h))
            dst.append(slice(t + b, t + b + h))
    return tuple(src), tuple(dst)


# ---------------------------------------------------------------------------
# statement execution shared by templates
# ---------------------------------------------------------------------------
def _exec_statements(kernel: ir.StencilIR, tap_read, scalars, shape, dtype):
    """Run kernel statements; returns {output grid: value}.

    ``tap_read(grid, offsets)`` reads *old* values; center reads of grids
    written by an earlier statement return the new value (sequential
    multi-statement semantics, checked by analysis.check_read_after_write).
    """
    env: Dict[str, jnp.ndarray] = {}
    locals_env: Dict[str, jnp.ndarray] = {}

    def read(g, offs):
        if g in env and not any(offs):
            return env[g]
        return tap_read(g, offs)

    for stmt in kernel.body:
        val = lowering.eval_expr(stmt.expr, read, scalars, locals_env)
        if isinstance(stmt, ir.LocalDef):
            locals_env[stmt.name] = val
        else:
            env[stmt.grid] = jnp.broadcast_to(jnp.asarray(val, dtype), shape)
    return env


# ---------------------------------------------------------------------------
# template kernel bodies
# ---------------------------------------------------------------------------
def _make_body_blocked(kernel, info, spec, use_scratch: bool):
    """gmem / f4 (use_scratch=False) and smem (use_scratch=True) bodies."""
    B, gh, ndim = spec["B"], spec["gh"], spec["ndim"]
    in_index, scal_names, out_grids, dtype = (
        spec["in_index"], spec["scal_names"], spec["out_grids"], spec["dtype"])

    def body(*refs):
        n_in = len(in_index)
        in_refs = refs[:n_in]
        scal_refs = refs[n_in:n_in + len(scal_names)]
        out_refs = refs[n_in + len(scal_names):n_in + len(scal_names) + len(out_grids)]
        scratch = refs[n_in + len(scal_names) + len(out_grids):]

        loaded: Dict = {}

        def read_block(g, d):
            key = (g, d)
            if key not in loaded:
                loaded[key] = in_refs[in_index[key]][...]
            return loaded[key]

        scalars = {n: r[0, 0] for n, r in zip(scal_names, scal_refs)}

        if use_scratch:
            tiles = {}
            for gi, g in enumerate(spec["in_grids"]):
                sref = scratch[gi]
                sref[...] = jnp.zeros(sref.shape, dtype)
                for d in spec["deltas"][g]:
                    src, dst = _paste_slices(d, B, gh[g], gh[g])
                    sref[dst] = read_block(g, d)[src]
                tiles[g] = sref

            def tap_read(g, offs):
                h = gh[g]
                idx = tuple(slice(h[ax] + offs[ax], h[ax] + offs[ax] + B[ax])
                            for ax in range(ndim))
                return tiles[g][idx]
        else:
            def tap_read(g, offs):
                # concat-of-neighbor-block-slices, axis by axis
                def rec(axis, delta):
                    if axis == ndim:
                        return read_block(g, delta)
                    o = offs[axis]
                    if o == 0:
                        return rec(axis + 1, delta + (0,))
                    lo = rec(axis + 1, delta + ((-1,) if o < 0 else (0,)))
                    hi = rec(axis + 1, delta + ((0,) if o < 0 else (1,)))
                    cut = B[axis] + o if o < 0 else o
                    a = lax.slice_in_dim(lo, cut, B[axis], axis=axis)
                    b = lax.slice_in_dim(hi, 0, cut, axis=axis)
                    return lax.concatenate([a, b], dimension=axis)
                return rec(0, ())

        env = _exec_statements(kernel, tap_read, scalars, B, dtype)
        for g, oref in zip(out_grids, out_refs):
            oref[...] = env[g]

    return body


def _semi_linearize(kernel):
    """Linearize for the semi template: out_grid -> ([(grid, offs,
    coeff_expr)], const_expr), plus the streaming halo H.  Coefficients may
    contain center-only taps (coefficient *fields*, e.g. vp² in acoustic
    ISO) — evaluated per output plane by the streaming loop."""
    lin = {}
    written = set()
    for a in analysis.inline_locals(kernel):
        terms, const = analysis.linearize(a.expr, allow_center_fields=True)
        for t in ir.StencilIR(kernel.name, kernel.ndim, kernel.grid_params,
                              kernel.scalar_params, (a,)).taps():
            if t.grid in written:
                raise ValueError("semi template does not support reading "
                                 "a previously-written grid")
        written.add(a.grid)
        lin[a.grid] = ([(g, offs, c) for (g, offs), c in terms.items()],
                       const)
    H = max((abs(offs[0]) for terms, _ in lin.values()
             for _, offs, _ in terms), default=0)
    return lin, H


def _stream_halo(kernel, spec, variant):
    """(lin, H) for a streaming body: the x-axis window halo and, for semi,
    the linearized form."""
    if variant == "semi":
        return _semi_linearize(kernel)
    gh, in_grids = spec["gh"], spec["in_grids"]
    return None, max((gh[g][0] for g in in_grids), default=0)


class _Tile:
    """One grid's x-column tile, read plane by plane from a VMEM ref.

    The tile has x-halo ``H`` and the grid's own y/z halo.  Its ``n``
    planes map onto ref rows ``x0 + t - pad`` (``yz`` statically slices
    the y/z extent); the ``pad = H - h_g`` planes on each x side beyond
    the grid's own halo read as zero, and ``t`` is clamped into the tile
    like an out-of-range ``dynamic_slice``.  Streaming bodies index refs
    and never values, because Mosaic lowers a dynamic row load of a ref
    but no ``dynamic_slice`` of a value."""

    def __init__(self, ref, x0: int, yz: tuple, n: int, pad: int):
        self.ref, self.x0, self.yz, self.n, self.pad = ref, x0, yz, n, pad

    def plane(self, t):
        t = jnp.clip(t, 0, self.n - 1)
        if not self.pad:
            return self.ref[(self.x0 + t,) + self.yz]
        i = t - self.pad
        rows = self.n - 2 * self.pad
        v = self.ref[(self.x0 + jnp.clip(i, 0, rows - 1),) + self.yz]
        return jnp.where(jnp.logical_and(i >= 0, i < rows), v,
                         jnp.zeros_like(v))


def _stream_outputs(kernel, spec, tiles, scalars, emit, *, variant: str,
                    mem_type: str, H: int, lin):
    """Run the 2.5D streaming loop over per-grid x-column ``tiles``
    (:class:`_Tile`, x-halo ``H``, per-grid y/z halo) and hand every
    finished output plane to ``emit(t, {grid: plane})`` (``t`` the plane's
    x index in the block ``spec['B']``).  Shared by the per-application
    bodies (tiles pasted from neighbor-block refs) and the fused bodies
    (tiles read straight from the halo'd input window)."""
    B, gh, ndim = spec["B"], spec["gh"], spec["ndim"]
    out_grids, dtype = spec["out_grids"], spec["dtype"]
    in_grids = spec["in_grids"]
    plane_shape = tuple(B[1:])
    bx = B[0]

    def plane(g, t):
        """Input plane at tile-x index t, full y/z halo extent."""
        return tiles[g].plane(t)

    def center_yz(g, arr, offs_yz):
        h = gh[g][1:]
        idx = tuple(slice(h[ax] + offs_yz[ax], h[ax] + offs_yz[ax] + B[1 + ax])
                    for ax in range(ndim - 1))
        return arr[idx]

    if variant == "semi":
        def field_read_at(tile_idx):
            """Read center-only coefficient-field taps at the plane with
            the given (dynamic) tile-x index."""
            def tr(g, offs):
                return center_yz(g, plane(g, tile_idx),
                                 tuple(offs[1:]))
            return tr

        zero = jnp.zeros(plane_shape, dtype)

        def step(t, Ps):
            # Invariant: at start of step t, P[k] holds the partial sum
            # for output plane (t - 2H + k).  Input plane at tile-x
            # index t is region plane x_in = t - H; its term (g,offs=d)
            # contributes coeff(x_in - d) * u[x_in] to out plane
            # o = x_in - d (slot H - d, coeff-field tile idx t - d,
            # clamped reads only ever reach never-emitted planes).
            newPs, done = [], {}
            for og, P in zip(out_grids, Ps):
                P = list(P)
                terms, const = lin[og]
                for (g, offs, c) in terms:
                    d = offs[0]
                    cval = lowering.eval_expr(
                        c, field_read_at(t - d), scalars, {})
                    contrib = cval * center_yz(g, plane(g, t), offs[1:])
                    P[H - d] = P[H - d] + contrib
                cv = lowering.eval_expr(
                    const, field_read_at(t - H), scalars, {})
                done[og] = jnp.broadcast_to(P[0] + cv, plane_shape)
                newPs.append(tuple(P[1:]) + (zero,))
            o = t - 2 * H

            @pl.when(o >= 0)
            def _():
                emit(o, done)
            return tuple(newPs)

        Ps0 = tuple((zero,) * (2 * H + 1) for _ in out_grids)
        lax.fori_loop(0, bx + 2 * H, step, Ps0)
        return

    # ---- shift / unroll ------------------------------------------------
    win_len = {g: 2 * gh[g][0] + 1 for g in in_grids}

    if mem_type == "vmem":
        # stream straight from the VMEM tile: taps = dynamic plane slices
        def step(t, carry):
            def tap_read(g, offs):
                # tile x index of region plane t+offs[0]: t + H + offs[0]
                p = plane(g, t + H + offs[0])
                return center_yz(g, p, offs[1:])
            emit(t, _exec_statements(kernel, tap_read, scalars,
                                     plane_shape, dtype))
            return carry

        lax.fori_loop(0, bx, step, 0)
        return

    # mem_type == 'registers': rolling loop-carried window per grid.
    # Invariant: after `advance` at step t, window slot k holds the
    # plane at region coord t - hg0 + k (tile-x index t - hg0 + k + H).
    # shift carries the window as a tuple of planes (the rotation is a
    # renaming); unroll rebuilds one stacked array by concatenation.
    def init_window(g):
        n = win_len[g]
        hg0 = gh[g][0]
        planes = [jnp.zeros_like(plane(g, 0))]
        for k in range(1, n):
            planes.append(plane(g, H - hg0 + k - 1))
        return jnp.stack(planes, axis=0) if variant == "unroll" \
            else tuple(planes)

    def advance(W, new_plane):
        if variant == "unroll":
            return jnp.concatenate([W[1:], new_plane[None]], axis=0)
        return W[1:] + (new_plane,)

    def step(t, Ws):
        # newest slot holds region plane t + hg0 → tile-x index t+hg0+H
        Ws2 = tuple(advance(W, plane(g, t + gh[g][0] + H))
                    for g, W in zip(in_grids, Ws))

        def tap_read(g, offs):
            W = Ws2[in_grids.index(g)]
            slot = gh[g][0] + offs[0]
            return center_yz(g, W[slot], offs[1:])

        emit(t, _exec_statements(kernel, tap_read, scalars, plane_shape,
                                 dtype))
        return Ws2

    lax.fori_loop(0, bx, step, tuple(init_window(g) for g in in_grids))


def _make_body_streaming(kernel, info, spec, *, variant: str,
                         mem_type: str, prefetch: bool):
    """shift / unroll / semi bodies: 2.5D streaming along axis 0.  Each
    grid's x-column tile (x-halo H, its own y/z halo) is pasted from the
    neighbor-block refs into a zeroed VMEM scratch buffer, as the smem
    template does; planes beyond the grid's halo stay zero, harmless for
    the linear scatter."""
    B, gh, ndim = spec["B"], spec["gh"], spec["ndim"]
    in_index, scal_names, out_grids, dtype = (
        spec["in_index"], spec["scal_names"], spec["out_grids"], spec["dtype"])
    in_grids = spec["in_grids"]
    lin, H = _stream_halo(kernel, spec, variant)

    def body(*refs):
        n_in = len(in_index)
        in_refs = refs[:n_in]
        scal_refs = refs[n_in:n_in + len(scal_names)]
        out_refs = refs[n_in + len(scal_names):n_in + len(scal_names) + len(out_grids)]
        scratch = refs[n_in + len(scal_names) + len(out_grids):]

        scalars = {n: r[0, 0] for n, r in zip(scal_names, scal_refs)}

        tiles = {}
        for sref, g in zip(scratch, in_grids):
            ht = (H,) + tuple(gh[g][1:])
            sref[...] = jnp.zeros(sref.shape, dtype)
            for d in spec["deltas"][g]:
                src, dst = _paste_slices(d, B, gh[g], ht)
                sref[dst] = in_refs[in_index[(g, d)]][src]
            tiles[g] = _Tile(sref, 0, (slice(None),) * (ndim - 1),
                             B[0] + 2 * H, 0)

        def emit(t, env):
            for g, oref in zip(out_grids, out_refs):
                oref[pl.ds(t, 1)] = env[g][None]

        _stream_outputs(kernel, spec, tiles, scalars, emit, variant=variant,
                        mem_type=mem_type, H=H, lin=lin)

    return body


def _stream_scratch(kernel, spec, variant, dtype):
    """VMEM tile buffers of the per-application streaming body."""
    B, gh, ndim = spec["B"], spec["gh"], spec["ndim"]
    _, H = _stream_halo(kernel, spec, variant)
    return [pltpu.VMEM((B[0] + 2 * H,) + tuple(B[ax] + 2 * gh[g][ax]
                                               for ax in range(1, ndim)),
                       dtype)
            for g in spec["in_grids"]]


# ---------------------------------------------------------------------------
# top-level lowering
# ---------------------------------------------------------------------------
def lower_pallas(kernel: ir.StencilIR,
                 halos: Dict[str, Tuple[int, ...]],
                 interior_shape: Tuple[int, ...],
                 region,
                 backend):
    """Build ``fn(arrays: dict, scalars: dict) -> dict`` running the kernel
    through a generated Pallas TPU kernel (``backend.interpret`` runs the
    body in the Pallas interpreter)."""
    info = analysis.analyze(kernel)
    ndim = kernel.ndim
    if ndim not in (2, 3):
        raise ValueError("pallas backend supports 2D and 3D stencils")
    if region is None:
        region = tuple((0, s) for s in interior_shape)
    R = tuple(e - b for b, e in region)
    if int(getattr(backend, "time_block", 1) or 1) > 1:
        raise ValueError(
            "time_block > 1 is a fused time-loop feature (st.timeloop / "
            "plan_pallas); the per-application path advances one step")
    template = backend.template
    B = choose_block(backend.block, template, ndim, R)

    in_grids = info.input_grids
    out_grids = info.output_grids
    gh = {g: info.halo_per_grid.get(g, (0,) * ndim) for g in in_grids}
    for g in in_grids:
        for ax in range(ndim):
            if gh[g][ax] > B[ax]:
                raise ValueError(
                    f"halo {gh[g][ax]} exceeds block {B[ax]} on axis {ax}; "
                    "increase block size")
    if template == "f4":
        if B[-1] % 128 or (ndim >= 2 and B[-2] % 8):
            raise ValueError("f4 template requires lane-aligned blocks "
                             "(last dim %128, 2nd-last %8)")

    mem_type = backend.mem_type
    if mem_type is None:
        mem_type = "registers" if info.shape in ("star", "point") else "vmem"

    nb = tuple(-(-R[ax] // B[ax]) for ax in range(ndim))

    # taps per grid → needed block-neighbor deltas
    taps_by_grid: Dict[str, List[Tuple[int, ...]]] = {g: [] for g in in_grids}
    for t in kernel.taps():
        taps_by_grid[t.grid].append(t.offsets)
    deltas = {g: _deltas_for(taps_by_grid[g]) for g in in_grids}

    def _neighbor_index_map(d):
        def imap(*gi):
            return tuple(gi[ax] + 1 + d[ax] for ax in range(ndim))
        return imap

    in_index: Dict = {}
    in_specs = []
    for g in in_grids:
        for d in deltas[g]:
            in_index[(g, d)] = len(in_specs)
            in_specs.append(pl.BlockSpec(B, _neighbor_index_map(d)))
    scal_names = [n for n, _ in kernel.scalar_params]
    for _ in scal_names:
        in_specs.append(pl.BlockSpec((1, 1), lambda *gi: (0, 0)))

    out_specs = [pl.BlockSpec(B, lambda *gi: gi) for _ in out_grids]

    spec = dict(B=B, gh=gh, ndim=ndim, in_index=in_index,
                scal_names=scal_names, out_grids=out_grids,
                in_grids=in_grids, deltas=deltas, dtype=None)

    grid = nb

    def fn(arrays: Dict[str, jnp.ndarray], scalars: Dict[str, jnp.ndarray]):
        dtype = arrays[out_grids[0]].dtype
        spec_d = dict(spec, dtype=dtype)

        if template in ("gmem", "f4"):
            body = _make_body_blocked(kernel, info, spec_d, use_scratch=False)
            scratch_shapes = []
        elif template == "smem":
            body = _make_body_blocked(kernel, info, spec_d, use_scratch=True)
            scratch_shapes = [
                pltpu.VMEM(tuple(B[ax] + 2 * gh[g][ax] for ax in range(ndim)),
                           dtype)
                for g in in_grids]
        else:
            body = _make_body_streaming(kernel, info, spec_d,
                                        variant=template, mem_type=mem_type,
                                        prefetch=backend.prefetch)
            scratch_shapes = _stream_scratch(kernel, spec_d, template, dtype)

        # ---- pad inputs: one extra block per side + halo placement -------
        ops = []
        for g in in_grids:
            arr = arrays[g]
            halo_arr = halos[g]
            h = gh[g]
            for ax in range(ndim):
                if halo_arr[ax] + region[ax][0] < h[ax]:
                    raise ValueError(
                        f"grid '{g}' halo {halo_arr[ax]} too small for "
                        f"kernel halo {h[ax]} at region {region[ax]}")
            sl = tuple(slice(halo_arr[ax] + region[ax][0] - h[ax],
                             halo_arr[ax] + region[ax][1] + h[ax])
                       for ax in range(ndim))
            W = arr[sl]
            pads = []
            for ax in range(ndim):
                before = B[ax] - h[ax]
                total = (nb[ax] + 2) * B[ax]
                pads.append((before, total - before - W.shape[ax]))
            P = jnp.pad(W, pads)
            for d in deltas[g]:
                ops.append(P)
        for n in scal_names:
            ops.append(jnp.asarray(scalars[n], jnp.float32).reshape(1, 1))

        call = pl.pallas_call(
            body,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=[jax.ShapeDtypeStruct(
                tuple(nb[ax] * B[ax] for ax in range(ndim)), dtype)
                for _ in out_grids],
            scratch_shapes=scratch_shapes,
            interpret=backend.interpret,
            name=f"stencil_{kernel.name}_{template}",
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",) * ndim),
        )
        outs = call(*ops)
        if not isinstance(outs, (list, tuple)):
            outs = [outs]

        result = dict(arrays)
        for g, O in zip(out_grids, outs):
            full = arrays[g]
            halo_arr = halos[g]
            idx = tuple(slice(halo_arr[ax] + region[ax][0],
                              halo_arr[ax] + region[ax][1])
                        for ax in range(ndim))
            cut = tuple(slice(0, R[ax]) for ax in range(ndim))
            result[g] = full.at[idx].set(O[cut])
        return result

    return fn


# ---------------------------------------------------------------------------
# fused time-loop path: one-time layout stage + per-step kernel stage
# ---------------------------------------------------------------------------
def _block_origin(B):
    """Interior coordinates of this program instance's block.  Read once
    at the top of a body: interpret mode resolves ``program_id`` only
    there, not inside the body's loops."""
    return tuple(pl.program_id(ax) * b for ax, b in enumerate(B))


def _valid_mask(origin, B, R, ext=None, plane=None):
    """Mask of positions that belong to the true interior, over the block
    at ``origin`` (``_block_origin``) extended by ``ext`` per side
    (temporal sub-steps compute shrinking shells that reach below
    coordinate 0 and past R; the block may also overhang the interior
    when R is not a block multiple).  ``plane=t`` gives the mask of
    x-plane ``t`` of that extended block only."""
    ndim = len(B)
    ext = ext or (0,) * ndim
    first = 0 if plane is None else 1
    shape = tuple(B[ax] + 2 * ext[ax] for ax in range(first, ndim))
    mask = None
    for ax in range(ndim):
        coord = origin[ax] - ext[ax]
        if ax < first:
            coord = coord + plane
        else:
            coord = coord + lax.broadcasted_iota(jnp.int32, shape, ax - first)
        m = jnp.logical_and(coord >= 0, coord < R[ax])
        mask = m if mask is None else jnp.logical_and(mask, m)
    return jnp.broadcast_to(mask, shape)


def _window_tiles(spec, H, wins, ext):
    """Streaming tiles over windows ``wins[g] = (ref, origin)``: the tile
    of grid ``g`` covers the block extended by ``ext`` plus the grid's own
    halo, read from the window whose logical (halo ``wf``) origin sits at
    ``origin`` in the ref."""
    B, gh, ndim, wf = spec["B"], spec["gh"], spec["ndim"], spec["wf"]
    tiles = {}
    for g in spec["in_grids"]:
        ref, org = wins[g]
        w, h = wf[g], gh[g]
        lo = tuple(org[ax] + w[ax] - ext[ax] - h[ax] for ax in range(ndim))
        yz = tuple(slice(lo[ax], lo[ax] + B[ax] + 2 * (ext[ax] + h[ax]))
                   for ax in range(1, ndim))
        tiles[g] = _Tile(ref, lo[0], yz, B[0] + 2 * ext[0] + 2 * H,
                         H - h[0])
    return tiles


def _make_body_fused(kernel, info, spec, *, template: str, mem_type: str):
    """Persistent-layout step body: one halo'd *window* ref per grid
    (deduplicated operands), outputs written in padded layout with
    pass-through of the old value outside the true interior (preserves the
    grid halo and the padding across fused steps — no repacking)."""
    B, gh, ndim, R = spec["B"], spec["gh"], spec["ndim"], spec["R"]
    opnd_index, scal_names, out_grids, dtype = (
        spec["opnd_index"], spec["scal_names"], spec["out_grids"],
        spec["dtype"])
    crop = spec["crop"]
    streaming = template in ("shift", "unroll", "semi")
    lin = H = None
    if streaming:
        lin, H = _stream_halo(kernel, spec, template)

    def body(*refs):
        n_in = len(opnd_index)
        in_refs = refs[:n_in]
        scal_refs = refs[n_in:n_in + len(scal_names)]
        out_refs = refs[n_in + len(scal_names):]

        scalars = {n: r[0, 0] for n, r in zip(scal_names, scal_refs)}
        # outputs have center-only taps, so their window is the bare block
        # (crop offset 0): the old value outside the interior is read there
        old = {g: in_refs[opnd_index[g]] for g in out_grids}
        origin = _block_origin(B)

        if streaming:
            wins = {g: (in_refs[opnd_index[g]],
                        tuple(c.start for c in crop[g]))
                    for g in spec["in_grids"]}
            tiles = _window_tiles(spec, H, wins, (0,) * ndim)

            def emit(t, env):
                mask = _valid_mask(origin, B, R, plane=t)
                for g, oref in zip(out_grids, out_refs):
                    oref[pl.ds(t, 1)] = jnp.where(
                        mask, env[g], old[g][pl.ds(t, 1)][0])[None]

            _stream_outputs(kernel, spec, tiles, scalars, emit,
                            variant=template, mem_type=mem_type, H=H,
                            lin=lin)
            return

        loaded: Dict = {}

        def win(g):
            if g not in loaded:
                loaded[g] = in_refs[opnd_index[g]][crop[g]]
            return loaded[g]

        def tap_read(g, offs):
            h = gh[g]
            idx = tuple(slice(h[ax] + offs[ax], h[ax] + offs[ax] + B[ax])
                        for ax in range(ndim))
            return win(g)[idx]

        env = _exec_statements(kernel, tap_read, scalars, B, dtype)
        mask = _valid_mask(origin, B, R)
        for g, oref in zip(out_grids, out_refs):
            # outside the interior keep the old value
            oref[...] = jnp.where(mask, env[g], old[g][...])

    return body


def _make_body_temporal(kernel, info, spec, *, template: str, mem_type: str,
                        time_block: int, swap):
    """In-kernel temporal blocking: advance ``time_block`` leapfrog steps
    per kernel invocation (paper-style time skewing brought inside the
    Pallas block, cf. ``distributed._lower_time_skewed`` at pod level).

    Each operand grid is fetched once as a window with an expanded halo
    (``k·h`` for the swap pair, ``(k−1)·h + h_g`` for coefficient grids).
    The swap pair's windows are copied into VMEM scratch *frames*, which
    the sub-steps update in place; coefficient grids are read straight
    from their fetched windows.  Sub-step ``j`` evaluates the template
    body over the block extended by ``(k−1−j)·h`` per side — the valid
    region shrinks by ``h`` per step, shells being recomputed redundantly
    by neighboring blocks — and writes the leapfrog buffers alternately
    (sub-step 0 → ``swap[0]``'s buffer, 1 → ``swap[1]``'s, …), which is
    exactly the per-step write+rotate sequence expressed in buffer space.
    Outside the true interior every sub-step passes the frame's old value
    through, so grid-halo cells keep their original values and feed later
    sub-steps unchanged (per-step boundary semantics).  Only the final
    ``B`` interior of each swap frame is written back — HBM sees one read
    and one write per grid per ``k`` steps.

    The write-back is *double-buffered*: outputs alias dedicated
    destination operands (``refs[n_in:n_in+len(step_out)]``, never read
    here), NOT the window operands.  The expanded windows reach ``k·h``
    into neighboring blocks' output interiors, and on real TPU the grid
    runs sequentially — aliasing the read buffers in place would let
    later blocks fetch halo data that earlier blocks already advanced
    ``k`` steps (interpret mode reads inputs functionally and hides the
    hazard).
    """
    B, gh, ndim, R = spec["B"], spec["gh"], spec["ndim"], spec["R"]
    opnd_index, scal_names, dtype = (
        spec["opnd_index"], spec["scal_names"], spec["dtype"])
    in_grids, crop = spec["in_grids"], spec["crop"]
    wf, hvec = spec["wf"], spec["hvec"]
    step_out = spec["step_out_grids"]          # (written, other) buffers
    k = time_block
    written, other = swap
    lin, H = _stream_halo(kernel, spec, template)

    def body(*refs):
        n_in = len(opnd_index)
        in_refs = refs[:n_in]
        # destination operands (aliased to the outputs) sit between the
        # read windows and the scalars; their values are never read
        n_dst = len(step_out)
        scal_refs = refs[n_in + n_dst:n_in + n_dst + len(scal_names)]
        n_out = len(step_out)
        out_refs = refs[n_in + n_dst + len(scal_names):
                        n_in + n_dst + len(scal_names) + n_out]
        scratch = refs[n_in + n_dst + len(scal_names) + n_out:]

        scalars = {n: r[0, 0] for n, r in zip(scal_names, scal_refs)}
        origin = _block_origin(B)
        # frame of each buffer: (ref, origin of its logical window)
        frames = {g: (in_refs[i], tuple(c.start for c in crop[g]))
                  for g, i in opnd_index.items()}
        for g, sref in zip(step_out, scratch):
            sref[...] = in_refs[opnd_index[g]][crop[g]]
            frames[g] = (sref, (0,) * ndim)

        for j in range(k):
            ext = tuple((k - 1 - j) * hvec[ax] for ax in range(ndim))
            S = tuple(B[ax] + 2 * ext[ax] for ax in range(ndim))
            # leapfrog in buffer space: IR names ↔ buffers alternate
            nm = {written: written, other: other} if j % 2 == 0 \
                else {written: other, other: written}
            tgt_ref, _ = frames[nm[written]]
            w = wf[nm[written]]
            lo = tuple(w[ax] - ext[ax] for ax in range(ndim))

            if ndim == 2:
                # 2-D planes are rows on the sublane axis, which Mosaic
                # cannot load at a dynamic unaligned offset; a 2-D block
                # is small, so the sub-step computes all of it at once
                def tap_read(g, offs, ext=ext, S=S, nm=nm):
                    ref, org = frames[nm.get(g, g)]
                    w = wf[nm.get(g, g)]
                    return ref[tuple(
                        slice(org[ax] + w[ax] - ext[ax] + offs[ax],
                              org[ax] + w[ax] - ext[ax] + offs[ax] + S[ax])
                        for ax in range(ndim))]

                val = _exec_statements(kernel, tap_read, scalars, S,
                                       dtype)[written]
                region = tuple(slice(lo[ax], lo[ax] + S[ax])
                               for ax in range(ndim))
                tgt_ref[region] = jnp.where(
                    _valid_mask(origin, B, R, ext), val, tgt_ref[region])
                continue

            # planes are emitted in place: outputs have center-only taps,
            # so plane t of the target is read before it is written and
            # never read again in this sub-step
            wins = {}
            for g in in_grids:
                ref, org = frames[nm.get(g, g)]
                # the tile geometry follows grid g, its data the buffer
                # the name is bound to in this sub-step
                wins[g] = (ref, tuple(org[ax] + wf[nm.get(g, g)][ax]
                                      - wf[g][ax] for ax in range(ndim)))
            tiles = _window_tiles(spec, H, wins, ext)

            # outside the true interior the buffer keeps its original
            # (grid-halo) value — re-imposed every sub-step so shells never
            # leak boundary garbage into later sub-steps
            def emit(t, env, lo=lo, ext=ext, S=S, tgt_ref=tgt_ref):
                idx = (lo[0] + t,) + tuple(
                    slice(lo[ax], lo[ax] + S[ax]) for ax in range(1, ndim))
                mask = _valid_mask(origin, B, R, ext, plane=t)
                tgt_ref[idx] = jnp.where(mask, env[written], tgt_ref[idx])

            _stream_outputs(kernel, dict(spec, B=S), tiles, scalars, emit,
                            variant=template, mem_type=mem_type, H=H,
                            lin=lin)

        for g, oref in zip(step_out, out_refs):
            ref, _ = frames[g]
            w = wf[g]
            oref[...] = ref[tuple(slice(w[ax], w[ax] + B[ax])
                                  for ax in range(ndim))]

    return body


class PallasPlan:
    """Split Pallas lowering for fused time stepping.

    ``to_padded``  — one-time layout stage: convert each participating grid
                     to the persistent block-padded layout (ONE ``jnp.pad``
                     per grid; counted in ``PAD_COUNT``).
    ``step``       — kernel stage: one ``pallas_call`` that reads halo'd
                     windows (one deduplicated operand per grid) and
                     writes each output grid in-place in padded layout
                     (``input_output_aliases``), passing the old value
                     through outside the interior so halos survive.  The
                     in-place aliasing is only legal because outputs are
                     restricted to center-only taps: every read of an
                     aliased buffer stays inside the block the same
                     program instance writes.  With
                     ``backend.time_block=k`` one call advances k leapfrog
                     steps on k·h-expanded windows and writes *both* swap
                     buffers back — but the k·h windows overlap
                     neighboring blocks' output interiors, so the outputs
                     are double-buffered: they alias dedicated
                     destination operands (``make_spares``), never the
                     read windows (see ``_make_body_temporal``).
    ``from_padded``— write padded interiors back into full (grid-halo'd)
                     arrays at a fusion boundary.

    Grids named in ``swap`` share a common layout halo so their buffers can
    be rotated between steps without re-laying-out.
    """

    def __init__(self, kernel: ir.StencilIR,
                 halos: Dict[str, Tuple[int, ...]],
                 interior_shape: Tuple[int, ...],
                 backend,
                 swap: Optional[Tuple[str, str]] = None):
        info = analysis.analyze(kernel)
        ndim = kernel.ndim
        if ndim not in (2, 3):
            raise ValueError("pallas backend supports 2D and 3D stencils")
        template, mem_type = backend.template, backend.mem_type
        R = tuple(interior_shape)
        k = int(getattr(backend, "time_block", 1) or 1)
        if k < 1:
            raise ValueError("time_block must be >= 1")
        if k > 1:
            template, mem_type = temporal_body(template, mem_type, ndim)
        hvec = tuple(info.halo) if info.halo else (0,) * ndim
        in_grids = info.input_grids
        out_grids = info.output_grids
        if k > 1:
            if swap is None:
                raise ValueError(
                    "time_block > 1 requires a swap pair: the in-kernel "
                    "sub-steps are the leapfrog write+rotate sequence")
            if len(out_grids) != 1 or out_grids[0] != swap[0]:
                raise ValueError(
                    "time_block > 1 supports single-output kernels writing "
                    f"swap[0] (outputs: {out_grids}, swap: {swap})")
        B = choose_block(backend.block, template, ndim, R,
                         min_halo=tuple(k * h for h in hvec) if k > 1
                         else None)
        opnd_grids = tuple(g for g in kernel.grid_params
                           if g in set(in_grids) | set(out_grids))
        gh = {g: info.halo_per_grid.get(g, (0,) * ndim) for g in opnd_grids}
        for g in out_grids:
            if any(gh[g]):
                raise ValueError(
                    f"fused time stepping requires center-only taps of the "
                    f"output grid '{g}' (its padded buffer is written "
                    "in-place while neighbors still read it)")
        for g in in_grids:
            for ax in range(ndim):
                if gh[g][ax] > B[ax]:
                    raise ValueError(
                        f"halo {gh[g][ax]} exceeds block {B[ax]} on axis "
                        f"{ax}; increase block size")
        # expanded window (frame) halo per operand: the swap pair trades
        # buffers between sub-steps so both carry the full k·h; coefficient
        # grids are only read while the valid region is ≥ (k−1−j)·h wide
        if k > 1:
            wf = {g: tuple(k * hvec[ax] for ax in range(ndim))
                  if g in swap
                  else tuple((k - 1) * hvec[ax] + gh[g][ax]
                             for ax in range(ndim))
                  for g in opnd_grids}
        else:
            wf = dict(gh)
        for g in opnd_grids:
            for ax in range(ndim):
                if wf[g][ax] > B[ax]:
                    raise ValueError(
                        f"time_block={k}: expanded halo {wf[g][ax]} exceeds "
                        f"block {B[ax]} on axis {ax} (need k·h <= block "
                        "extent; reduce time_block or increase block)")
        if template == "f4" and (B[-1] % 128 or B[-2] % 8):
            raise ValueError("f4 template requires lane-aligned blocks "
                             "(last dim %128, 2nd-last %8)")
        if mem_type is None:
            mem_type = "registers" if info.shape in ("star", "point") \
                else "vmem"

        # layout halo: swap partners trade buffers between steps, so they
        # must share one padded geometry (the elementwise max of their taps)
        hw = dict(gh)
        if swap is not None:
            a, b = swap
            if a not in opnd_grids or b not in opnd_grids:
                raise ValueError(f"swap grids {swap} must appear in kernel")
            m = tuple(max(gh[a][ax], gh[b][ax]) for ax in range(ndim))
            hw[a] = hw[b] = m
        for g in opnd_grids:
            for ax in range(ndim):
                if halos[g][ax] < hw[g][ax]:
                    raise ValueError(
                        f"grid '{g}' halo {halos[g][ax]} too small for "
                        f"layout halo {hw[g][ax]} on axis {ax}")

        nb = tuple(-(-R[ax] // B[ax]) for ax in range(ndim))
        padded_shape = tuple((nb[ax] + 2) * B[ax] for ax in range(ndim))
        scal_names = [n for n, _ in kernel.scalar_params]

        # fetched window halo: the DMA that brings a window into VMEM must
        # start and end on the (8, 128) tile of the last two axes, so where
        # the block is a tile multiple the logical halo ``wf`` is rounded up
        # to the tile (wa <= B still holds) and the body crops the logical
        # window back out of the fetched one (``_window_crop``).  A user's
        # block that is not a tile multiple keeps the exact window: the
        # interpreter runs it, the TPU lowering refuses it.
        ts = tuple(_tile(ax, ndim) if B[ax] % _tile(ax, ndim) == 0 else 1
                   for ax in range(ndim))
        wa = {g: tuple(_round_up(wf[g][ax], ts[ax]) for ax in range(ndim))
              for g in opnd_grids}

        def _window_map(w):
            # (g·(B/t) + (B−w)/t)·t: the tile factor t outermost, the form
            # in which Mosaic proves the window start tile-aligned
            def imap(*gi):
                return tuple((gi[ax] * (B[ax] // t) + (B[ax] - w[ax]) // t)
                             * t for ax, t in enumerate(ts))
            return imap

        # per-invocation outputs: with time_block > 1 both swap buffers are
        # advanced in-kernel, so both are written back (aliased in-place)
        step_out = tuple(out_grids) if k == 1 else tuple(swap)

        in_specs = []
        for g in opnd_grids:
            w = wa[g]
            in_specs.append(pl.BlockSpec(
                tuple(pl.Element(B[ax] + 2 * w[ax]) for ax in range(ndim)),
                _window_map(w)))
        if k > 1:
            # double-buffered outputs: the k·h-expanded windows reach into
            # neighboring blocks' output interiors, and the TPU grid runs
            # sequentially — aliasing the read buffers in place would let
            # later blocks fetch halos already advanced k steps.  Outputs
            # therefore alias dedicated block-sized destination operands
            # (one per advanced grid, never read by the body); the engine
            # ping-pongs them against the read buffers between invocations.
            for _ in step_out:
                in_specs.append(pl.BlockSpec(
                    B, lambda *gi: tuple(g + 1 for g in gi)))
            aliases = {len(opnd_grids) + oi: oi
                       for oi in range(len(step_out))}
        else:
            # k=1 may alias in place: outputs are center-only-tapped, so
            # no program instance reads outside the block it writes
            aliases = {opnd_grids.index(g): oi
                       for oi, g in enumerate(step_out)}
        for _ in scal_names:
            in_specs.append(pl.BlockSpec((1, 1), lambda *gi: (0, 0)))
        out_specs = [pl.BlockSpec(B, lambda *gi: tuple(g + 1 for g in gi))
                     for _ in step_out]

        self.kernel, self.info, self.backend = kernel, info, backend
        self.halos = {g: tuple(halos[g]) for g in opnd_grids}
        self.template, self.mem_type = template, mem_type
        self.ndim, self.R, self.B, self.nb = ndim, R, B, nb
        self.gh, self.hw, self.swap = gh, hw, swap
        self.time_block, self.hvec, self.wf, self.wa = k, hvec, wf, wa
        self.in_grids, self.out_grids = in_grids, out_grids
        self.step_out_grids = step_out
        self.opnd_grids, self.scal_names = opnd_grids, scal_names
        self.padded_shape = padded_shape
        self._in_specs, self._out_specs = in_specs, out_specs
        self._aliases = aliases
        self._calls: Dict = {}
        # grids whose padded buffers change across steps (need write-back)
        self.touched = tuple(g for g in opnd_grids
                             if g in set(out_grids) | set(swap or ()))

    # -- traffic model -----------------------------------------------------
    @property
    def _dest_fetches(self) -> int:
        """Destination-operand block fetches per invocation: the k>1
        double-buffered outputs alias dedicated operands whose blocks are
        DMA'd in like any input even though the body never reads them."""
        return len(self.step_out_grids) if self.time_block > 1 else 0

    @property
    def grid_reads_per_step(self) -> float:
        """Grid HBM fetches per time step (each invocation reads one window
        per operand grid plus the destination blocks, and covers
        ``time_block`` steps)."""
        return ((len(self.opnd_grids) + self._dest_fetches)
                / self.time_block)

    @property
    def grid_writes_per_step(self) -> float:
        """Grid-block HBM writes per time step."""
        return len(self.step_out_grids) / self.time_block

    def hbm_bytes_per_step(self, itemsize: int = 4) -> float:
        """Modeled HBM bytes moved per time step by the kernel stage: every
        block fetches one expanded-halo window per operand grid (plus the
        block-sized destination operands when double-buffered) and writes
        one ``B`` block per output, amortized over ``time_block`` steps.

        Like ``TRAFFIC_COUNT`` this models the steady-state kernel stage
        only: one-time layout-stage costs per fusion window — the
        ``to_padded`` pads and the ``make_spares`` copies — are excluded
        (they amortize over the window length, which the plan does not
        know)."""
        nblocks = math.prod(self.nb)
        read = sum(math.prod(self.B[ax] + 2 * self.wa[g][ax]
                             for ax in range(self.ndim))
                   for g in self.opnd_grids)
        read += self._dest_fetches * math.prod(self.B)
        write = len(self.step_out_grids) * math.prod(self.B)
        return nblocks * (read + write) * itemsize / self.time_block

    def vmem_bytes(self, itemsize: int = 4) -> int:
        """Modeled VMEM of one kernel invocation: the pipeline holds two
        copies of every fetched window, destination block and output
        block, plus the ``time_block>1`` frames."""
        blk = math.prod(self.B)
        pipelined = sum(math.prod(self.B[ax] + 2 * self.wa[g][ax]
                                  for ax in range(self.ndim))
                        for g in self.opnd_grids)
        pipelined += (self._dest_fetches + len(self.step_out_grids)) * blk
        frames = sum(math.prod(self.B[ax] + 2 * self.wf[g][ax]
                               for ax in range(self.ndim))
                     for g in self.step_out_grids) \
            if self.time_block > 1 else 0
        return (2 * pipelined + frames) * itemsize

    def layout_bytes_per_window(self, itemsize: int = 4) -> float:
        """Modeled HBM bytes of the one-time per-fusion-window costs that
        ``hbm_bytes_per_step`` amortizes away: the ``to_padded`` layout
        stage (read each operand's layout-halo'd window, write its padded
        buffer), the ``make_spares`` double-buffer copies when temporally
        blocked (read + write one padded buffer per advanced grid), and
        the ``from_padded`` write-back of every touched grid's interior at
        the window boundary.  The cost model charges this once per window,
        which is why larger ``fuse_steps`` predict cheaper on this path."""
        padded = math.prod(self.padded_shape)
        total = 0.0
        for g in self.opnd_grids:
            total += math.prod(self.R[ax] + 2 * self.hw[g][ax]
                               for ax in range(self.ndim)) + padded
        if self.time_block > 1:
            total += 2 * padded * len(self.step_out_grids)
        total += 2 * math.prod(self.R) * len(self.touched)
        return total * itemsize

    def count_window(self, steps: int, batch: int = 1) -> None:
        """Accumulate modeled traffic for a fused window of ``steps`` time
        steps into ``TRAFFIC_COUNT`` (windows of ``time_block`` plus a
        remainder of single steps, mirroring the engine's decomposition).
        Remainder steps run through the single-step plan, which aliases in
        place and fetches no destination blocks.  With ``batch=B`` (the
        vmapped scenario axis — an extra leading grid dimension of the same
        ``pallas_call``) every grid's traffic scales by B; modeled ``steps``
        stay per-scenario time steps."""
        k = self.time_block
        m, r = divmod(int(steps), k)
        b = max(1, int(batch))
        TRAFFIC_COUNT["grid_reads"] += b * (
            m * (len(self.opnd_grids) + self._dest_fetches)
            + r * len(self.opnd_grids))
        TRAFFIC_COUNT["grid_writes"] += b * (m * len(self.step_out_grids)
                                             + r * len(self.out_grids))
        TRAFFIC_COUNT["steps"] += int(steps)

    # -- layout stage ------------------------------------------------------
    def to_padded(self, arrays: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
        B, nb, R, ndim = self.B, self.nb, self.R, self.ndim
        padded = {}
        for g in self.opnd_grids:
            arr = arrays[g]
            ha, w = self.halos[g], self.hw[g]
            sl = tuple(slice(ha[ax] - w[ax], ha[ax] + R[ax] + w[ax])
                       for ax in range(ndim))
            W = arr[sl]
            pads = []
            for ax in range(ndim):
                before = B[ax] - w[ax]
                total = (nb[ax] + 2) * B[ax]
                pads.append((before, total - before - W.shape[ax]))
            padded[g] = jnp.pad(W, pads)
            PAD_COUNT[g] += 1
            PAD_COUNT["total"] += 1
        return padded

    # -- kernel stage ------------------------------------------------------
    def _call_for(self, dtype):
        key = jnp.dtype(dtype).name
        call = self._calls.get(key)
        if call is None:
            spec = dict(B=self.B, gh=self.gh, ndim=self.ndim, R=self.R,
                        opnd_index={g: i for i, g in
                                    enumerate(self.opnd_grids)},
                        scal_names=self.scal_names,
                        out_grids=self.out_grids, in_grids=self.in_grids,
                        wf=self.wf, hvec=self.hvec,
                        crop={g: _window_crop(self.wa[g], self.wf[g], self.B)
                              for g in self.opnd_grids},
                        step_out_grids=self.step_out_grids,
                        dtype=dtype)
            if self.time_block > 1:
                body = _make_body_temporal(self.kernel, self.info, spec,
                                           template=self.template,
                                           mem_type=self.mem_type,
                                           time_block=self.time_block,
                                           swap=self.swap)
            else:
                body = _make_body_fused(self.kernel, self.info, spec,
                                        template=self.template,
                                        mem_type=self.mem_type)
            # k>1: one VMEM frame per swap buffer (its logical window)
            scratch = [pltpu.VMEM(tuple(self.B[ax] + 2 * self.wf[g][ax]
                                        for ax in range(self.ndim)), dtype)
                       for g in self.step_out_grids] \
                if self.time_block > 1 else []
            call = pl.pallas_call(
                body,
                grid=self.nb,
                in_specs=self._in_specs,
                out_specs=self._out_specs,
                out_shape=[jax.ShapeDtypeStruct(self.padded_shape, dtype)
                           for _ in self.step_out_grids],
                scratch_shapes=scratch,
                input_output_aliases=self._aliases,
                interpret=self.backend.interpret,
                name=(f"stencil_{self.kernel.name}_{self.template}"
                      f"_fused_step_k{self.time_block}"),
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("arbitrary",) * self.ndim,
                    vmem_limit_bytes=_vmem_limit(
                        self.vmem_bytes(jnp.dtype(dtype).itemsize))),
            )
            self._calls[key] = call
        return call

    def make_spares(self, padded: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
        """Initial destination buffers for the double-buffered
        ``time_block>1`` kernel stage.  The kernel writes only interior
        blocks; the surrounding ring blocks (zero padding + grid halo) are
        taken over from the destination, so each spare starts as a copy of
        its grid's current buffer (identical ring — halo cells never change
        across steps)."""
        return {g: jnp.copy(padded[g]) for g in self.step_out_grids}

    def step(self, padded: Dict[str, jnp.ndarray],
             scalars: Dict[str, jnp.ndarray],
             spares: Optional[Dict[str, jnp.ndarray]] = None
             ) -> Dict[str, jnp.ndarray]:
        """One kernel invocation entirely in padded layout (jittable):
        one time step when ``time_block`` is 1, else ``time_block`` leapfrog
        steps with both swap buffers advanced.  With ``time_block>1`` the
        caller must pass ``spares`` — one destination buffer per
        ``step_out_grids`` entry (``make_spares``); outputs land in the
        spares' memory so the read buffers stay intact for the whole
        sequential device grid, and the buffers just read become the next
        invocation's spares.  Buffer↔name bindings are untouched; the
        caller applies the leapfrog rotation parity (``time_block``
        rotations) to the names."""
        dtype = padded[self.out_grids[0]].dtype
        ops = [padded[g] for g in self.opnd_grids]
        if self.time_block > 1:
            if spares is None:
                raise ValueError(
                    "time_block > 1 kernel stage is double-buffered: pass "
                    "spares= destination buffers (plan.make_spares)")
            ops += [spares[g] for g in self.step_out_grids]
        ops += [jnp.asarray(scalars[n], jnp.float32).reshape(1, 1)
                for n in self.scal_names]
        outs = self._call_for(dtype)(*ops)
        if not isinstance(outs, (list, tuple)):
            outs = [outs]
        new = dict(padded)
        for g, O in zip(self.step_out_grids, outs):
            new[g] = O
        return new

    # -- boundary stage ----------------------------------------------------
    def from_padded(self, padded: Dict[str, jnp.ndarray],
                    arrays: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
        """Merge padded interiors back into the full (grid-halo'd) arrays."""
        B, R, ndim = self.B, self.R, self.ndim
        result = dict(arrays)
        blk = tuple(slice(B[ax], B[ax] + R[ax]) for ax in range(ndim))
        for g in self.touched:
            ha = self.halos[g]
            idx = tuple(slice(ha[ax], ha[ax] + R[ax]) for ax in range(ndim))
            result[g] = result[g].at[idx].set(padded[g][blk])
        return result


def plan_pallas(kernel: ir.StencilIR,
                halos: Dict[str, Tuple[int, ...]],
                interior_shape: Tuple[int, ...],
                backend,
                swap: Optional[Tuple[str, str]] = None) -> PallasPlan:
    """Build the split (layout / per-step kernel) lowering used by the
    fused time-loop engine (``repro.core.timeloop``)."""
    return PallasPlan(kernel, halos, interior_shape, backend, swap=swap)
