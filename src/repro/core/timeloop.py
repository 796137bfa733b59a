"""Fused time-loop execution engine: single-program time stepping.

The per-step path (``st.map`` inside a Python loop) pays one compiled call,
one host↔device sync and one dict-of-arrays repack per time step — and on
the Pallas path a full ``jnp.pad`` halo repack per grid per step.  Devito
and the Cerebras stencil work both show that fusing the time dimension into
the generated program is where stencil throughput lives; this module is
that fusion for all three backends:

  xla          — ``steps`` applications + leapfrog buffer rotation run in
                 one jitted ``lax.fori_loop`` program with donated buffers
                 (``lowering.lower_jax_window``).
  pallas       — lowering is split into a one-time layout stage (grids →
                 persistent block-padded layout, ONE ``jnp.pad`` per grid
                 per fusion window) and a per-invocation kernel stage
                 executed inside the fused loop (``codegen.plan_pallas``);
                 outputs are written in-place in padded layout and the grid
                 halo is passed through, so no repacking happens between
                 steps.  With ``time_block=k`` on the backend, one kernel
                 invocation advances k leapfrog steps entirely in VMEM
                 (expanded k·h halos, in-kernel temporal blocking) — the
                 fusion window is decomposed into ⌊kw/k⌋ k-step invocations
                 plus a remainder of single steps, so any window length
                 runs exactly — ``fuse_steps`` (the host-sync / between-
                 hook cadence) is honored as requested, never rounded to
                 the temporal depth.  The k-step invocations double-buffer
                 the swap pair: outputs land in spare padded buffers that
                 ping-pong with the read buffers between invocations.
                 The loop runs steps (and invocations) in pairs, so no
                 buffer changes carry slot inside it; the host applies the
                 window's leapfrog parity by renaming.
                 Modeled HBM traffic per window is accumulated in
                 ``codegen.TRAFFIC_COUNT`` alongside ``PAD_COUNT``.
  distributed  — the ENTIRE fusion window runs as ONE jitted shard_map'd
                 program (``distributed.lower_distributed_window``) on
                 the halo-padded grids, sharded on the mesh in and out
                 (the crop to interiors and the write-back are part of
                 it, so nothing of the global extent is gathered where
                 the mesh divides the padded extents): a
                 ``lax.fori_loop`` over depth-k exchange groups
                 (k = ``time_steps`` × inner ``time_block``) plus an
                 unrolled remainder group, each group = one k·h-wide halo
                 exchange + k kernel applications on shrinking regions +
                 the leapfrog swap, with the deep-interior pre-pass issued
                 before the ppermutes resolve so communication overlaps
                 compute across steps.  ``fuse_steps`` stays the host-sync
                 / between-hook cadence; ``time_steps``/``time_block`` set
                 only the exchange *depth* within the window.  Batched
                 scenarios ride a leading unsharded axis inside the same
                 program.  Each window sent adds its exchange groups and
                 modeled collective bytes to ``distributed.EXCHANGE_STATS``.

The host syncs only at fusion-window boundaries; an optional ``between``
hook runs there (e.g. acoustic source injection).

With ``batch=B`` the engine carries a leading *scenario* dimension: one
compiled program advances B independent grid-sets (distinct initial
conditions, coefficient grids, and scalar parameters) per step.  The
per-window program is ``jax.vmap``-ped over the leading axis — on the
pallas path XLA's batching rule turns the scenario axis into an extra
leading grid dimension of the same ``pallas_call`` (the batched operand
layout), so the kernel stage stays one program.  Scalars may be python
floats (broadcast) or ``(B,)`` arrays (per-scenario).  The batched xla
path additionally supports *masked* windows for shape-bucketed serving
(``lowering.lower_jax_window_masked``): a per-scenario spatial mask
freezes cells outside a request's true sub-domain and a per-scenario
step budget freezes finished scenarios, both exactly.

This module is DSL-agnostic: it works on dicts of jnp arrays.  The user
API is ``st.timeloop(...)`` / ``st.launch(..., fuse_steps=K)`` in
``core/dsl.py``; the array-level wrapper is
``repro.kernels.stencil.ops.stencil_timeloop``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from . import ir as _ir
from . import lowering
from . import trace as _trace


def window_parts(kw: int, k_inner: int) -> list:
    """Decompose a fusion window that is not a multiple of the temporal
    depth: the largest ``k_inner`` multiple (depth active) plus the
    remainder — an indivisible window must degrade only its remainder to
    depth 1, never the whole window.  Both backends now decompose
    *inside* one program (pallas: ⌊kw/k⌋ k-step invocations + single
    steps; distributed: fori_loop groups + an unrolled remainder group,
    the same split expressed by ``halo.HaloSpec.group_depths``); this
    helper states the invariant and backs the group accounting."""
    if k_inner > 1 and kw > k_inner and kw % k_inner:
        return [kw - kw % k_inner, kw % k_inner]
    return [kw]


def backend_time_block(backend) -> int:
    """Effective in-kernel temporal depth of a backend: the knob rides on
    the backend itself for pallas, on the pallas ``inner`` for distributed
    wrappers, and is 1 everywhere else.  The single reader shared by the
    engine and the distributed lowering — they must agree on the depth or
    the window decomposition and the exchange width disagree."""
    if getattr(backend, "kind", "") == "distributed":
        backend = getattr(backend, "inner", None)
    return int(getattr(backend, "time_block", 1) or 1)


def normalize_fuse(fuse_steps: Optional[int], steps: int,
                   max_fuse: Optional[int] = None) -> int:
    """Fusion-window normalization shared by the engine and the autotuner
    (both must agree on the window that actually runs).

    Clamp the request to the loop length and the overlapped-tiling bound
    (``max_fuse``) — the hard constraints — and nothing else.
    ``fuse_steps`` is the host-sync / ``between``-hook cadence (source
    injection, diagnostics), which the engine honors *exactly*: in-kernel
    temporal blocking never alters the window, because every window
    decomposes into ⌊kw/k⌋ k-step invocations plus a single-step
    remainder (in-program on the pallas path, via ``window_parts`` on the
    distributed path).  Rounding a window to the temporal depth would
    silently move hook firings — changing physics, not just speed."""
    steps = int(steps)
    if steps <= 0:
        return 1
    if fuse_steps is None:
        fuse = steps
    else:
        fuse = int(fuse_steps)
        if fuse < 1:
            raise ValueError("fuse_steps must be >= 1")
    fuse = min(fuse, steps)
    if max_fuse is not None:
        fuse = min(fuse, max_fuse)
    return fuse


def normalize_swap(kernel: _ir.StencilIR,
                   swap: Optional[Tuple[str, str]]) -> Optional[Tuple[str, str]]:
    """Validate and orient a swap pair as (written, other)."""
    if swap is None:
        return None
    a, b = swap
    params = set(kernel.grid_params)
    for g in (a, b):
        if g not in params:
            raise ValueError(f"swap grid '{g}' is not a kernel parameter")
    outs = set(kernel.output_grids())
    wr = [g for g in (a, b) if g in outs]
    if len(wr) != 1:
        raise ValueError(
            f"swap pair {swap} must contain exactly one output grid "
            f"(outputs: {sorted(outs)})")
    written = wr[0]
    other = b if written == a else a
    return (written, other)


def _rotate(arrays: Dict[str, jnp.ndarray], swap) -> Dict[str, jnp.ndarray]:
    out = dict(arrays)
    out[swap[0]], out[swap[1]] = out[swap[1]], out[swap[0]]
    return out


#: host-side accounting of the Pallas windows ``TimeloopEngine.run`` has
#: sent: ``paired_steps`` ran two to a loop iteration, ``single_steps`` ran
#: outside the loop (a window's odd step or odd k-step invocation)
WINDOW_STATS: Dict[str, int] = {
    "windows": 0, "paired_steps": 0, "single_steps": 0}


def reset_window_stats() -> None:
    """Zero ``WINDOW_STATS``.

    >>> reset_window_stats()
    >>> WINDOW_STATS["windows"]
    0
    """
    for k in WINDOW_STATS:
        WINDOW_STATS[k] = 0


def _donate_ok(differentiable: bool = False, arrays=None) -> bool:
    """Whether a fused-window program may donate the input buffers
    ``arrays`` (any pytree).

    Donation is gated by backend (CPU jit does not implement it — warns
    and copies) AND by differentiation: a donated window input is dead
    after the call, so it cannot be saved as a VJP residual or replayed
    from a checkpoint — the backward pass would read freed buffers.  Both
    an explicit ``differentiable=True`` engine flag and traced inputs (the
    window driven from inside another transform, as the adjoint's
    forward/replay passes or a user's ``jit`` do) disable donation."""
    if differentiable or any(isinstance(a, jax.core.Tracer)
                             for a in jax.tree.leaves(arrays)):
        return False
    return jax.default_backend() in ("tpu", "gpu")


class TimeloopEngine:
    """Backend-specific fused window programs for one (kernel, geometry).

    ``run(arrays, scalars, steps, fuse_steps, between)`` executes ``steps``
    applications of the kernel (+ buffer rotation when ``swap`` is set) in
    fusion windows of ``fuse_steps``, syncing with the host only at window
    boundaries.  Returns the final arrays dict (same naming convention as
    the per-step path: after each step the ``swap`` names trade buffers).
    """

    def __init__(self, kernel: _ir.StencilIR,
                 halos: Mapping[str, Tuple[int, ...]],
                 interior_shape: Tuple[int, ...],
                 backend,
                 swap: Optional[Tuple[str, str]] = None,
                 mesh=None,
                 batch: int = 0,
                 differentiable: bool = False):
        self.kernel = kernel
        self.halos = {g: tuple(h) for g, h in halos.items()}
        self.interior = tuple(interior_shape)
        self.backend = backend
        self.swap = normalize_swap(kernel, swap)
        self.mesh = mesh
        self.differentiable = bool(differentiable)
        self.batch = int(batch)
        if self.batch < 0:
            raise ValueError("batch must be >= 0 (0 = unbatched)")
        self._windows: Dict[Tuple[int, bool], Callable] = {}
        self._plan = self._plan1 = None
        self.time_block = 1
        if backend.kind == "pallas":
            from repro.kernels.stencil import codegen as _codegen
            # (plan construction time is charged to "codegen" by the caller)
            self._plan = _codegen.plan_pallas(
                kernel, self.halos, self.interior, backend, swap=self.swap)
            self.time_block = self._plan.time_block
            if self.time_block > 1:
                # single-step plan for the fusion-window remainder
                # (kw mod time_block) — shares the padded geometry so the
                # same layout buffers feed both kernels
                be1 = dataclasses.replace(backend, time_block=1,
                                          block=self._plan.B)
                self._plan1 = _codegen.plan_pallas(
                    kernel, self.halos, self.interior, be1, swap=self.swap)
            else:
                self._plan1 = self._plan
        elif backend.kind not in ("xla", "distributed"):
            raise ValueError(f"timeloop: unsupported backend {backend.kind}")
        if backend.kind == "distributed":
            if self.swap is None:
                raise ValueError("distributed timeloop requires swap=(a, b)")
            self.time_block = backend_time_block(backend)
        # fuse_steps no longer needs an overlapped-tiling clamp: the fused
        # window decomposes into exchange groups of the backend's temporal
        # depth, and only the *depth* (time_steps × time_block) must fit
        # k·h ≤ local extent — validated by HaloSpec at lowering time
        self.max_fuse: Optional[int] = None

    # -- helpers -----------------------------------------------------------
    def _window(self, kw: int, masked: bool = False,
                donate: bool = False) -> Callable:
        """Compiled fused program for a window of ``kw`` steps.

        ``masked=True`` (batched xla only) selects the serving variant with
        per-scenario spatial masks and step budgets; ``donate=True`` (see
        ``_donate_ok``) lets the program reuse its first argument's
        buffers."""
        fn = self._windows.get((kw, masked, donate))
        if fn is None:
            with _trace.span(_trace.COMPILE, "comp"):
                fn = self._build_window(kw, masked, donate)
            self._windows[(kw, masked, donate)] = fn
        return fn

    def _window_split(self, kw: int) -> Tuple[int, int]:
        """A Pallas window of ``kw`` steps as (k-step invocations, single
        steps): ⌊kw/k⌋ and the remainder, or ``kw`` single steps when the
        plan is not temporally blocked."""
        k = self.time_block
        return divmod(kw, k) if k > 1 else (0, kw)

    def _build_window(self, kw: int, masked: bool, donate: bool) -> Callable:
        donate = (0,) if donate else ()
        if masked:
            if self.backend.kind not in ("xla", "distributed") \
                    or not self.batch:
                raise ValueError(
                    "masked windows require a batched xla or distributed "
                    "timeloop")
            if self.backend.kind == "distributed":
                from . import distributed as _dist
                fn = _dist.lower_distributed_window(
                    self.kernel, self.interior, self.backend, self.mesh,
                    self.swap, kw, batch=self.batch,
                    differentiable=self.differentiable, masked=True,
                    donate=bool(donate))
            else:
                win = lowering.lower_jax_window_masked(
                    self.kernel, self.halos, self.interior, self.swap, kw)
                # mask and limit are per-scenario; start is window-global
                fn = jax.jit(jax.vmap(win, in_axes=(0, 0, 0, None, 0)),
                             donate_argnums=donate)
        elif self.backend.kind == "xla":
            win = lowering.lower_jax_window(
                self.kernel, self.halos, self.interior, None, self.swap, kw)
            if self.batch:
                win = jax.vmap(win, in_axes=(0, 0))
            fn = jax.jit(win, donate_argnums=donate)
        elif self.backend.kind == "pallas":
            plan, plan1, swap = self._plan, self._plan1, self.swap
            k = self.time_block
            m, r = self._window_split(kw)

            def win(padded, scalars):
                from jax import lax

                def invoke(carry, rename=True):
                    # double-buffered k-step invocation: outputs land in
                    # the spare buffers (the kernel must not write the
                    # buffers whose k·h windows other blocks still read),
                    # and the buffers just read become the next
                    # invocation's spares.  A k-step invocation leaves
                    # buffer↔name bindings untouched; k leapfrog rotations
                    # net to k mod 2, applied to the output AND spare
                    # names together so every output name keeps a
                    # destination carrying its own ring (padding + halo).
                    p, sp = carry
                    out = plan.step(p, scalars, spares=sp)
                    new_sp = {g: p[g] for g in plan.step_out_grids}
                    if rename and k % 2:
                        out = _rotate(out, swap)
                        new_sp = _rotate(new_sp, swap)
                    return out, new_sp

                def single(p, rename=True):
                    out = plan1.step(p, scalars)
                    return _rotate(out, swap) if swap and rename else out

                def run(n, one, carry):
                    # n applications of ``one``, two per loop iteration: a
                    # pair nets the leapfrog renaming to the identity, so
                    # every buffer leaves an iteration in the carry slot it
                    # came in with and XLA aliases the kernel's writes in
                    # place (a rename inside the loop body costs a copy of
                    # each renamed buffer per iteration).  An odd last one
                    # runs after the loop without renaming; the caller
                    # applies the window's parity to the names.
                    if n >= 2:
                        carry = lax.fori_loop(
                            0, n // 2, lambda _, c: one(one(c)), carry)
                    return one(carry, rename=False) if n % 2 else carry

                p = dict(padded)
                if m:
                    p, _ = run(m, invoke, (p, plan.make_spares(p)))
                # an odd k-step invocation left its k renames unapplied:
                # the remainder steps run on the names as the leapfrog
                # sees them, and hand back the slots they were given
                flip = m % 2 and k % 2
                if r:
                    p = run(r, single, _rotate(p, swap) if flip else p)
                    p = _rotate(p, swap) if flip else p
                return p
            if self.batch:
                # XLA's batching rule lifts the scenario axis into an extra
                # leading grid dimension of the same pallas_call — one
                # program still advances all B scenarios per invocation
                win = jax.vmap(win, in_axes=(0, 0))
            fn = jax.jit(win, donate_argnums=donate)
        else:  # distributed: the whole window is ONE shard_map'd program
            from . import distributed as _dist
            fn = _dist.lower_distributed_window(
                self.kernel, self.interior, self.backend, self.mesh,
                self.swap, kw, batch=self.batch,
                differentiable=self.differentiable, donate=bool(donate))
        return fn

    def window_for(self, steps: int, fuse_steps: Optional[int] = None) -> int:
        """The fusion-window size that actually runs for this request
        (see ``normalize_fuse``).  Idempotent, so callers may report the
        result and pass it back to ``run``."""
        return normalize_fuse(fuse_steps, steps, self.max_fuse)

    def window_arrays(self, kw: int, masked: bool = False) -> Callable:
        """PURE arrays-level callable for one fused window of ``kw`` steps:
        ``fn(arrays, scalars) -> arrays`` (masked:
        ``fn(arrays, scalars, mask, start, limits) -> arrays``), with the
        same carry convention as ``run`` — on the pallas path the padded
        layout round-trip and the host-side leapfrog name parity are folded
        in, so the returned function maps full (grid-halo'd) arrays to full
        arrays on every backend.

        This is the carry-capture surface of the adjoint engine
        (``core/adjoint.py``): the forward pass of the timeloop VJP runs
        these callables to snapshot checkpointed carries and the backward
        pass replays them bit-exactly from those checkpoints (the same
        replay primitive ``run_resilient`` relies on).  Unlike
        ``_run_window``, no window spans, host syncs, or modeled-traffic
        counters fire here — the function must be traceable inside
        another transform."""
        if masked or self.backend.kind in ("xla", "distributed"):
            return self._window(kw, masked=masked)
        plan, swap, batch = self._plan, self.swap, self.batch
        win = self._window(kw)

        def fn(arrays, scal):
            padded = (jax.vmap(plan.to_padded)(arrays) if batch
                      else plan.to_padded(arrays))
            padded = win(padded, scal)
            if swap and kw % 2:
                arrays = _rotate(arrays, swap)
                padded = _rotate(padded, swap)
            return (jax.vmap(plan.from_padded)(padded, arrays) if batch
                    else plan.from_padded(padded, arrays))
        return fn

    # -- driver ------------------------------------------------------------
    def run(self, arrays: Dict[str, jnp.ndarray],
            scalars: Mapping[str, jnp.ndarray],
            steps: int,
            fuse_steps: Optional[int] = None,
            between: Optional[Callable] = None,
            *,
            domain_mask: Optional[jnp.ndarray] = None,
            step_limits=None) -> Dict[str, jnp.ndarray]:
        """Advance the grids ``steps`` applications and return the final
        buffers.

        Args:
            arrays: grid name → halo-padded buffer (leading scenario axis
                of ``self.batch`` when batched).  Not mutated.
            scalars: scalar-param name → value; floats broadcast, ``(B,)``
                arrays stay per-scenario under batching.
            steps: total kernel applications (with the leapfrog swap
                rotation between them).
            fuse_steps: fusion-window size; ``None`` fuses the whole loop.
                Clamped via ``window_for``.
            between: optional host hook ``between(t, arrays) -> arrays``
                invoked at every window boundary.
            domain_mask: per-scenario boolean interior mask — ``False``
                cells hold their values (serving: frozen regions).
                Requires a batched xla or distributed engine.
            step_limits: per-scenario step counts; scenario ``b`` stops
                advancing after ``step_limits[b]`` applications.

        Returns a NEW dict of final buffers (same keys/shapes as
        ``arrays``); window programs are compiled once per (window, mask)
        signature and cached on the engine."""
        if self.backend.kind == "pallas" and not self.backend.interpret:
            from repro import kernels as _kernels
            _kernels.require_tpu()
        fuse = self.window_for(steps, fuse_steps)
        arrays = dict(arrays)
        if self.batch:
            for g, a in arrays.items():
                if a.ndim != len(self.interior) + 1 \
                        or a.shape[0] != self.batch:
                    raise ValueError(
                        f"batched timeloop: grid '{g}' must carry a leading "
                        f"scenario axis of {self.batch} (got {a.shape})")
            # python floats broadcast; (B,) arrays stay per-scenario
            scal = {n: jnp.broadcast_to(jnp.asarray(v, jnp.float32),
                                        (self.batch,))
                    for n, v in scalars.items()}
        else:
            scal = {n: jnp.asarray(v, jnp.float32)
                    for n, v in scalars.items()}
        masked = domain_mask is not None or step_limits is not None
        mask = limits = None
        if masked:
            if not self.batch \
                    or self.backend.kind not in ("xla", "distributed"):
                raise ValueError(
                    "domain_mask / step_limits require a batched xla or "
                    "distributed timeloop (the serving path)")
            if domain_mask is None:
                mask = jnp.ones((self.batch,) + self.interior, bool)
            else:
                mask = jnp.asarray(domain_mask, bool)
                if mask.shape != (self.batch,) + self.interior:
                    raise ValueError(
                        f"domain_mask must have shape "
                        f"{(self.batch,) + self.interior} (got {mask.shape})")
            if step_limits is None:
                limits = jnp.full((self.batch,), steps, jnp.int32)
            else:
                limits = jnp.asarray(step_limits, jnp.int32)
                if limits.shape != (self.batch,):
                    raise ValueError(
                        f"step_limits must have shape ({self.batch},) "
                        f"(got {limits.shape})")
        t = 0
        while t < steps:
            kw = min(fuse, steps - t)
            with _trace.span(_trace.WINDOW, "kernel"):
                if masked:
                    fn = self._window(
                        kw, masked=True,
                        donate=_donate_ok(self.differentiable, arrays))
                    with _trace.span(_trace.DISPATCH):
                        arrays = fn(arrays, scal, mask, jnp.int32(t), limits)
                else:
                    arrays = self._run_window(arrays, scal, kw)
                with _trace.span(_trace.SYNC):
                    jax.block_until_ready(arrays)
            t += kw
            if between is not None and t < steps:
                with _trace.span(_trace.HOOK):
                    arrays = between(t, arrays) or arrays
        return arrays

    def _run_window(self, arrays, scal, kw):
        donate = _donate_ok(self.differentiable, arrays)
        if self.backend.kind != "pallas":
            # xla, and distributed: one program advances the whole window
            # (exchange groups + remainder + every leapfrog rotation
            # happen in-program; on the distributed path the crop of the
            # sharded grids to their interiors and the write-back too)
            fn = self._window(kw, donate=donate)
            if self.backend.kind == "distributed":
                from . import distributed as _dist
                # the geometry of the program as built and cached, also
                # where a wrapper of ``_window`` hands back its own callable
                _dist.count_window(self._windows[(kw, False, donate)],
                                   jnp.dtype(arrays[self.swap[0]].dtype)
                                   .itemsize)
            with _trace.span(_trace.DISPATCH):
                return fn(arrays, scal)
        plan = self._plan
        with _trace.span(_trace.TO_PADDED, "layout"):
            if self.batch:
                # vmapped layout stage: still ONE pad per grid per window
                # (eager vmap pads all B scenarios in a single batched op)
                padded = jax.vmap(plan.to_padded)(arrays)
            else:
                padded = plan.to_padded(arrays)     # ONE pad/grid/window
            plan.count_window(kw, batch=max(1, self.batch))  # modeled HBM
            m, r = self._window_split(kw)
            WINDOW_STATS["windows"] += 1
            WINDOW_STATS["paired_steps"] += (m - m % 2) * self.time_block \
                + r - r % 2
            WINDOW_STATS["single_steps"] += m % 2 * self.time_block + r % 2
        fn = self._window(kw, donate=donate)
        with _trace.span(_trace.DISPATCH):
            padded = fn(padded, scal)
        with _trace.span(_trace.FROM_PADDED):
            # the device program keeps every buffer under the name it
            # came in with; apply the window's leapfrog parity to the
            # padded AND the full arrays so halos travel with their
            # buffers, then write the padded interiors back
            if self.swap and kw % 2:
                arrays = _rotate(arrays, self.swap)
                padded = _rotate(padded, self.swap)
            if self.batch:
                return jax.vmap(plan.from_padded)(padded, arrays)
            return plan.from_padded(padded, arrays)


def run_timeloop(kernel: _ir.StencilIR,
                 arrays: Dict[str, jnp.ndarray],
                 scalars: Mapping[str, jnp.ndarray],
                 steps: int,
                 *,
                 halos: Mapping[str, Tuple[int, ...]],
                 interior_shape: Tuple[int, ...],
                 backend,
                 swap: Optional[Tuple[str, str]] = None,
                 fuse_steps: Optional[int] = None,
                 between: Optional[Callable] = None,
                 mesh=None,
                 batch: int = 0) -> Dict[str, jnp.ndarray]:
    """One-shot convenience wrapper (builds a fresh engine)."""
    eng = TimeloopEngine(kernel, halos, interior_shape, backend,
                         swap=swap, mesh=mesh, batch=batch)
    return eng.run(dict(arrays), scalars, steps, fuse_steps, between)


def run_resilient(engine: TimeloopEngine,
                  arrays: Dict[str, jnp.ndarray],
                  scalars: Mapping[str, jnp.ndarray],
                  steps: int,
                  fuse_steps: Optional[int] = None,
                  between: Optional[Callable] = None,
                  *,
                  ckpt_dir: str,
                  ckpt_every: int = 1,
                  max_failures: int = 3,
                  injector=None,
                  watchdog=None,
                  loss: Optional[Callable] = None) -> Dict[str, jnp.ndarray]:
    """Fault-tolerant timeloop driver: checkpoint/restore of the leapfrog
    carry through ``train.checkpoint`` + ``train.fault_tolerance``.

    The simulation advances one fusion window per restartable step; every
    ``ckpt_every`` windows the full arrays dict (the leapfrog carry — both
    swap buffers plus coefficient grids) is snapshotted atomically to
    ``ckpt_dir``.  On a failure (or a fresh process pointed at the same
    directory) the run restores the latest snapshot and resumes from that
    window boundary.  Replay is deterministic — each window re-executes
    the identical compiled program on the identical carry — so a resumed
    run is bit-exact with an uninterrupted one (pinned in
    tests/test_resilience.py).  ``between`` fires at the same window
    boundaries as ``engine.run`` (a window is never re-split), so source
    injection timing survives restarts too.  Works for every backend the
    engine supports, including the distributed fused window on a mesh.

    ``loss`` (a pure scalar function of the final arrays) switches the
    driver to a fault-tolerant *gradient* run: the forward sweep AND the
    checkpointed backward sweep both advance one restartable unit at a
    time and resume from the latest snapshot after a failure — see
    ``adjoint.resilient_grad``.  Returns that function's result dict
    (``value`` / ``grad_arrays`` / ``grad_scalars``) instead of the final
    arrays; requires ``TimeloopEngine(..., differentiable=True)``.
    """
    from repro.train import fault_tolerance as _ft

    if loss is not None:
        from . import adjoint as _adj
        return _adj.resilient_grad(
            engine, arrays, scalars, steps, loss, fuse_steps=fuse_steps,
            between=between, ckpt_dir=ckpt_dir, ckpt_every=ckpt_every,
            max_failures=max_failures, injector=injector,
            watchdog=watchdog)

    fuse = engine.window_for(steps, fuse_steps)
    n_windows = -(-steps // fuse) if steps > 0 else 0
    init_arrays = {g: jnp.asarray(a) for g, a in arrays.items()}

    def init_fn():
        return dict(init_arrays)

    def step_fn(state, wi):
        t0 = wi * fuse
        kw = min(fuse, steps - t0)
        out = engine.run(dict(state), scalars, kw, kw)
        t1 = t0 + kw
        if between is not None and t1 < steps:
            out = between(t1, out) or out
        return out

    if n_windows == 0:
        return dict(init_arrays)
    return _ft.run_with_restarts(
        init_fn=init_fn, step_fn=step_fn, n_steps=n_windows,
        ckpt_dir=ckpt_dir, ckpt_every=ckpt_every,
        max_failures=max_failures, injector=injector, watchdog=watchdog)
