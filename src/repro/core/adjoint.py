"""Adjoint wave propagation: a checkpointed VJP for the fused timeloop.

Inversion workloads (FWI / RTM — what seismic users of high-order stencils
actually run, per Devito) need gradients of a ``steps``-long leapfrog
recursion with respect to the initial grids, the coefficient grids
(velocity model), and the per-scenario scalars.  Naive reverse-mode
through the fused window programs stores every step's carry as a residual
— O(T) wavefields, which is exactly the memory wall Griewank-style
checkpointing exists for.  This module is that scheme over the engine's
own fusion windows:

  forward   — ``jax.custom_vjp`` over the window sequence of
              ``TimeloopEngine`` (the engine's OWN compiled programs, via
              ``engine.window_arrays``): run W windows, snapshotting the
              leapfrog carry (the same full-arrays snapshot structure
              ``train/checkpoint.py`` persists, kept in memory) at every
              ``stride``-th window start.  Checkpoint count ≈ ⌈√T⌉.
  backward  — per checkpoint segment, newest first: REPLAY the segment's
              windows from its checkpoint with the engine's programs
              (bit-exact with the forward run — the same replay primitive
              ``run_resilient``'s resume proves), then walk the segment's
              windows in reverse pulling each cotangent through one
              window's VJP at a time.

The per-window VJP differentiates the always-correct XLA reference
lowering (``lowering.lower_jax_window`` with ``remat=True`` — the oracle
every Pallas kernel is validated against) at the replayed carries.  On
the xla backend that IS the forward program; on the pallas backends the
forward/replay stays on the engine's compiled kernels (``pallas_call``
defines no VJP — and must not be asked for one) while the cotangent
chain runs through the numerically-matching reference window.  On the
distributed backend each window program carries its own VJP
(``distributed.lower_distributed_window(differentiable=True)``): the
cotangent pull is a second shard_map program whose halo exchanges are
the reverse ``ppermute``s of the forward ones (``HaloSpec.transpose``
geometry) with scalar cotangents ``psum``-reduced over the mesh, so the
whole backward pass stays sharded end-to-end.  Masked
(serving) windows differentiate through ``lower_jax_window_masked``,
whose ``where``-based freeze makes the adjoint freeze masked cells and
budget-exhausted scenarios too.  Batched engines differentiate
per-scenario: the reference window is vmapped over the leading scenario
axis exactly like the forward program, so ``(B,)`` scalars and
``(B, ...)`` grids receive per-scenario cotangents.

Peak backward memory: ⌈W/stride⌉ checkpoints + one segment of replayed
carries (≤ stride) + one window of per-step carries (≤ fuse) — with the
default schedule (fuse ≈ ⌈√T⌉, stride thinning the checkpoints back to
≈ ⌈√T⌉ when the caller forces a smaller hook cadence) every term is
O(√T).

The compiled program names its three kinds of work with
``jax.named_scope`` (``core/trace.py``): ``adjoint_forward`` for the
forward sweep, ``adjoint_replay`` for the backward pass's checkpoint
replays and ``adjoint_vjp`` for the per-window pullbacks; each device
operation's ``op_name`` carries its scope.  ``CHECKPOINT_STATS`` counts
the most recently traced pass.

``between`` hooks are supported when they are PURE traceable functions
``between(t, arrays) -> arrays`` (e.g. jnp source injection); they fire
at the same window boundaries as ``TimeloopEngine.run`` and are
differentiated as part of the window chain.  Donation is disabled on the
whole path (``timeloop._donate_ok``): a donated window input is dead
after the call and cannot be checkpointed or replayed.

User entry point: ``st.differentiable_timeloop`` in ``core/dsl.py``.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from . import lowering
from . import trace as _trace

__all__ = ["ceil_sqrt", "window_schedule", "checkpoint_stride",
           "differentiable_run", "resilient_grad", "CHECKPOINT_STATS",
           "reset_stats"]

#: trace-time accounting of the most recently traced pass — the forward
#: sets ``forward_windows`` and ``checkpoints`` (the carries saved as VJP
#: residuals, the O(√T) bound tests pin); the backward zeroes, then counts,
#: ``replayed_windows`` and ``vjp_windows``, its recompute work
CHECKPOINT_STATS: Dict[str, int] = {
    "forward_windows": 0, "checkpoints": 0, "replayed_windows": 0,
    "vjp_windows": 0}


def reset_stats() -> None:
    """Zero ``CHECKPOINT_STATS`` (call before tracing a fresh adjoint pass
    so its checkpoint/replay counters start from zero).

    >>> reset_stats()
    >>> CHECKPOINT_STATS["checkpoints"]
    0
    """
    for k in CHECKPOINT_STATS:
        CHECKPOINT_STATS[k] = 0


def ceil_sqrt(n: int) -> int:
    """⌈√n⌉ for n ≥ 0 (exact, no float round-trip)."""
    if n <= 0:
        return 0
    r = math.isqrt(n - 1)
    return r + 1


def window_schedule(steps: int, fuse: int) -> Tuple[Tuple[int, ...],
                                                    Tuple[int, ...]]:
    """(window sizes, window start steps) of a ``steps``-long run driven in
    fusion windows of ``fuse`` — the same decomposition ``run`` executes."""
    sizes: List[int] = []
    starts: List[int] = []
    t = 0
    while t < steps:
        kw = min(fuse, steps - t)
        sizes.append(kw)
        starts.append(t)
        t += kw
    return tuple(sizes), tuple(starts)


def checkpoint_stride(n_windows: int, steps: int) -> int:
    """Checkpoint thinning: snapshot the carry every ``stride``-th window
    start so the stored-checkpoint count stays ≈ ⌈√T⌉ even when the
    window cadence is much finer (fuse_steps=1 → T windows).  With the
    default fuse ≈ ⌈√T⌉ this is 1 (every window start is a checkpoint)."""
    target = max(1, ceil_sqrt(steps))
    return max(1, -(-n_windows // target))


def _zeros_like_tree(tree):
    return jax.tree.map(jnp.zeros_like, tree)


def _add_trees(a, b):
    return jax.tree.map(jnp.add, a, b)


class _AdjointPlan:
    """Shared prelude of the checkpointed-adjoint drivers: the window
    schedule, the checkpoint thinning, the masked-serving closures, and
    the per-window primal/adjoint callables — everything
    ``differentiable_run`` (in-memory checkpoints) and ``resilient_grad``
    (on-disk checkpoints, restartable) have in common."""

    def __init__(self, engine, steps, fuse_steps, between,
                 domain_mask, step_limits, checkpoint_stride_windows):
        if not engine.differentiable:
            raise ValueError(
                "the checkpointed adjoint requires TimeloopEngine(..., "
                "differentiable=True): an engine that may donate window "
                "inputs cannot be checkpointed or replayed")
        self.engine = engine
        self.between = between
        self.steps = steps = int(steps)
        self.fuse = engine.window_for(
            steps, ceil_sqrt(steps) if fuse_steps is None else fuse_steps)
        self.sizes, self.starts = window_schedule(steps, self.fuse)
        self.W = len(self.sizes)
        self.stride = (int(checkpoint_stride_windows)
                       if checkpoint_stride_windows
                       else checkpoint_stride(self.W, steps))
        self.n_ckpts = -(-self.W // self.stride) if self.W else 0

        self.masked = domain_mask is not None or step_limits is not None
        self.mask = self.limits = None
        if self.masked:
            if not engine.batch \
                    or engine.backend.kind not in ("xla", "distributed"):
                raise ValueError(
                    "domain_mask / step_limits require a batched xla or "
                    "distributed timeloop (the serving path)")
            if domain_mask is None:
                self.mask = jnp.ones((engine.batch,) + engine.interior,
                                     bool)
            else:
                self.mask = jnp.asarray(domain_mask, bool)
            if step_limits is None:
                self.limits = jnp.full((engine.batch,), steps, jnp.int32)
            else:
                self.limits = jnp.asarray(step_limits, jnp.int32)

        self._primal_cache: Dict[int, Callable] = {}
        self._adjoint_cache: Dict[int, Callable] = {}

    # primal/replay: the engine's own compiled programs (bit-exact with a
    # plain engine.run of the same windows)
    def primal_window(self, kw: int) -> Callable:
        fn = self._primal_cache.get(kw)
        if fn is None:
            fn = self.engine.window_arrays(kw, masked=self.masked)
            self._primal_cache[kw] = fn
        return fn

    # adjoint: the XLA reference lowering (remat'd: one carry per step),
    # vmapped over the scenario axis exactly like the engine's programs.
    # The distributed window program carries its own VJP (the shard_map
    # backward program of ``distributed.lower_distributed_window``), so
    # there the adjoint window IS the primal window.
    def adjoint_window(self, kw: int) -> Callable:
        engine = self.engine
        if engine.backend.kind == "distributed":
            return self.primal_window(kw)
        fn = self._adjoint_cache.get(kw)
        if fn is None:
            if self.masked:
                win = lowering.lower_jax_window_masked(
                    engine.kernel, engine.halos, engine.interior,
                    engine.swap, kw, remat=True)
                fn = jax.vmap(win, in_axes=(0, 0, 0, None, 0))
            else:
                win = lowering.lower_jax_window(
                    engine.kernel, engine.halos, engine.interior, None,
                    engine.swap, kw, remat=True)
                fn = jax.vmap(win, in_axes=(0, 0)) if engine.batch else win
            self._adjoint_cache[kw] = fn
        return fn

    def chain(self, i: int, window_fn_for: Callable) -> Callable:
        """Window i as a function of (carry, scalars): the fused window
        program plus the ``between`` hook at its trailing boundary — the
        exact per-window step ``engine.run`` executes."""
        kw, t0 = self.sizes[i], self.starts[i]
        t1 = t0 + kw
        win = window_fn_for(kw)
        between, steps = self.between, self.steps
        masked, mask, limits = self.masked, self.mask, self.limits

        def fn(arrays, scalars):
            if masked:
                out = win(arrays, scalars, mask, jnp.int32(t0), limits)
            else:
                out = win(arrays, scalars)
            if between is not None and t1 < steps:
                out = between(t1, dict(out))
            return dict(out)
        return fn

    def normalize_scalars(self, scalars):
        scal = {}
        for n, v in ({} if scalars is None else scalars).items():
            a = jnp.asarray(v)
            if not jnp.issubdtype(a.dtype, jnp.floating):
                a = a.astype(jnp.float32)
            if self.engine.batch:
                a = jnp.broadcast_to(a, (self.engine.batch,))
            scal[n] = a
        return scal

    def forward_window(self, i: int, carry, scalars):
        """Window i of the forward sweep on the engine's programs."""
        with jax.named_scope(_trace.ADJOINT_FORWARD):
            return self.chain(i, self.primal_window)(carry, scalars)

    def replay_window(self, i: int, carry, scalars):
        """Window i recomputed from a checkpoint in the backward pass:
        the forward's program, bit-exact with it."""
        with jax.named_scope(_trace.ADJOINT_REPLAY):
            return self.chain(i, self.primal_window)(carry, scalars)

    def vjp_window(self, i: int, carry, scalars, cot):
        """Pull ``cot`` backward through window i linearized at ``carry``;
        returns (carry cotangent, scalar cotangent contribution)."""
        with jax.named_scope(_trace.ADJOINT_VJP):
            _, vjp_fn = jax.vjp(self.chain(i, self.adjoint_window),
                                carry, scalars)
            d_carry, d_scal = vjp_fn(dict(cot))
        return dict(d_carry), d_scal


def differentiable_run(engine,
                       steps: int,
                       fuse_steps: Optional[int] = None,
                       between: Optional[Callable] = None,
                       *,
                       domain_mask=None,
                       step_limits=None,
                       checkpoint_stride_windows: Optional[int] = None
                       ) -> Callable:
    """Differentiable counterpart of ``TimeloopEngine.run``.

    Returns a PURE function ``fn(arrays, scalars) -> arrays`` computing
    the same window sequence ``engine.run(arrays, scalars, steps,
    fuse_steps, between)`` executes, but reverse-mode differentiable with
    the O(√T) checkpointed adjoint described in the module docstring.
    Gradients flow to every grid in ``arrays`` (initial wavefields AND
    coefficient grids riding in the carry) and to every float scalar.

    ``fuse_steps=None`` picks the adjoint default ⌈√steps⌉ (the memory-
    optimal single-level schedule) instead of ``run``'s whole-loop
    default; pass it explicitly to pin a ``between``-hook cadence.
    ``domain_mask`` / ``step_limits`` select the masked serving windows
    (batched xla engines only), closed over as non-differentiable
    constants.  ``checkpoint_stride_windows`` overrides the checkpoint
    thinning (testing / memory tuning).

    The engine must be built with ``differentiable=True`` so none of its
    window programs donate their inputs (donated buffers cannot be saved
    as VJP residuals or replayed — ``timeloop._donate_ok``).

    Distributed engines are fully supported: the replay runs the same
    shard_mapped window programs, and the cotangent pull goes through
    each window's own backward shard_map program (reverse ``ppermute``
    halo exchanges — ``fn.spec_T`` geometry) instead of the single-device
    reference window.  Gradients on the swap grids live on the interiors
    (the distributed carry convention keeps grid-halo cells fixed at
    zero, so no cotangent lands on them).

    Example (single device; add ``mesh=`` via ``st.differentiable_timeloop``
    for the sharded version)::

        eng = TimeloopEngine(k.ir, halos, shape, st.xla(), swap=("v", "u"),
                             differentiable=True)
        fn = differentiable_run(eng, steps=100)
        g = jax.grad(lambda a, s: jnp.sum(fn(a, s)["v"] ** 2))(arrays, scal)
    """
    steps = int(steps)
    if steps <= 0:
        def identity(arrays, scalars):
            return dict(arrays)
        identity.schedule = {"windows": (), "starts": (), "stride": 1,
                             "checkpoints": 0}
        return identity

    plan = _AdjointPlan(engine, steps, fuse_steps, between,
                        domain_mask, step_limits, checkpoint_stride_windows)
    W, stride, n_ckpts = plan.W, plan.stride, plan.n_ckpts

    # -- custom VJP --------------------------------------------------------
    @jax.custom_vjp
    def core(arrays, scalars):
        carry = dict(arrays)
        for i in range(W):
            carry = plan.forward_window(i, carry, scalars)
        return carry

    def core_fwd(arrays, scalars):
        ckpts = []
        carry = dict(arrays)
        for i in range(W):
            if i % stride == 0:
                ckpts.append(carry)
            carry = plan.forward_window(i, carry, scalars)
        CHECKPOINT_STATS["forward_windows"] = W
        CHECKPOINT_STATS["checkpoints"] = len(ckpts)
        return carry, (tuple(ckpts), scalars)

    def core_bwd(res, cot):
        ckpts, scalars = res
        g_scal = _zeros_like_tree(scalars)
        cot = dict(cot)
        CHECKPOINT_STATS["replayed_windows"] = 0
        CHECKPOINT_STATS["vjp_windows"] = 0
        for seg in reversed(range(n_ckpts)):
            first = seg * stride
            last = min(first + stride, W)
            # replay the segment's carries from its checkpoint with the
            # engine's own programs — bit-exact with the forward pass
            carries = [ckpts[seg]]
            for i in range(first, last - 1):
                carries.append(plan.replay_window(i, carries[-1], scalars))
                CHECKPOINT_STATS["replayed_windows"] += 1
            # pull the cotangent backward one window at a time through the
            # reference adjoint, linearized at the replayed carry
            for i in reversed(range(first, last)):
                cot, gs = plan.vjp_window(i, carries[i - first], scalars,
                                          cot)
                g_scal = _add_trees(g_scal, gs)
                CHECKPOINT_STATS["vjp_windows"] += 1
        return cot, g_scal

    core.defvjp(core_fwd, core_bwd)

    def fn(arrays: Dict[str, jnp.ndarray], scalars=None):
        arrays = {g: jnp.asarray(a) for g, a in arrays.items()}
        return core(arrays, plan.normalize_scalars(scalars))

    fn.schedule = {"windows": plan.sizes, "starts": plan.starts,
                   "stride": stride, "checkpoints": n_ckpts,
                   "fuse": plan.fuse}
    return fn


def resilient_grad(engine,
                   arrays: Dict[str, jnp.ndarray],
                   scalars,
                   steps: int,
                   loss: Callable,
                   *,
                   fuse_steps: Optional[int] = None,
                   between: Optional[Callable] = None,
                   domain_mask=None,
                   step_limits=None,
                   checkpoint_stride_windows: Optional[int] = None,
                   ckpt_dir: str,
                   ckpt_every: int = 1,
                   max_failures: int = 3,
                   injector=None,
                   watchdog=None) -> Dict[str, object]:
    """Fault-tolerant checkpointed gradient: ``value_and_grad`` of
    ``loss(final arrays)`` through the same √T-checkpointed window
    schedule as ``differentiable_run``, driven one restartable unit at a
    time through ``train.fault_tolerance.run_with_restarts``.

    The restartable units are: one fusion window per forward step (the
    √T checkpoints ride in the persisted state), one step seeding the
    cotangent with ``jax.value_and_grad(loss)``, then one checkpoint
    *segment* per backward step (replay ≤ ``stride`` windows, pull the
    cotangent through each in reverse).  A crash anywhere — including
    mid-backward — resumes from the latest on-disk snapshot and yields a
    bit-exact result (deterministic replay, same compiled programs).
    Works on every engine ``differentiable_run`` accepts, including
    distributed engines on a mesh.

    Returns ``{"value", "grad_arrays", "grad_scalars"}``.
    """
    from repro.train import fault_tolerance as _ft

    steps = int(steps)
    init_arrays = {g: jnp.asarray(a) for g, a in arrays.items()}
    if steps <= 0:
        value, cot = jax.value_and_grad(loss)(init_arrays)
        return {"value": value, "grad_arrays": cot,
                "grad_scalars": _zeros_like_tree(dict(scalars or {}))}

    plan = _AdjointPlan(engine, steps, fuse_steps, between,
                        domain_mask, step_limits, checkpoint_stride_windows)
    W, stride, n_ckpts = plan.W, plan.stride, plan.n_ckpts
    scal = plan.normalize_scalars(scalars)

    # constant-treedef restartable state: every phase of the run writes
    # the same pytree structure, so any snapshot restores into any step
    def init_fn():
        zero = _zeros_like_tree(init_arrays)
        return {"carry": dict(init_arrays),
                "ckpts": tuple(dict(zero) for _ in range(n_ckpts)),
                "cot": dict(zero),
                "g_scal": _zeros_like_tree(scal),
                "value": jnp.zeros((), jnp.result_type(float))}

    def step_fn(state, wi):
        state = dict(state)
        if wi < W:                                   # forward window wi
            if wi % stride == 0:
                ckpts = list(state["ckpts"])
                ckpts[wi // stride] = dict(state["carry"])
                state["ckpts"] = tuple(ckpts)
            state["carry"] = plan.forward_window(wi, state["carry"], scal)
        elif wi == W:                                # seed the cotangent
            value, cot = jax.value_and_grad(loss)(state["carry"])
            state["value"] = jnp.asarray(value, state["value"].dtype)
            state["cot"] = dict(cot)
        else:                                        # backward segment
            seg = n_ckpts - 1 - (wi - W - 1)
            first = seg * stride
            last = min(first + stride, W)
            carries = [dict(state["ckpts"][seg])]
            for i in range(first, last - 1):
                carries.append(plan.replay_window(i, carries[-1], scal))
            cot, g_scal = state["cot"], state["g_scal"]
            for i in reversed(range(first, last)):
                cot, gs = plan.vjp_window(i, carries[i - first], scal, cot)
                g_scal = _add_trees(g_scal, gs)
            state["cot"], state["g_scal"] = cot, g_scal
        return state

    final = _ft.run_with_restarts(
        init_fn=init_fn, step_fn=step_fn, n_steps=W + 1 + n_ckpts,
        ckpt_dir=ckpt_dir, ckpt_every=ckpt_every,
        max_failures=max_failures, injector=injector, watchdog=watchdog)
    return {"value": final["value"], "grad_arrays": final["cot"],
            "grad_scalars": final["g_scal"]}
