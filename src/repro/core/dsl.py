"""StencilPy user-facing DSL (paper Table 1 constructs), hosted in Python.

Usage mirrors paper Listing 1::

    from repro.core import dsl as st

    @st.kernel
    def star2d1r(u: st.grid, v: st.grid):
        v.at(0, 0).set(0.5 * u.at(0, 0)
                       + 0.125 * (u.at(-1, 0) + u.at(1, 0))
                       + 0.125 * (u.at(0, -1) + u.at(0, 1)))

    @st.target
    def run(u: st.grid, v: st.grid, iters: st.i32):
        for _t in range(iters):
            st.map(e=u.shape)(star2d1r)(u, v)
            (v, u) = (u, v)

    u = st.grid(dtype=st.f32, shape=(512, 512), order=1)
    v = st.grid(dtype=st.f32, shape=(512, 512), order=1)
    st.launch(backend=st.pallas(template="gmem"))(run)(u, v, 10)

Constructs: ``kernel``, ``target``, ``map``, ``launch``, ``at``/``at.set``
(inside kernels), ``grid``.  Backends: ``xla`` (pure-jnp/XLA), ``pallas``
(TPU Pallas codegen, compiled by Mosaic on a TPU and interpreted where
there is none), ``distributed`` (shard_map domain decomposition), plus a
``cuda`` compatibility alias so paper Listing 1 runs verbatim.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import kernels as _kernels

from . import analysis as _analysis
from . import frontend as _frontend
from . import ir as _ir
from . import lowering as _lowering
from . import trace as _trace

__all__ = [
    "grid", "kernel", "target", "map", "timeloop", "launch",
    "differentiable_timeloop",
    "f32", "f64", "bf16", "i32", "i64",
    "xla", "pallas", "tpu", "cuda", "distributed",
    "Kernel", "LaunchResult", "TimeloopResult",
]


# --------------------------------------------------------------------------
# dtype markers
# --------------------------------------------------------------------------
class _DType:
    def __init__(self, name: str, np_dtype):
        self.name = name
        self.dtype = np_dtype

    def __repr__(self):
        return f"st.{self.name}"


f32 = _DType("f32", jnp.float32)
f64 = _DType("f64", jnp.float64)
bf16 = _DType("bf16", jnp.bfloat16)
i32 = _DType("i32", jnp.int32)
i64 = _DType("i64", jnp.int64)


# --------------------------------------------------------------------------
# grid
# --------------------------------------------------------------------------
class grid:
    """A stencil data grid: ``shape`` interior points + ``order`` halo cells
    on each side of every axis (paper §2.1).  Also used as the kernel
    parameter type annotation (``u: st.grid``).

    ``batch=B`` adds a leading *scenario* axis: the grid holds B independent
    copies of the (halo-padded) domain, advanced together by
    ``st.timeloop(..., batch=B)`` in one compiled program.  The scenario
    axis carries no halo."""

    def __init__(self, dtype: _DType = f32, shape: Tuple[int, ...] = (),
                 order: int = 0, data: Optional[jnp.ndarray] = None,
                 batch: Optional[int] = None):
        self.shape = tuple(shape)
        self.order = int(order)
        self.batch = int(batch) if batch else None
        self.dtype = dtype.dtype if isinstance(dtype, _DType) else dtype
        full = tuple(s + 2 * self.order for s in self.shape)
        if self.batch:
            full = (self.batch,) + full
        if data is not None:
            assert tuple(data.shape) == full, (data.shape, full)
            self.data = jnp.asarray(data, self.dtype)
        else:
            self.data = jnp.zeros(full, self.dtype)

    # -- views -------------------------------------------------------------
    @property
    def halo(self) -> Tuple[int, ...]:
        """Per-axis halo width, ``(order,) * ndim``.

        Returns the number of ghost cells padded on EACH side of every
        spatial axis.  The scenario batch axis (if any) carries no halo.

        >>> grid(dtype=f32, shape=(8, 8), order=2).halo
        (2, 2)
        """
        return (self.order,) * len(self.shape)

    @property
    def _interior_idx(self):
        o = self.order
        idx = tuple(slice(o, o + s) for s in self.shape)
        return ((slice(None),) + idx) if self.batch else idx

    @property
    def interior(self) -> jnp.ndarray:
        """View of the halo-free interior, shape ``([batch,] *shape)``.

        Reading slices the ``order``-deep halo ring off ``data``; assigning
        writes a value of the same interior shape back (cast to the grid
        dtype), leaving the halo cells untouched.

        >>> g = grid(dtype=f32, shape=(4, 4), order=1)
        >>> g.interior = 2.0 * jnp.ones((4, 4))
        >>> (g.data.shape, float(g.interior[0, 0]), float(g.data[0, 0]))
        ((6, 6), 2.0, 0.0)
        """
        return self.data[self._interior_idx]

    @interior.setter
    def interior(self, value) -> None:
        self.data = self.data.at[self._interior_idx].set(
            jnp.asarray(value, self.dtype))

    # -- init helpers --------------------------------------------------------
    def randomize(self, seed: int = 0, scale: float = 1.0) -> "grid":
        """Fill the interior with ``scale`` × standard-normal noise.

        Args:
            seed: ``numpy.random.default_rng`` seed, so initial conditions
                are reproducible across runs and backends.
            scale: multiplier applied to the draws.

        Returns this grid (chainable):

        >>> g = grid(dtype=f32, shape=(8, 8), order=1).randomize(7)
        >>> bool(jnp.any(g.interior != 0.0))
        True
        """
        rng = np.random.default_rng(seed)
        shape = ((self.batch,) + self.shape) if self.batch else self.shape
        vals = scale * rng.standard_normal(shape)
        self.interior = np.asarray(vals, dtype=np.dtype(self.dtype))
        return self

    def copy(self) -> "grid":
        """Shallow copy: new ``grid`` sharing this one's (immutable) buffer.

        Backends never mutate ``data`` in place (jax arrays are immutable;
        runs assign fresh buffers), so a copy taken before a launch
        preserves the initial state for a reference run:

        >>> a = grid(dtype=f32, shape=(4, 4), order=1).randomize(0)
        >>> b = a.copy()
        >>> a.data = a.data + 1.0   # leaves b.data untouched
        >>> float(jnp.max(jnp.abs(a.data - b.data)))
        1.0
        """
        g = grid.__new__(grid)
        g.shape, g.order, g.dtype = self.shape, self.order, self.dtype
        g.batch = self.batch
        g.data = self.data
        return g

    def __repr__(self):
        b = f", batch={self.batch}" if self.batch else ""
        return (f"st.grid(shape={self.shape}, order={self.order}, "
                f"dtype={self.dtype}{b})")


# --------------------------------------------------------------------------
# kernel
# --------------------------------------------------------------------------
class Kernel:
    """A parsed stencil kernel: the object ``@st.kernel`` returns.

    Holds the kernel's ``ir`` (:class:`repro.core.ir.StencilIR` — grid/
    scalar params and the update expression), its static analysis in
    ``info`` (dimensionality, stencil ``shape``/``order``, flops per
    point, bytes moved), and a per-(backend, shapes) compilation cache.
    Pass it to ``st.map``/``st.timeloop``/``st.differentiable_timeloop``;
    it is not called directly.
    """

    def __init__(self, fn: Callable):
        self.fn = fn
        self.name = fn.__name__
        t0 = time.perf_counter()
        self.ir: _ir.StencilIR = _frontend.parse_kernel(fn)
        self.frontend_time = time.perf_counter() - t0
        _analysis.check_read_after_write(self.ir)
        self.info: _analysis.StencilInfo = _analysis.analyze(self.ir)
        self._cache: Dict = {}

    def __repr__(self):
        i = self.info
        return (f"<st.kernel {self.name}: {i.ndim}D {i.shape} order={i.order} "
                f"flops/pt={i.flops_per_point}>")


def kernel(fn: Callable) -> Kernel:
    """Decorator parsing a Python stencil function into a :class:`Kernel`.

    The body must be pure ``v.at(dx, dy, ...).set(expr)`` assignments over
    grid parameters (annotated ``st.grid``) and scalar parameters
    (``st.f32``/``st.i32``…), with relative offsets bounded by each grid's
    ``order`` (paper Table 1).  Parsing happens once at decoration time
    via the AST — the function itself never executes::

        @st.kernel
        def star2d1r(u: st.grid, v: st.grid):
            v.at(0, 0).set(0.5 * u.at(0, 0)
                           + 0.125 * (u.at(-1, 0) + u.at(1, 0))
                           + 0.125 * (u.at(0, -1) + u.at(0, 1)))

    Returns the :class:`Kernel` (so ``star2d1r.info.order == 1``).  Note:
    the source must be on disk (``inspect.getsource``) — kernels cannot be
    defined inside ``python -c`` strings or a REPL without a file.
    """
    return Kernel(fn)


def target(fn: Callable) -> Callable:
    """Decorator marking a driver function for ``st.launch``.

    A target is plain Python orchestrating ``st.map``/``st.timeloop``
    calls over grids (paper Listing 1's ``run``).  The decorator only tags
    the function — ``st.launch(backend=...)(run)(u, v, 10)`` supplies the
    backend/mesh context its stencil calls pick up.  Returns ``fn``.
    """
    fn._is_stencil_target = True
    return fn


# --------------------------------------------------------------------------
# backends
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Backend:
    """Base class for backend selectors (``st.xla``/``st.pallas``/
    ``st.distributed``).

    A backend is an immutable value object: it names a lowering path and
    carries its knobs, and is hashed into compilation-cache keys — it
    holds no runtime state.  Instantiate a concrete subclass and pass it
    to ``st.launch(backend=...)`` or ``st.differentiable_timeloop(...,
    backend=...)``.
    """
    kind: str = "xla"

    def cache_key(self):
        """Hashable tuple identifying this configuration for compilation
        caches (every knob participates; subclasses with non-astuple-able
        fields override)."""
        return dataclasses.astuple(self)


@dataclasses.dataclass(frozen=True)
class xla(Backend):
    """Pure-``jax.numpy`` lowering compiled by XLA (the portable baseline).

    No knobs: stencils become shifted-slice arithmetic on the full grid
    buffer and XLA fuses the window.  Works on any jax platform and is
    the reference the other backends are validated against.
    """
    kind: str = "xla"


@dataclasses.dataclass(frozen=True)
class pallas(Backend):
    """TPU Pallas backend.  ``template`` per paper Table 2; ``block`` is the
    BlockSpec tile (the paper's Dx/Dy/Dz knobs); ``mem_type`` selects the
    streaming-dim storage for 2.5D templates ('registers' → unrolled VREG
    window, 'vmem' → VMEM scratch window, None → shape-directed default:
    star→registers, box→vmem, mirroring the paper's auto choice);
    ``interpret`` runs the kernel body in the Pallas interpreter.  It
    follows the platform when left ``None`` — compiled Mosaic on a TPU,
    the interpreter where JAX finds no TPU — and the resolved value is
    stored, so it keys every cache.  An explicit ``interpret=False``
    without a TPU raises when the kernel is run.

    ``time_block=k`` enables in-kernel temporal blocking on the fused
    time-loop path (``st.timeloop``): each kernel invocation fetches a
    k·h-deep halo window per grid, advances k leapfrog steps in VMEM, and
    writes only the final interiors back (double-buffered) — per k steps
    each advanced grid costs one expanded-window read, one destination
    fetch and one block write instead of a read+write per step, an
    asymptotically ~k× HBM-traffic cut (small depths can lose to the halo
    growth; the autotuner measures).  Requires k·h ≤ the block extent on
    every axis (the default block geometry grows to fit) and a ``swap``
    pair on the timeloop."""
    kind: str = "pallas"
    template: str = "gmem"
    block: Optional[Tuple[int, ...]] = None
    mem_type: Optional[str] = None
    prefetch: bool = False
    interpret: Optional[bool] = None
    time_block: int = 1

    def __post_init__(self):
        if self.interpret is None:
            object.__setattr__(self, "interpret", not _kernels.on_tpu())
        if self.template not in ("gmem", "smem", "f4", "shift", "unroll", "semi"):
            raise ValueError(f"unknown template {self.template!r}")
        if int(self.time_block) < 1:
            raise ValueError("time_block must be >= 1")


def tpu(**kw) -> pallas:
    """Alias for :class:`pallas` (paper naming: the TPU backend).

    ``st.tpu(template="smem", block=(256, 256))`` ≡
    ``st.pallas(template="smem", block=(256, 256))``.
    """
    return pallas(**kw)


def cuda(computeCapability: str = "", threadsPerBlock: Optional[Tuple[int, ...]] = None,
         template: str = "gmem", **kw) -> pallas:
    """Paper-compat alias: Listing 1's ``st.cuda(...)`` maps onto the Pallas
    backend (threadsPerBlock → BlockSpec block)."""
    del computeCapability
    return pallas(template=template, block=threadsPerBlock, **kw)


@dataclasses.dataclass(frozen=True)
class distributed(Backend):
    """shard_map domain decomposition across a device mesh.

    ``grid_axes`` maps stencil-grid axes to mesh axis names, e.g.
    ('data', 'model') splits axes 0,1 of the domain.  ``inner`` is the
    per-shard backend.  Halo exchange via ppermute; see core/distributed.py.

    ``time_steps`` > 1 enables overlapped tiling (paper §3 / time skewing
    at pod level): ONE k·h-wide halo exchange covers k kernel applications,
    trading a thin shell of redundant compute for 1/k the exchange rounds.
    Requires ``swap`` — the (older, newer) grid pair rotated between
    applications (the leapfrog buffer swap).

    Under the fused engine (``st.timeloop``) the whole fusion window runs
    as ONE shard_map'd program and ``time_steps`` (× a pallas ``inner``'s
    ``time_block``) sets only the exchange *depth* within it: a window of
    ``fuse_steps`` decomposes into ⌊w/k⌋ depth-k exchange groups plus a
    remainder group inside the same ``lax.fori_loop``.  The depth must
    satisfy k·h ≤ local shard extent; the window itself is unbounded.
    ``overlap`` there selects the deep-interior pre-pass that hides the
    ppermute latency behind compute (exchange geometry: core/halo.py).
    """
    kind: str = "distributed"
    grid_axes: Tuple[Optional[str], ...] = ("data",)
    inner: Backend = dataclasses.field(default_factory=xla)
    overlap: bool = True
    time_steps: int = 1
    swap: Optional[Tuple[str, str]] = None

    def cache_key(self):
        """Cache key flattening the nested ``inner`` backend (plain
        ``astuple`` would recurse into the dataclass and lose its type)."""
        return ("distributed", self.grid_axes, self.inner.cache_key(),
                self.overlap, self.time_steps, self.swap)


# --------------------------------------------------------------------------
# launch context + profiler
# --------------------------------------------------------------------------
class _Ctx(threading.local):
    def __init__(self):
        self.backend: Backend = xla()
        self.mesh = None
        self.active = False
        self.fuse_steps: Optional[int] = None
        self.time_block: Optional[int] = None
        self.autotune: Optional[Dict[str, object]] = None


_CTX = _Ctx()


@dataclasses.dataclass
class LaunchResult:
    """What a launched target returns.

    ``value`` is the target function's own return value; ``profile`` maps
    phase names to the summed wall seconds of the host spans
    (``core/trace.py``) that feed them; a phase that never ran is absent:

    - ``codegen``: ``dsl.codegen``, kernel and engine builds (trace and
      lower; an ``st.map`` build includes its compile);
    - ``comp``: ``dsl.compile``, the ahead-of-time XLA compile of an
      ``st.map`` program, and ``engine.compile``, a fusion-window cache
      miss (the program is built there; XLA compiles it at its first
      dispatch, inside ``kernel``);
    - ``kernel``: ``dsl.map``, one ``st.map`` application blocked until
      ready, and ``engine.window``, one fusion window from the layout
      stage through dispatch to ``engine.sync``;
    - ``layout``: ``layout.to_padded``, the engine's pad into its padded
      layout (inside ``kernel``);
    - ``total``: ``dsl.launch``, the whole launch.
    """
    value: object
    profile: Dict[str, float]


# --------------------------------------------------------------------------
# map — apply a kernel over a region
# --------------------------------------------------------------------------
class _MapCall:
    def __init__(self, begin=None, end=None, e=None):
        # syntax sugar (paper §4.2): map(e=u.shape) loops the whole interior
        if e is not None:
            begin = tuple(0 for _ in e)
            end = tuple(e)
        self.begin, self.end = begin, end

    def __call__(self, k: Kernel):
        def apply(*args):
            return _apply_kernel(k, args, self.begin, self.end)
        return apply


def map(begin=None, end=None, e=None) -> _MapCall:  # noqa: A001 (paper name)
    """Apply a kernel over an interior region (paper §4.2's ``map``).

    ``st.map(e=u.shape)(star2d1r)(u, v)`` sweeps the whole interior;
    ``st.map(begin=(8, 0), end=(16, 64))`` restricts the update to a
    sub-box (half-open per-axis bounds in interior coordinates — cells
    outside keep their old values).  The returned applicator binds
    positional args per the kernel signature (grids first, then scalars),
    runs one compiled application, and writes results back into the
    output grids' ``.data``.  Inside ``st.launch`` the context backend
    applies; standalone calls use ``st.xla()``.  For time stepping prefer
    ``st.timeloop`` — per-step ``map`` calls sync with the host every
    application.
    """
    return _MapCall(begin=begin, end=end, e=e)


def _bind_args(k: Kernel, args):
    """Split positional args into (grids dict, scalars dict) per the kernel
    signature, checking types and interior-shape consistency."""
    grids: Dict[str, grid] = {}
    scalars: Dict[str, object] = {}
    gi = 0
    for name in k.ir.grid_params:
        g = args[gi]
        if not isinstance(g, grid):
            raise TypeError(f"argument {gi} for '{name}' must be st.grid")
        grids[name] = g
        gi += 1
    for name, _dt in k.ir.scalar_params:
        scalars[name] = args[gi]
        gi += 1
    if gi != len(args):
        raise TypeError(f"{k.name} expects {gi} args, got {len(args)}")

    interior = next(iter(grids.values())).shape
    for g in grids.values():
        if g.shape != interior:
            raise ValueError("all grids in one map must share interior shape")
    batches = {g.batch for g in grids.values()}
    if len(batches) > 1:
        raise ValueError(
            f"all grids must share the scenario batch dimension "
            f"(got {sorted(b or 0 for b in batches)})")
    return grids, scalars


def _apply_kernel(k: Kernel, args, begin, end):
    grids, scalars = _bind_args(k, args)
    if next(iter(grids.values())).batch:
        raise ValueError("st.map does not support batched grids; use "
                         "st.timeloop(..., batch=B)")
    interior = next(iter(grids.values())).shape

    region = None
    if begin is not None:
        region = tuple((int(b), int(e)) for b, e in zip(begin, end))
        if region == tuple((0, s) for s in interior):
            region = None  # whole-interior sugar (paper's map(e=u.shape))

    backend = _CTX.backend if _CTX.active else xla()
    key = (backend.cache_key(), tuple(sorted((n, g.shape, g.order, str(g.dtype))
                                             for n, g in grids.items())), region)
    entry = k._cache.get(key)
    if entry is None:
        with _trace.span(_trace.CODEGEN, "codegen"):
            entry = _build_callable(k, backend, grids, region)
        k._cache[key] = entry

    arrays = {n: g.data for n, g in grids.items()}
    with _trace.span(_trace.MAP, "kernel"):
        out = entry(arrays, scalars)
        jax.block_until_ready(out)
    for name in k.ir.output_grids():
        grids[name].data = out[name]
    return None


# --------------------------------------------------------------------------
# timeloop — fused time stepping (kernel application + buffer swap)
# --------------------------------------------------------------------------
@dataclasses.dataclass
class TimeloopResult:
    """Execution report returned by a ``st.timeloop`` application.

    ``steps`` is the number of kernel applications requested; ``fuse_steps``
    the fusion-window size that actually ran (after clamping to the loop
    length); ``windows`` the number of compiled-program invocations
    (``ceil(steps / fuse_steps)``); ``seconds`` the wall-clock time of the
    loop body including device sync.
    """
    steps: int
    fuse_steps: int
    windows: int
    seconds: float

    @property
    def steps_per_s(self) -> float:
        """Time-step throughput, ``steps / seconds`` (inf when untimed)."""
        return self.steps / self.seconds if self.seconds > 0 else float("inf")


class _TimeloopCall:
    def __init__(self, steps: int, swap=None, fuse_steps=None, between=None,
                 batch: int = 0):
        self.steps = int(steps)
        self.swap = tuple(swap) if swap is not None else None
        self.fuse_steps = fuse_steps
        self.between = between
        self.batch = int(batch)

    def __call__(self, k: Kernel):
        def apply(*args) -> TimeloopResult:
            return _run_timeloop(k, args, self)
        return apply


def timeloop(steps: int, swap=None, fuse_steps: Optional[int] = None,
             between=None, batch: int = 0) -> _TimeloopCall:
    """Fused time stepping: ``steps`` applications of the kernel plus the
    leapfrog buffer swap, traced once and executed inside a single compiled
    program per fusion window (paper-style time-to-solution execution;
    see ``core/timeloop.py``)::

        @st.target
        def run(u: st.grid, v: st.grid, iters: st.i32):
            st.timeloop(iters, swap=("v", "u"))(star2d1r)(u, v)

    ``swap`` names the grid pair whose buffers rotate after every step (the
    pair must contain the kernel's output grid).  ``fuse_steps`` is the
    fusion-window size: the host syncs (and the optional ``between(t,
    grids)`` hook runs) only every ``fuse_steps`` steps.  Default: fuse the
    whole loop, or the enclosing ``st.launch(..., fuse_steps=K)`` value.
    Equivalent to the per-step ``st.map`` loop up to float-accumulation
    order (identical when fuse_steps=1).

    ``batch=B`` advances B independent scenarios (grids built with
    ``st.grid(..., batch=B)``, scalar params passed as floats or ``(B,)``
    arrays) in one compiled program — the per-step kernel is vmapped over
    the leading scenario axis inside the fused loop.  Defaults to the
    grids' own batch dimension when they carry one.
    """
    return _TimeloopCall(steps, swap=swap, fuse_steps=fuse_steps,
                         between=between, batch=batch)


def _run_timeloop(k: Kernel, args, call: _TimeloopCall) -> TimeloopResult:
    from . import timeloop as _tl

    grids, scalars = _bind_args(k, args)
    interior = next(iter(grids.values())).shape
    grid_batch = next(iter(grids.values())).batch or 0
    if call.batch and grid_batch and call.batch != grid_batch:
        raise ValueError(
            f"st.timeloop(batch={call.batch}) but grids carry "
            f"batch={grid_batch}")
    if call.batch and not grid_batch:
        raise ValueError(
            f"st.timeloop(batch={call.batch}) requires grids built with "
            f"st.grid(..., batch={call.batch})")
    batch = call.batch or grid_batch
    backend = _CTX.backend if _CTX.active else xla()
    mesh = _CTX.mesh if _CTX.active else None
    swap = _tl.normalize_swap(k.ir, call.swap)

    at_cfg = _CTX.autotune if _CTX.active else None
    tuned_fuse = None
    if (at_cfg is not None and swap is not None and not batch
            and call.steps > 0 and backend.kind != "distributed"):
        # st.launch(autotune=...): pick the backend (and default fusion
        # window) via the two-stage cost-model search.  The measurement
        # launches inside tune() run under their own _Launcher, whose
        # default autotune=None stops recursion.
        from . import autotune as _at
        tuned = _at.tune(
            k, grids, iters=int(at_cfg.get("iters", 1)),
            space=at_cfg.get("space"), swap=swap,
            steps=min(call.steps, int(at_cfg.get("steps", 16))),
            fuse_space=at_cfg.get("fuse_space", (1, 4, 16)),
            time_block_space=at_cfg.get("time_block_space", (1, 2, 4)),
            cache_dir=at_cfg.get("cache_dir"),
            top_k=at_cfg.get("top_k", 3),
            cost_model=at_cfg.get("cost_model"),
            # distributed candidates in a custom space are priced and
            # measured on the launch mesh
            mesh=mesh)
        backend = tuned.backend
        tuned_fuse = tuned.fuse_steps
    tb = _CTX.time_block if _CTX.active else None
    if tb is not None:
        # launch-level override of the in-kernel temporal-blocking depth
        if backend.kind == "pallas":
            backend = dataclasses.replace(backend, time_block=int(tb))
        elif (backend.kind == "distributed"
              and getattr(backend.inner, "kind", None) == "pallas"):
            backend = dataclasses.replace(
                backend, inner=dataclasses.replace(backend.inner,
                                                   time_block=int(tb)))
        elif int(tb) != 1:
            # silently running without blocking would let a user believe
            # the depth is active while measuring the plain fused loop
            raise ValueError(
                f"time_block={tb} requires a pallas backend (or a "
                f"distributed backend with a pallas inner); got "
                f"'{backend.kind}'")
    fuse = call.fuse_steps
    if fuse is None and _CTX.active:
        fuse = _CTX.fuse_steps
    if fuse is None:
        fuse = tuned_fuse        # autotuned window, unless overridden
    if fuse is not None:
        fuse = max(1, int(fuse))

    key = ("timeloop", backend.cache_key(),
           tuple(sorted((n, g.shape, g.order, str(g.dtype))
                        for n, g in grids.items())),
           swap, id(mesh) if mesh is not None else None, batch)
    engine = k._cache.get(key)
    if engine is None:
        halos = {n: g.halo for n, g in grids.items()}
        with _trace.span(_trace.CODEGEN, "codegen"):
            engine = _tl.TimeloopEngine(
                k.ir, halos, interior, backend, swap=swap, mesh=mesh,
                batch=batch)
        k._cache[key] = engine
    # clamp the window to the loop length; report the size that actually
    # runs.  Temporal depth (time_block / time_steps) never alters the
    # window — the between-hook cadence is honored exactly via in-window
    # decomposition on every backend
    fuse = engine.window_for(call.steps, fuse)

    def between_arrays(t, arrays):
        # surface current state to the user hook via the grid objects
        for n, g in grids.items():
            g.data = arrays[n]
        call.between(t, grids)
        return {n: g.data for n, g in grids.items()}

    arrays = {n: g.data for n, g in grids.items()}
    t0 = time.perf_counter()
    # window_for is idempotent, so the reported window can be passed back
    arrays = engine.run(arrays, scalars, call.steps, fuse,
                        between_arrays if call.between else None)
    seconds = time.perf_counter() - t0
    for n, g in grids.items():
        g.data = arrays[n]
    return TimeloopResult(
        steps=call.steps, fuse_steps=fuse,
        windows=-(-call.steps // fuse) if call.steps else 0,
        seconds=seconds)


def differentiable_timeloop(k: Kernel, *args,
                            steps: int,
                            swap=None,
                            fuse_steps: Optional[int] = None,
                            between=None,
                            domain_mask=None,
                            step_limits=None,
                            checkpoint_stride: Optional[int] = None,
                            backend=None,
                            mesh=None):
    """Differentiable fused time stepping (the adjoint wave propagator).

    Takes the SAME positional arguments a ``k(u, v, dt, st.timeloop(...))``
    call would (grids then scalars) and returns a PURE function

        fn(arrays: dict[str, jnp.ndarray], scalars: dict | None) -> dict

    computing ``steps`` fused applications of the kernel (+ leapfrog
    ``swap`` rotation, ``between`` hook, optional serving masks) exactly
    like ``st.timeloop`` — but reverse-mode differentiable under
    ``jax.grad``/``jax.vjp``, with O(√steps) checkpointed recomputation
    instead of O(steps) stored residuals (``core/adjoint.py``).  Gradients
    flow to every grid array (initial wavefields and coefficient grids
    such as a velocity model) and every float scalar; batched grids
    differentiate per-scenario.

    The positional args fix shapes/dtypes and provide defaults:
    ``fn.arrays`` / ``fn.scalars`` hold the bound initial values, and
    ``fn()`` runs them as-is.  ``fn.schedule`` reports the window/
    checkpoint plan.  ``between`` must be a pure traceable hook
    ``between(t, grids) -> None`` mutating ``g.data`` with jnp ops (e.g.
    source injection); it runs at window boundaries, so pass
    ``fuse_steps=1`` for a per-step cadence.  Backend/mesh come from the
    ``backend=`` / ``mesh=`` keywords, falling back to the enclosing
    ``st.launch`` context (default xla).  With
    ``backend=st.distributed(...), mesh=...`` the forward windows run as
    shard_mapped programs on the mesh and the backward pass pulls
    cotangents through each window's own reverse-``ppermute`` shard_map
    program — gradients reach sharded velocity grids and per-scenario
    scalars without ever gathering the wavefield.  The engine is built
    with ``differentiable=True`` — no buffer donation (donated window
    inputs cannot be VJP residuals), cached separately from the forward
    engine.

    Example::

        fn = st.differentiable_timeloop(
            k, u, v, c, dt, steps=200, swap=("v", "u"),
            backend=st.distributed(grid_axes=("data", None)),
            mesh=make_mesh((8,), ("data",)))  # repro.launch.mesh
        value, grads = jax.value_and_grad(
            lambda a: jnp.sum(fn(a)["v"] ** 2))(fn.arrays)
    """
    from . import adjoint as _adj
    from . import timeloop as _tl

    grids, scalars = _bind_args(k, args)
    interior = next(iter(grids.values())).shape
    batch = next(iter(grids.values())).batch or 0
    if backend is None:
        backend = _CTX.backend if _CTX.active else xla()
    if mesh is None:
        mesh = _CTX.mesh if _CTX.active else None
    swap = _tl.normalize_swap(k.ir, tuple(swap) if swap is not None else None)

    key = ("difftimeloop", backend.cache_key(),
           tuple(sorted((n, g.shape, g.order, str(g.dtype))
                        for n, g in grids.items())),
           swap, id(mesh) if mesh is not None else None, batch)
    engine = k._cache.get(key)
    if engine is None:
        halos = {n: g.halo for n, g in grids.items()}
        engine = _tl.TimeloopEngine(
            k.ir, halos, interior, backend, swap=swap, mesh=mesh,
            batch=batch, differentiable=True)
        k._cache[key] = engine

    between_arrays = None
    if between is not None:
        def between_arrays(t, arrays):
            # same grid-object surface as st.timeloop's hook — but traced,
            # so the hook must be pure jnp code on g.data
            for n, g in grids.items():
                g.data = arrays[n]
            between(t, grids)
            return {n: g.data for n, g in grids.items()}

    run = _adj.differentiable_run(
        engine, steps, fuse_steps, between_arrays,
        domain_mask=domain_mask, step_limits=step_limits,
        checkpoint_stride_windows=checkpoint_stride)

    def fn(arrays=None, scal=None):
        if arrays is None:
            arrays = {n: g.data for n, g in grids.items()}
        if scal is None:
            scal = scalars
        return run(arrays, scal)

    fn.arrays = {n: g.data for n, g in grids.items()}
    fn.scalars = dict(scalars)
    fn.schedule = run.schedule
    fn.engine = engine
    return fn


def _build_callable(k: Kernel, backend: Backend, grids: Dict[str, grid], region):
    halos = {n: g.halo for n, g in grids.items()}
    interior = next(iter(grids.values())).shape
    if backend.kind == "xla":
        fn = _lowering.lower_jax(k.ir, halos, interior, region)
        jitted = jax.jit(fn)
    elif backend.kind == "pallas":
        from repro.kernels.stencil import codegen as _codegen
        if not backend.interpret:
            _kernels.require_tpu()
        fn = _codegen.lower_pallas(k.ir, halos, interior, region, backend)
        jitted = jax.jit(fn)
    elif backend.kind == "distributed":
        from . import distributed as _dist
        fn = _dist.lower_distributed(k.ir, halos, interior, region,
                                     backend, _CTX.mesh)

        def run_dist(arrays, scalars):
            return fn(arrays, scalars)
        return run_dist
    else:
        raise ValueError(backend.kind)

    # explicit AOT compile so the profile separates comp from kernel time;
    # scalars are always passed as f32, so the signature is fixed
    abstract_arrays = {n: jax.ShapeDtypeStruct(g.data.shape, g.data.dtype)
                       for n, g in grids.items()}
    abstract_scalars = {n: jax.ShapeDtypeStruct((), jnp.float32)
                        for n, _ in k.ir.scalar_params}
    with _trace.span(_trace.MAP_COMPILE, "comp"):
        compiled = jitted.lower(abstract_arrays, abstract_scalars).compile()

    def run(arrays, scalars):
        scal = {n: jnp.asarray(v, jnp.float32) for n, v in scalars.items()}
        return compiled(arrays, scal)
    return run


# --------------------------------------------------------------------------
# launch
# --------------------------------------------------------------------------
class _Launcher:
    def __init__(self, backend: Backend, mesh=None,
                 fuse_steps: Optional[int] = None,
                 time_block: Optional[int] = None,
                 autotune: Optional[Dict[str, object]] = None):
        self.backend, self.mesh = backend, mesh
        self.fuse_steps = fuse_steps
        self.time_block = time_block
        self.autotune = autotune

    def __call__(self, tgt: Callable):
        def run(*args, **kw) -> LaunchResult:
            prev = (_CTX.backend, _CTX.mesh, _CTX.active,
                    _CTX.fuse_steps, _CTX.time_block, _CTX.autotune)
            _CTX.backend, _CTX.mesh = self.backend, self.mesh
            _CTX.active = True
            _CTX.fuse_steps = self.fuse_steps
            _CTX.time_block = self.time_block
            _CTX.autotune = self.autotune
            try:
                with _trace.collect() as prof, \
                        _trace.span(_trace.LAUNCH, "total"):
                    value = tgt(*args, **kw)
            finally:
                (_CTX.backend, _CTX.mesh, _CTX.active,
                 _CTX.fuse_steps, _CTX.time_block, _CTX.autotune) = prev
            return LaunchResult(value=value, profile=prof)
        return run


def launch(backend: Backend = None, mesh=None,
           fuse_steps: Optional[int] = None,
           time_block: Optional[int] = None,
           autotune: bool = False,
           autotune_space: Optional[List] = None,
           autotune_cache: Optional[str] = None,
           autotune_top_k: Optional[int] = 3,
           autotune_steps: int = 16,
           autotune_iters: int = 1,
           autotune_fuse_space: Sequence[int] = (1, 4, 16),
           autotune_time_block_space: Sequence[int] = (1, 2, 4),
           autotune_cost_model=None) -> _Launcher:
    """Run a ``@st.target`` under ``backend``.  ``fuse_steps`` sets the
    default fusion-window size for any ``st.timeloop`` inside the target
    (per-step ``st.map`` loops are unaffected).  ``time_block`` overrides
    the pallas backend's in-kernel temporal-blocking depth for those
    timeloops (k leapfrog steps per kernel invocation; see st.pallas).

    ``autotune=True`` replaces the fixed ``backend`` for each
    ``st.timeloop`` with the winner of the two-stage cost-model search
    over ``autotune_space`` (see ``core/autotune.py``): all candidates
    are ranked by predicted cost, only the ``autotune_top_k`` cheapest
    are measured (``None`` → exhaustive), and results are cached
    in-process and — with ``autotune_cache`` — on disk.  The tuned
    fusion window applies unless ``fuse_steps`` (or the timeloop's own)
    overrides it; ``time_block`` still applies on top of the tuned
    backend.  Batched, distributed, and swap-less timeloops fall
    through to the fixed backend unchanged."""
    at_cfg = None
    if autotune:
        at_cfg = {"space": autotune_space, "cache_dir": autotune_cache,
                  "top_k": autotune_top_k, "steps": int(autotune_steps),
                  "iters": int(autotune_iters),
                  "fuse_space": tuple(autotune_fuse_space),
                  "time_block_space": tuple(autotune_time_block_space),
                  "cost_model": autotune_cost_model}
    return _Launcher(backend or xla(), mesh=mesh,
                     fuse_steps=fuse_steps, time_block=time_block,
                     autotune=at_cfg)
