"""Analytical cost model for autotune candidates: predict, don't measure.

The autotuner's search space (template × block × fuse_steps × time_block)
already runs to dozens of compiled candidates per kernel, and every
planned extension — streaming templates, meshes, batch sizes — multiplies
it.  Devito ships an analytical performance model next to its autotuner
for exactly this reason; this module is ours.

A candidate's cost is modeled as roofline time over its HBM traffic plus
a per-dispatch overhead::

    seconds ≈ (steps · bytes_per_step + windows · bytes_per_window)
              / bytes_per_s  +  windows · overhead_s

where the traffic terms are **deterministic geometry**, not measurements:

  * pallas candidates — ``PallasPlan`` is constructed (never compiled)
    and charged ``plan.hbm_bytes_per_step()`` for the steady-state kernel
    stage plus ``plan.layout_bytes_per_window()`` for the per-window
    to_padded/make_spares/from_padded costs.  A plan that raises
    ``ValueError`` (infeasible k·h, misaligned f4 block, …) predicts
    ``inf`` — the same value measuring it would produce.
  * xla candidates — a short probe window is AOT-lowered once per
    (kernel, geometry) via ``lowering.lower_jax_window`` and the HLO-text
    walk (``launch/hlo_analysis.op_stats``) charges its trip-count-aware
    HBM bytes; the result is memoized so one compile covers every
    ``fuse_steps`` expansion of the candidate.

``(bytes_per_s, overhead_s)`` is a per-(execution class, dtype) **rate**
calibrated once per process from a tiny star2d1r probe timeloop — a fully
fused run pins the bandwidth term, a fuse=1 run of the same loop isolates
the per-window overhead — and persisted next to the autotune disk cache
(``roofline-v{CALIBRATION_VERSION}-{jax_backend}.json``) so warm
processes never re-probe.  ``CostModel(calibrate=False)`` skips probing
and uses ``DEFAULT_RATES`` (deterministic — what the tests rank with).

The model's job is *ranking*, not absolute prediction: the calibrated
~10³ bandwidth gap between compiled XLA and interpret-mode pallas, and
the monotone window-overhead term, are what ``autotune.tune``'s two-stage
search prunes with.  ``benchmarks/timeloop.py`` records predicted-vs-
measured rank quality and CI guards it (``check_regression.py``).

Distributed candidates are priced when the caller supplies the mesh
(``predict(..., mesh=...)``): the per-shard compute term reuses the XLA
byte accounting at the *local* shard shape (the fused sharded window runs
its sub-steps through the same ``lower_jax`` regions — a Pallas ``inner``
only changes exchange depth), and the collective term charges
``halo.HaloSpec.window_collective_bytes`` — the exact per-window ppermute
traffic of ``distributed.lower_distributed_window`` — against the
``"link"`` rate plus one link overhead per exchange group.  The link
rate is *measured* when the caller's mesh is a real ``jax.sharding.Mesh``
with ≥ 2 devices: a tiny ppermute ring probe at two message sizes solves
(bytes_per_s, overhead_s) for that device count, keyed
``link@{ndev}/{dtype}`` and persisted in the roofline JSON beside the
compute rates.  A plain ``{axis: size}`` mapping (shape known, devices
unknown) or a single-device mesh falls back to the fixed
``DEFAULT_RATES["link"]``.  Without a mesh the prediction stays ``None``
(geometry unknown → the tuner measures).
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import os
import tempfile
from typing import Dict, Optional, Tuple

import jax
import numpy as np

from . import dsl as st
from . import halo as _halo
from . import lowering as _lowering
from . import timeloop as _tl
from repro.launch import hlo_analysis as _hlo

__all__ = ["CALIBRATION_VERSION", "Rate", "DEFAULT_RATES", "CostModel",
           "default_model", "reset_default_models", "exec_key",
           "kernel_fingerprint"]

#: bump when the prediction formula or the probe protocol changes —
#: persisted calibrations (and disk tune entries, which key on this via
#: ``autotune._disk_key``) then miss and re-derive
#: (v2: measured ``link@{ndev}`` rates join the roofline JSON)
CALIBRATION_VERSION = 2

#: fori_loop length of the AOT-lowered window used for XLA byte
#: accounting: ≥ 2 keeps the loop a genuine ``while`` in optimized HLO
#: (a trip-count-1 loop may be simplified away), and per-window constants
#: average out over the probe steps
_XLA_PROBE_STEPS = 4

#: probe geometry per execution class: small enough that calibration is
#: ~a second, large enough that the fused run is traffic- not
#: overhead-dominated
_PROBE = {
    "xla": {"shape": (48, 48), "steps": 12},
    "pallas": {"shape": (32, 32), "steps": 8},
    "pallas_interpret": {"shape": (24, 32), "steps": 6},
}


def kernel_fingerprint(kernel: st.Kernel) -> str:
    """Content hash of a kernel: name + its StencilIR repr.  Editing the
    kernel body changes the fingerprint, invalidating disk entries."""
    text = f"{kernel.name}:{kernel.ir!r}"
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def exec_key(backend) -> Optional[str]:
    """Calibration class of a backend — which measured rate applies.
    ``None`` means the backend has no single-device execution class:
    distributed cost is mesh-dependent, so ``predict`` prices it only
    when given the mesh (compute at the local shape over the xla rate +
    collectives over the ``"link"`` rate) and the tuner measures it
    otherwise."""
    kind = getattr(backend, "kind", None)
    if kind == "xla":
        return "xla"
    if kind == "pallas":
        return "pallas_interpret" if backend.interpret else "pallas"
    return None


@dataclasses.dataclass(frozen=True)
class Rate:
    """Calibrated execution rate for one (execution class, dtype):
    effective bandwidth against the model's own byte accounting, plus a
    fixed per-dispatch overhead charged once per fusion window."""
    bytes_per_s: float
    overhead_s: float


#: fixed rates for ``CostModel(calibrate=False)``, the deterministic
#: testing configuration.  Absolute values are deliberately coarse; the
#: ranking-relevant property is the ~10³ bandwidth gap between compiled
#: paths and interpret-mode pallas.
DEFAULT_RATES: Dict[str, Rate] = {
    "xla": Rate(bytes_per_s=2e9, overhead_s=2e-4),
    "pallas": Rate(bytes_per_s=2e9, overhead_s=2e-4),
    "pallas_interpret": Rate(bytes_per_s=2e6, overhead_s=2e-3),
    # inter-shard halo-exchange traffic: bandwidth per ppermute byte plus
    # a fixed latency per exchange *group* (one exchange round).  This is
    # the NO-MESH fallback only — ``rate_for("link", dtype, mesh=...)``
    # measures the real rate with a ppermute ring probe whenever the mesh
    # carries ≥ 2 actual devices.  The ranking-relevant property of the
    # fallback is that link bytes are slower and rounds far more expensive
    # than local HBM, so deeper time skewing (fewer, wider exchanges)
    # predicts cheaper.
    "link": Rate(bytes_per_s=1e9, overhead_s=5e-4),
}


def _rate_key(key: str, dtype) -> str:
    return f"{key}/{np.dtype(dtype).name}"


def _probeable_mesh(mesh):
    """The mesh, iff it is a real ``jax.sharding.Mesh`` whose device set a
    ppermute probe can actually exercise (≥ 2 devices); else ``None``.
    Plain ``{axis: size}`` shape mappings price geometry but name no
    devices, so the link rate stays the fixed fallback for them."""
    devices = getattr(mesh, "devices", None)
    if devices is None:
        return None
    return mesh if np.asarray(devices).size >= 2 else None


class CostModel:
    """Deterministic candidate-cost predictor (see module docstring).

    ``cache_dir`` — persist/load calibrated rates next to the autotune
    disk cache.  ``calibrate=False`` — never probe; use ``rates`` then
    ``DEFAULT_RATES`` (fully deterministic, the testing configuration).
    ``rates`` — pre-seeded {"class/dtype": Rate} overrides.
    """

    def __init__(self, cache_dir: Optional[str] = None,
                 calibrate: bool = True,
                 rates: Optional[Dict[str, Rate]] = None):
        self.cache_dir = cache_dir
        self.calibrate = calibrate
        self._rates: Dict[str, Rate] = dict(rates or {})
        self._bytes_memo: Dict = {}
        if cache_dir:
            self._load_rates()

    # -- rates -------------------------------------------------------------
    def rate_for(self, key: str, dtype, mesh=None) -> Rate:
        """Calibrated (or default) rate for one execution class × dtype.
        First use per process probes (when ``calibrate``) and persists.

        ``mesh`` applies to the ``"link"`` class only: a real device mesh
        (≥ 2 devices) switches to the *measured* inter-shard rate for that
        device count — probed once with a ppermute ring and persisted as
        ``link@{ndev}/{dtype}`` — while a shape-only mapping, a 1-device
        mesh, or no mesh keeps the fixed ``DEFAULT_RATES["link"]``."""
        probe_mesh = _probeable_mesh(mesh) if key == "link" else None
        rk = (_rate_key(f"link@{np.asarray(probe_mesh.devices).size}", dtype)
              if probe_mesh is not None else _rate_key(key, dtype))
        r = self._rates.get(rk)
        if r is None:
            if key == "link" and probe_mesh is None:
                # nothing to measure against — fixed fallback, not cached
                # to disk so a later real-mesh call still probes
                return DEFAULT_RATES["link"]
            if self.calibrate:
                # a failing probe raises: ranking against made-up rates
                # for a device that was never measured would hide it
                r = (self._probe_link(dtype, probe_mesh)
                     if key == "link" else self._probe(key, dtype))
            else:
                r = DEFAULT_RATES[key]
            self._rates[rk] = r
            if self.cache_dir:
                self._store_rates()
        return r

    def _probe(self, key: str, dtype) -> Rate:
        """Measure one Rate from a tiny star2d1r timeloop.

        A fully fused run (one window) and a fuse=1 run (one window per
        step) of the same ``steps``-step loop differ only in window
        count, so::

            overhead_s  = (t_split − t_full) / (steps − 1)
            bytes_per_s = (steps·bytes_per_step + bytes_per_window)
                          / (t_full − overhead_s)

        with the byte terms taken from this model's own accounting — the
        calibration is consistent with prediction by construction."""
        from . import suite as _suite
        cfg = _PROBE[key]
        shape, steps = cfg["shape"], cfg["steps"]
        if key == "xla":
            backend = st.xla()
        else:
            backend = st.pallas(template="gmem",
                                interpret=(key == "pallas_interpret"))
        k = _suite.get_kernel("star2d1r")
        swap = _suite.swap_pair("star2d1r")

        def run_once(fuse: int) -> float:
            grids = {g: st.grid(dtype, shape, k.info.order).randomize(i)
                     for i, g in enumerate(k.ir.grid_params)}

            def tgt(*args):
                return st.timeloop(steps, swap=swap,
                                   fuse_steps=fuse)(k)(*args)

            run = st.launch(backend=backend)
            args = tuple(grids.values())
            run(tgt)(*args)                  # warmup: codegen + compile
            return min(run(tgt)(*args).value.seconds for _ in range(2))

        t_full = run_once(steps)
        t_split = run_once(1)
        overhead = max((t_split - t_full) / max(steps - 1, 1), 1e-8)
        halos = {g: (k.info.order,) * k.info.ndim for g in k.ir.grid_params}
        per_step, per_window = self.step_bytes(k, halos, tuple(shape),
                                               backend, swap, dtype)
        bw = (steps * per_step + per_window) / max(t_full - overhead, 1e-9)
        return Rate(bytes_per_s=max(bw, 1.0), overhead_s=overhead)

    #: ppermute-probe protocol: per-shard message elements at the two
    #: sizes, and rounds per timed call (amortizes dispatch the same way
    #: the fused exchange schedule does)
    _LINK_PROBE = {"small": 1 << 10, "big": 1 << 16, "rounds": 8}

    def _probe_link(self, dtype, mesh) -> Rate:
        """Measure the inter-shard ``"link"`` Rate on a real device mesh.

        All mesh devices form a 1-D ppermute ring (the exact collective
        ``distributed.lower_distributed_window`` issues per halo
        exchange); one jitted shard_map runs ``rounds`` ring shifts over a
        per-shard message.  Timing that program at two message sizes
        gives two equations in the roofline's two unknowns::

            t(bytes) = bytes / bytes_per_s + overhead_s

        so ``bytes_per_s = Δbytes/Δt`` and ``overhead_s`` falls out of the
        small-message time.  Bytes are per shard per round — the same
        accounting ``HaloSpec.window_collective_bytes`` charges."""
        import time
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P

        devs = np.asarray(mesh.devices).reshape(-1)
        n = devs.size
        ring = Mesh(devs, ("ring",))
        perm = [(i, (i + 1) % n) for i in range(n)]
        rounds = self._LINK_PROBE["rounds"]

        def ring_fn(x):
            def body(_, y):
                return jax.lax.ppermute(y, "ring", perm)
            return jax.lax.fori_loop(0, rounds, body, x)

        def per_round_seconds(elems: int) -> float:
            x = jnp.zeros((n * elems,), dtype)
            f = jax.jit(jax.shard_map(ring_fn, mesh=ring,
                                  in_specs=P("ring"), out_specs=P("ring")))
            f(x).block_until_ready()          # compile + warm the path
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                f(x).block_until_ready()
                best = min(best, time.perf_counter() - t0)
            return best / rounds

        itemsize = np.dtype(dtype).itemsize
        small, big = self._LINK_PROBE["small"], self._LINK_PROBE["big"]
        t_small = per_round_seconds(small)
        t_big = per_round_seconds(big)
        d_bytes = (big - small) * itemsize
        bw = d_bytes / max(t_big - t_small, 1e-12)
        overhead = max(t_small - small * itemsize / bw, 1e-8)
        return Rate(bytes_per_s=max(bw, 1.0), overhead_s=overhead)

    # -- calibration persistence (next to the autotune disk cache) ---------
    def _cal_path(self) -> str:
        return os.path.join(
            self.cache_dir,
            f"roofline-v{CALIBRATION_VERSION}-{jax.default_backend()}.json")

    def _load_rates(self) -> None:
        try:
            with open(self._cal_path()) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError):
            return
        if data.get("version") != CALIBRATION_VERSION:
            return
        for rk, r in data.get("rates", {}).items():
            try:
                self._rates.setdefault(
                    rk, Rate(float(r["bytes_per_s"]), float(r["overhead_s"])))
            except (KeyError, TypeError, ValueError):
                continue

    def _store_rates(self) -> None:
        entry = {
            "version": CALIBRATION_VERSION,
            "jax_backend": jax.default_backend(),
            "rates": {rk: {"bytes_per_s": r.bytes_per_s,
                           "overhead_s": r.overhead_s}
                      for rk, r in self._rates.items()},
        }
        os.makedirs(self.cache_dir, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=self.cache_dir, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(entry, f, indent=1, sort_keys=True)
            os.replace(tmp, self._cal_path())
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass

    # -- traffic -----------------------------------------------------------
    def step_bytes(self, kernel: st.Kernel, halos, interior, backend,
                   swap, dtype) -> Optional[Tuple[float, float]]:
        """(bytes per time step, bytes per fusion window) for a candidate,
        from geometry alone — no compilation on the pallas path, one
        memoized AOT lowering per (kernel, geometry) on the xla path.
        ``(inf, 0)`` marks an infeasible pallas plan; ``None`` a backend
        the model cannot account (the tuner measures those)."""
        key = exec_key(backend)
        if key is None:
            return None
        memo_key = (kernel_fingerprint(kernel),
                    tuple(sorted((g, tuple(h)) for g, h in halos.items())),
                    tuple(interior), backend.cache_key(),
                    tuple(swap) if swap else None, np.dtype(dtype).name)
        hit = self._bytes_memo.get(memo_key)
        if hit is not None:
            return hit
        itemsize = np.dtype(dtype).itemsize
        if key == "xla":
            out = (self._xla_step_bytes(kernel, halos, interior, swap,
                                        dtype), 0.0)
        else:
            from repro.kernels.stencil import codegen as _codegen
            try:
                plan = _codegen.plan_pallas(kernel.ir, dict(halos),
                                            tuple(interior), backend,
                                            swap=tuple(swap) if swap
                                            else None)
            except ValueError:
                out = (float("inf"), 0.0)
            else:
                out = (plan.hbm_bytes_per_step(itemsize),
                       plan.layout_bytes_per_window(itemsize))
        self._bytes_memo[memo_key] = out
        return out

    def _xla_step_bytes(self, kernel, halos, interior, swap, dtype) -> float:
        """Per-step HBM bytes of the fused xla window: AOT-lower a short
        ``lower_jax_window`` probe and walk its optimized HLO.  The probe
        length divides out, so one compile serves every fuse_steps."""
        try:
            steps = _XLA_PROBE_STEPS
            win = _lowering.lower_jax_window(
                kernel.ir, dict(halos), tuple(interior), None,
                tuple(swap) if swap else None, steps)
            abstract = {
                g: jax.ShapeDtypeStruct(
                    tuple(interior[ax] + 2 * halos[g][ax]
                          for ax in range(len(interior))), dtype)
                for g in kernel.ir.grid_params}
            scal = {n: jax.ShapeDtypeStruct((), np.float32)
                    for n, _dt in kernel.ir.scalar_params}
            compiled = jax.jit(win).lower(abstract, scal).compile()
            stats = _hlo.op_stats(compiled.as_text())
            return float(stats.hbm_bytes) / steps
        except Exception:
            # mirror the tuner's measured semantics: a candidate that
            # cannot lower/compile costs inf and never wins
            return float("inf")

    # -- prediction --------------------------------------------------------
    def predict(self, kernel: st.Kernel, grids: Dict[str, st.grid],
                backend, fuse: int, steps: int,
                swap: Optional[Tuple[str, str]],
                mesh=None) -> Optional[float]:
        """Predicted seconds for the quantity the tuner measures: ``steps``
        fused time steps (or one application when ``swap`` is None).
        ``None`` — unpredictable backend; ``inf`` — infeasible candidate.
        ``mesh`` (a ``jax.sharding.Mesh`` or an {axis: size} mapping)
        makes distributed candidates predictable; without it they stay
        ``None`` and are always measured.
        """
        if getattr(backend, "kind", None) == "distributed":
            return self._predict_distributed(kernel, grids, backend, fuse,
                                             steps, swap, mesh)
        key = exec_key(backend)
        if key is None:
            return None
        g0 = next(iter(grids.values()))
        interior = tuple(g0.shape)
        batch = max(1, int(g0.batch or 1))
        halos = {n: g.halo for n, g in grids.items()}
        sb = self.step_bytes(kernel, halos, interior, backend, swap,
                             g0.dtype)
        if sb is None:
            return None
        per_step, per_window = sb
        if not math.isfinite(per_step):
            return float("inf")
        rate = self.rate_for(key, g0.dtype)
        if swap is None:
            return batch * per_step / rate.bytes_per_s + rate.overhead_s
        steps = max(1, int(steps))
        windows = -(-steps // max(1, int(fuse)))
        traffic = batch * (steps * per_step + windows * per_window)
        return traffic / rate.bytes_per_s + windows * rate.overhead_s

    def _predict_distributed(self, kernel, grids, backend, fuse, steps,
                             swap, mesh) -> Optional[float]:
        """Price a distributed candidate on a known mesh: per-shard compute
        bytes at the local shape over the xla rate (the fused window's
        sub-steps run through ``lower_jax``) + per-window ``HaloSpec``
        collective bytes over the link rate + one link overhead per
        exchange group.  Mirrors ``distributed.lower_distributed_window``'s
        schedule; infeasible geometry (indivisible mesh, k·h too deep for
        the shard) predicts ``inf`` like a failed compile would measure."""
        if mesh is None:
            return None
        mesh_shape = (dict(mesh.shape) if hasattr(mesh, "shape")
                      else dict(mesh))
        g0 = next(iter(grids.values()))
        interior = tuple(g0.shape)
        batch = max(1, int(g0.batch or 1))
        halos = {n: tuple(g.halo) for n, g in grids.items()}
        itemsize = np.dtype(g0.dtype).itemsize
        steps = max(1, int(steps))
        if swap is None:
            # the tuner measures a single application for swap-less targets
            steps, window, windows = 1, 1, 1
            depth = 1
        else:
            window = min(max(1, int(fuse)), steps)
            windows = -(-steps // window)
            depth = min(backend.time_steps * _tl.backend_time_block(backend),
                        window)
        h_max = max((h for hs in halos.values() for h in hs), default=0)
        if h_max == 0:
            depth = 1
        try:
            spec = _halo.HaloSpec.build(halos, backend.grid_axes, interior,
                                        mesh_shape, depth=depth, swap=swap)
        except ValueError:
            return float("inf")
        sb = self.step_bytes(kernel, halos, spec.local_shape, st.xla(),
                             swap, g0.dtype)
        if sb is None:
            return None
        per_step, _ = sb
        if not math.isfinite(per_step):
            return float("inf")
        crate = self.rate_for("xla", g0.dtype)
        # measured inter-shard rate when the candidate mesh names real
        # devices; the fixed default for shape-only meshes
        lrate = self.rate_for("link", g0.dtype, mesh=mesh)
        coll_w = spec.window_collective_bytes(window, itemsize, batch=batch)
        groups_w = sum(c for c, _d in spec.group_depths(window))
        compute = (batch * steps * per_step / crate.bytes_per_s
                   + windows * crate.overhead_s)
        comm = windows * (coll_w / lrate.bytes_per_s
                          + groups_w * lrate.overhead_s)
        return compute + comm


# -- shared default models (one calibration per process per cache dir) -----
_MODELS: Dict[Optional[str], CostModel] = {}


def default_model(cache_dir: Optional[str] = None) -> CostModel:
    """Process-wide calibrated model per cache directory — the one
    ``autotune.tune`` builds when pruning without an explicit model, so
    repeated tunes share probes and memoized traffic."""
    m = _MODELS.get(cache_dir)
    if m is None:
        m = CostModel(cache_dir=cache_dir, calibrate=True)
        _MODELS[cache_dir] = m
    return m


def reset_default_models() -> None:
    """Drop shared models (tests / simulating a fresh process)."""
    _MODELS.clear()
