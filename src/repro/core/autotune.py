"""Auto-tuner over backend templates and their knobs (paper §7 lists this
as future work — implemented here as grid search with measured
time-to-solution, the paper's own metric).

    from repro.core import autotune, dsl as st
    best = autotune.tune(kernel, grids, iters=3)
    st.launch(backend=best.backend)(target)(...)

The search space mirrors Table 6's configuration column: template ×
block (Dx/Dy/Dz) × mem_type × prefetch.  When a ``swap`` pair is given,
the tuner measures fused time-loop execution instead of single
applications and searches the fusion-window size ``fuse_steps`` — and,
for pallas candidates, the in-kernel temporal-blocking depth
``time_block`` — alongside the backend knobs::

    best = autotune.tune(kernel, grids, swap=("v", "u"), steps=32)
    st.launch(backend=best.backend, fuse_steps=best.fuse_steps)(target)(...)

(``best.backend`` carries the winning ``time_block``; candidates whose
k·h halo cannot fit any block are measured as inf and never win.)

Candidates are deduplicated on (backend, fuse_steps) before measuring —
a custom ``space`` overlapping ``fuse_space``/``time_block_space`` pays
for each distinct configuration once.

**Two-stage search** (``top_k``): when the deduplicated space exceeds
``top_k`` candidates, every candidate is first *ranked* by the
analytical cost model (``core/cost_model.py`` — modeled HBM traffic over
a calibrated roofline, no compilation) and only the ``top_k`` cheapest
predicted are measured; candidates the model cannot predict are always
measured.  Distributed candidates are predictable (and hence prunable)
when the tuner is given the mesh (``tune(..., mesh=...)`` — compute at
the local shard shape plus ``HaloSpec`` collective bytes over the link
rate); without a mesh they stay unpredictable and are always measured.
``top_k=None`` recovers the exhaustive search.  ``TuneResult`` records the predictions, the
pruned-candidate count, and the predicted rank of the measured winner
(``rank_error`` — 0 means the model's first choice won), and the disk
cache persists all three so ``benchmarks/check_regression.py`` can guard
model quality.

Results are cached per (kernel, grid geometry, search space, iters,
time-loop configuration) so repeated launches pay once; a custom ``space``
or ``iters`` gets its own cache entry (``clear_cache()`` resets).

The in-process ``_CACHE`` is a read-through layer over an optional
**on-disk JSON cache** (one file per entry, atomic tmp-then-rename
writes), so a warm server process never re-measures configurations a
previous process already tuned.  Disk entries are keyed by (kernel
fingerprint, interior *shape bucket*, search-space/time-loop
configuration, jax backend) with schema versioning — a schema bump or a
different search space simply misses.  Enable it with the
``REPRO_AUTOTUNE_CACHE=<dir>`` environment variable or the
``cache_dir=`` argument to ``tune``.  ``MEASURE_COUNT`` counts actually
measured candidates; a warm-cache hit leaves it untouched (asserted in
CI via ``benchmarks/serve.py``).
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
import itertools
import json
import os
import tempfile
import time
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from . import cost_model as _cost
from . import dsl as st
from . import timeloop as _tl
from .cost_model import kernel_fingerprint  # noqa: F401  (re-export)

_CACHE: Dict = {}

#: bump when the on-disk entry layout changes — old entries then miss
#: (and ``purge_stale`` removes them on first touch of the directory).
#: v2: two-stage search fields (predictions, pruning, rank error) and the
#: cost-model calibration version in the key.
SCHEMA_VERSION = 2

#: environment variable naming the on-disk cache directory
CACHE_ENV = "REPRO_AUTOTUNE_CACHE"

#: measured-candidate counter: ``MEASURE_COUNT["measured_candidates"]``
#: increments once per (backend, fuse) configuration actually timed, and
#: ``MEASURE_COUNT["pruned_candidates"]`` once per candidate the cost
#: model pruned from the measured shortlist.  A warm cache (in-process
#: or disk) serves without touching either.  ``MEASURE_COUNT["failed"]``
#: counts candidates whose run raised; they score ``inf``, and the
#: exceptions are kept in ``MEASURE_FAILURES`` as ``(backend, exception)``
#: so a kernel the compiler refuses is visible, not silently outranked.
MEASURE_COUNT: collections.Counter = collections.Counter()
MEASURE_FAILURES: List[Tuple[object, BaseException]] = []


def clear_cache() -> None:
    """Drop all memoized tuning results (in-process layer only)."""
    _CACHE.clear()


def reset_measure_count() -> None:
    """Zero the ``MEASURE_COUNT`` telemetry counters (tests use this to
    assert exactly how many candidates a tune() call measured/pruned)."""
    MEASURE_COUNT.clear()
    MEASURE_FAILURES.clear()


def _failed(backend, exc: Exception) -> float:
    """Record a candidate whose run raised; its score is ``inf``."""
    MEASURE_COUNT["failed"] += 1
    MEASURE_FAILURES.append((backend, exc))
    warnings.warn(f"autotune candidate {backend} failed: {exc!r}")
    return float("inf")


def shape_bucket(shape: Sequence[int]) -> Tuple[int, ...]:
    """Round each interior extent up to a power of two (floor 8).

    Disk cache entries and the serving layer share this bucketing, so
    mixed request sizes map onto a small set of compiled/tuned
    configurations."""
    return tuple(max(8, 1 << (int(s) - 1).bit_length()) for s in shape)


@dataclasses.dataclass
class TuneResult:
    backend: st.Backend
    seconds: float
    trials: List[Tuple[st.Backend, int, float]]  # (backend, fuse_steps, s)
    fuse_steps: int = 1
    #: every candidate with its modeled cost — (backend, fuse_steps,
    #: predicted seconds | inf (infeasible) | None (unpredictable)).
    #: Empty when no cost model ran (small space, no explicit model).
    predicted: List[Tuple[st.Backend, int, Optional[float]]] = \
        dataclasses.field(default_factory=list)
    #: candidates ranked out of the measured shortlist by the cost model
    pruned_candidates: int = 0
    #: candidates actually timed (== len(trials))
    measured_candidates: int = 0
    #: predicted rank (0-based) of the measured-best candidate — 0 means
    #: the model's first choice also measured fastest; None without a model
    rank_error: Optional[int] = None
    #: the shortlist size this result was tuned with (None = exhaustive)
    top_k: Optional[int] = None


# --------------------------------------------------------------------------
# on-disk cache (read-through under _CACHE)
# --------------------------------------------------------------------------
def _backend_to_json(b) -> Optional[dict]:
    """JSON form of a tunable backend (xla / pallas).  Distributed
    backends carry live mesh references and are not persisted."""
    if b.kind == "xla":
        return {"kind": "xla"}
    if b.kind == "pallas":
        return {"kind": "pallas", "template": b.template,
                "block": list(b.block) if b.block else None,
                "mem_type": b.mem_type, "prefetch": bool(b.prefetch),
                "interpret": bool(b.interpret),
                "time_block": int(b.time_block)}
    return None


def _backend_from_json(d: dict):
    if d["kind"] == "xla":
        return st.xla()
    return st.pallas(template=d["template"],
                     block=tuple(d["block"]) if d["block"] else None,
                     mem_type=d["mem_type"], prefetch=d["prefetch"],
                     interpret=d["interpret"], time_block=d["time_block"])


def _seconds_to_json(s: float):
    return None if not np.isfinite(s) else float(s)


def _pred_to_json(p: Optional[float]):
    """Predictions distinguish inf (infeasible) from None (unpredictable),
    and JSON has no inf — encode it as the string "inf"."""
    if p is None:
        return None
    return "inf" if not np.isfinite(p) else float(p)


def _pred_from_json(p):
    if p is None:
        return None
    return float("inf") if p == "inf" else float(p)


def cache_dir_from_env() -> Optional[str]:
    """Disk-cache directory from ``$REPRO_AUTOTUNE_CACHE`` (the
    ``CACHE_ENV`` variable), or ``None`` when unset/empty — the default
    ``cache_dir`` for ``tune()`` callers that want environment control."""
    return os.environ.get(CACHE_ENV) or None


def _disk_key(kernel, grids, iters, space, swap, steps, fuse_space,
              time_block_space, top_k) -> Tuple[str, dict]:
    """(digest, human-readable key dict) for one disk entry.

    Geometry enters as the *shape bucket* (plus halo order and dtype), so
    every request shape inside a bucket shares the tuned entry — the same
    bucketing the serving layer packs waves by."""
    g0 = next(iter(grids.values()))
    readable = {
        "schema": SCHEMA_VERSION,
        "kernel": kernel.name,
        "fingerprint": kernel_fingerprint(kernel),
        "shape_bucket": list(shape_bucket(g0.shape)),
        "geometry": sorted([n, g.order, str(np.dtype(g.dtype))]
                           for n, g in grids.items()),
        "iters": int(iters),
        "space": repr(_space_key(space)),
        "swap": list(swap) if swap else None,
        "steps": int(steps) if swap else None,
        "fuse_space": [int(f) for f in fuse_space] if swap else None,
        "time_block_space":
            [int(t) for t in time_block_space] if swap else None,
        "top_k": int(top_k) if top_k is not None else None,
        # a recalibrated cost model can change the shortlist, so the
        # calibration version is part of the key
        "calibration": _cost.CALIBRATION_VERSION,
        "jax_backend": jax.default_backend(),
    }
    blob = json.dumps(readable, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:24], readable


def _disk_load(cdir: str, digest: str, readable: dict) -> Optional[TuneResult]:
    path = os.path.join(cdir, f"tune-{digest}.json")
    try:
        with open(path) as f:
            entry = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    if entry.get("schema") != SCHEMA_VERSION or entry.get("key") != readable:
        return None  # schema bump or (hash-collision-safe) key mismatch
    try:
        trials = [(_backend_from_json(b), int(fs),
                   float("inf") if s is None else float(s))
                  for b, fs, s in entry["trials"]]
        predicted = [(_backend_from_json(b), int(fs), _pred_from_json(p))
                     for b, fs, p in entry.get("predicted", [])]
        search = entry.get("search", {})
        best = entry["best"]
        rank = search.get("rank_error")
        tk = search.get("top_k")
        return TuneResult(backend=_backend_from_json(best["backend"]),
                          seconds=float("inf") if best["seconds"] is None
                          else float(best["seconds"]),
                          trials=trials, fuse_steps=int(best["fuse_steps"]),
                          predicted=predicted,
                          pruned_candidates=int(
                              search.get("pruned_candidates", 0)),
                          measured_candidates=int(
                              search.get("measured_candidates",
                                         len(trials))),
                          rank_error=int(rank) if rank is not None else None,
                          top_k=int(tk) if tk is not None else None)
    except (KeyError, TypeError, ValueError):
        return None


def _disk_store(cdir: str, digest: str, readable: dict,
                result: TuneResult) -> None:
    bjs = [(_backend_to_json(b), f, s) for b, f, s in result.trials]
    pjs = [(_backend_to_json(b), f, p) for b, f, p in result.predicted]
    if any(b is None for b, _, _ in bjs) \
            or any(b is None for b, _, _ in pjs) \
            or _backend_to_json(result.backend) is None:
        return  # non-serializable backend in the space (e.g. distributed)
    entry = {
        "schema": SCHEMA_VERSION,
        "key": readable,
        "best": {"backend": _backend_to_json(result.backend),
                 "fuse_steps": int(result.fuse_steps),
                 "seconds": _seconds_to_json(result.seconds)},
        "trials": [[b, int(f), _seconds_to_json(s)] for b, f, s in bjs],
        "predicted": [[b, int(f), _pred_to_json(p)] for b, f, p in pjs],
        "search": {"top_k": result.top_k,
                   "pruned_candidates": int(result.pruned_candidates),
                   "measured_candidates": int(result.measured_candidates),
                   "rank_error": result.rank_error},
    }
    os.makedirs(cdir, exist_ok=True)
    # checkpoint.py's tmp-then-rename idiom: readers never see torn writes
    fd, tmp = tempfile.mkstemp(dir=cdir, suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(entry, f, indent=1, sort_keys=True)
        os.replace(tmp, os.path.join(cdir, f"tune-{digest}.json"))
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass


#: directories already swept by ``purge_stale`` this process (one-shot)
_PURGED: set = set()


def purge_stale(cdir: Optional[str] = None) -> int:
    """Remove tune entries written under a different ``SCHEMA_VERSION``
    (or unreadable ones) from ``cdir``.  Without this a schema bump would
    strand every old file on disk forever — a changed key layout also
    changes the digest, so stale files would never even be overwritten.
    ``tune`` runs this once per directory per process on first touch.
    Returns the number of entries removed."""
    cdir = cdir or cache_dir_from_env()
    if not cdir or not os.path.isdir(cdir):
        return 0
    n = 0
    for name in os.listdir(cdir):
        if not (name.startswith("tune-") and name.endswith(".json")):
            continue
        path = os.path.join(cdir, name)
        try:
            with open(path) as f:
                stale = json.load(f).get("schema") != SCHEMA_VERSION
        except (OSError, json.JSONDecodeError):
            stale = True
        if stale:
            try:
                os.unlink(path)
                n += 1
            except OSError:
                pass
    return n


def clear_disk_cache(cdir: Optional[str] = None) -> int:
    """Remove all on-disk entries in ``cdir`` (default: the env-var
    directory).  Returns the number of entries removed."""
    cdir = cdir or cache_dir_from_env()
    if not cdir or not os.path.isdir(cdir):
        return 0
    n = 0
    for name in os.listdir(cdir):
        if name.startswith("tune-") and name.endswith(".json"):
            try:
                os.unlink(os.path.join(cdir, name))
                n += 1
            except OSError:
                pass
    return n


def default_space(ndim: int, interior: Sequence[int]) -> List[st.Backend]:
    """Candidate backends (pruned to blocks that fit the domain)."""
    if ndim == 3:
        blocks = [(8, 8, 128), (8, 16, 128), (16, 8, 128), (8, 8, 256)]
        sblocks = [(16, 8, 128), (32, 8, 128)]
    else:
        blocks = [(8, 128), (16, 128), (8, 256)]
        sblocks = [(16, 128), (32, 128)]
    out: List[st.Backend] = [st.xla()]
    for t in ("gmem", "smem", "f4"):
        for b in blocks:
            out.append(st.pallas(template=t, block=b))
    for t in ("shift", "unroll", "semi"):
        for b, m in itertools.product(sblocks, ("registers", "vmem")):
            if t == "semi" and m == "registers":
                continue
            out.append(st.pallas(template=t, block=b, mem_type=m))
    return out


def _temporal(b, ndim):
    """``b`` with the template and memory type its ``time_block>1`` kernel
    really runs, so candidates that build one program dedup to one."""
    if b.kind != "pallas" or b.time_block <= 1:
        return b
    from repro.kernels.stencil import codegen as _codegen
    t, m = _codegen.temporal_body(b.template, b.mem_type, ndim)
    return dataclasses.replace(b, template=t, mem_type=m)


def _normalize_space(space, ndim, interior, swap, steps, fuse_space,
                     time_block_space=(1,)):
    """Expand the search space into (backend, fuse_steps) candidates.

    With ``swap``, plain backend entries are expanded over ``fuse_space``
    and — for pallas backends — over ``time_block_space`` (the in-kernel
    temporal depth rides on the backend itself).  ``(backend, fuse)``
    tuple entries are taken verbatim.  Duplicates arising from overlap
    between a custom space and the expansion axes are removed before
    measuring, so tuning never times the same configuration twice.
    """
    base = space or default_space(ndim, interior)

    def _norm_fuse(f):
        # the engine's shared window normalization, so the dedup (and the
        # reported fuse_steps) sees the window size that actually runs —
        # e.g. requests ≥ steps collapse to one whole-loop window.  (The
        # overlapped-tiling clamp is mesh-dependent and applied by the
        # engine at measurement time.)
        return _tl.normalize_fuse(max(1, int(f)), steps)

    cands: List[Tuple[st.Backend, int]] = []
    for entry in base:
        if isinstance(entry, tuple):
            b, f = entry
            # without a swap pair only single applications are measured, so
            # a requested window size would be reported but never timed
            cands.append((_temporal(b, ndim),
                          _norm_fuse(f) if swap is not None else 1))
        elif swap is not None:
            backends = [entry]
            if entry.kind == "pallas":
                # expand over the search depths but keep the entry's own
                # (possibly user-pinned) depth in the set — an explicitly
                # requested configuration must be measured, not overwritten
                tbs = dict.fromkeys(
                    [int(getattr(entry, "time_block", 1) or 1)]
                    + [int(tb) for tb in time_block_space])
                backends = [_temporal(dataclasses.replace(entry,
                                                          time_block=tb),
                                      ndim) for tb in tbs]
            for b in backends:
                for f in fuse_space:
                    cands.append((b, _norm_fuse(f)))
        else:
            cands.append((entry, 1))
    # dedup while preserving order
    seen, out = set(), []
    for b, f in cands:
        key = (b.cache_key(), f)
        if key not in seen:
            seen.add(key)
            out.append((b, f))
    return out


def _measure(kernel: st.Kernel, grids: Dict[str, st.grid], backend,
             iters: int, mesh=None) -> float:
    """Median wall time of ``iters`` kernel applications (excludes the
    one-time codegen+compile warmup, like the paper's Kernel column)."""
    gs = {n: g.copy() for n, g in grids.items()}

    @st.target
    def tgt(*args):
        st.map(e=args[0].shape)(kernel)(*args)

    run = st.launch(backend=backend, mesh=mesh)
    args = tuple(gs.values())
    try:
        run(tgt)(*args)                      # warmup: codegen + compile
    except Exception as e:                   # noqa: BLE001 — scored inf
        return _failed(backend, e)
    times = []
    for _ in range(iters):
        res = run(tgt)(*args)
        times.append(res.profile.get("kernel", res.profile["total"]))
    return float(np.median(times))


def _measure_timeloop(kernel: st.Kernel, grids: Dict[str, st.grid],
                      backend, fuse: int, steps: int, swap, iters: int,
                      mesh=None) -> float:
    """Median wall time-to-solution of ``steps`` fused time steps."""
    gs = {n: g.copy() for n, g in grids.items()}

    def tgt(*args):
        return st.timeloop(steps, swap=swap, fuse_steps=fuse)(kernel)(*args)

    run = st.launch(backend=backend, mesh=mesh)
    args = tuple(gs.values())
    try:
        run(tgt)(*args)                      # warmup: codegen + compile
    except Exception as e:                   # noqa: BLE001 — scored inf
        return _failed(backend, e)
    times = []
    for _ in range(iters):
        times.append(run(tgt)(*args).value.seconds)
    return float(np.median(times))


def _space_key(space):
    if space is None:
        return None
    out = []
    for entry in space:
        if isinstance(entry, tuple):
            b, f = entry
            out.append((b.cache_key(), int(f)))
        else:
            out.append((entry.cache_key(), None))
    return tuple(out)


def shortlist_indices(predictions: Sequence[Optional[float]],
                      top_k: int) -> List[int]:
    """Candidate indices the two-stage search measures: the ``top_k``
    cheapest predicted (ties broken by original order — deterministic),
    plus every candidate the model cannot predict (``None``, e.g.
    distributed backends — pruning those would silently drop
    configurations the model knows nothing about).  Original order is
    preserved."""
    ranked = sorted((i for i, p in enumerate(predictions) if p is not None),
                    key=lambda i: (predictions[i], i))
    keep = set(ranked[:max(0, int(top_k))])
    keep.update(i for i, p in enumerate(predictions) if p is None)
    return sorted(keep)


def tune(kernel: st.Kernel, grids: Dict[str, st.grid], iters: int = 3,
         space: Optional[List] = None,
         verbose: bool = False,
         swap: Optional[Tuple[str, str]] = None,
         steps: int = 16,
         fuse_space: Sequence[int] = (1, 4, 16),
         time_block_space: Sequence[int] = (1, 2, 4),
         cache_dir: Optional[str] = None,
         top_k: Optional[int] = 3,
         cost_model: Optional[_cost.CostModel] = None,
         mesh=None) -> TuneResult:
    """Search the backend (and, with ``swap``, the fusion window) —
    two-stage: predict with the analytical cost model, measure a
    shortlist.

    ``space`` entries may be plain backends or ``(backend, fuse_steps)``
    pairs.  Without ``swap`` the tuner measures single kernel applications;
    with ``swap`` it measures ``steps`` fused time-loop steps per candidate
    and searches ``fuse_space`` window sizes for each backend, plus
    ``time_block_space`` in-kernel temporal depths for pallas backends
    (the winner's depth is carried on ``result.backend.time_block``).

    ``top_k`` — when the deduplicated space exceeds ``top_k`` candidates,
    rank all of them with the cost model (``cost_model`` if given, else a
    process-shared calibrated ``cost_model.default_model``) and measure
    only the ``top_k`` cheapest predicted (plus any the model cannot
    predict).  ``top_k=None`` forces the exhaustive search.  Passing an
    explicit ``cost_model`` computes predictions even when nothing is
    pruned — how the benchmarks obtain full predicted-vs-measured data.

    ``mesh`` — the device mesh distributed candidates in ``space`` run
    (and are *priced*) on; threaded into both the cost-model prediction
    and the measurement launches.  Mesh-tuned results stay in the
    in-process cache only (live device references are not persisted).

    ``cache_dir`` (or ``$REPRO_AUTOTUNE_CACHE``) enables the persistent
    on-disk cache: a miss in the in-process layer consults the disk entry
    for this (kernel fingerprint, shape bucket, configuration, top_k,
    calibration version) before predicting or measuring anything, and a
    fresh result is written back atomically.  Disk hits leave
    ``MEASURE_COUNT`` untouched; the first touch of a directory purges
    entries stranded by a ``SCHEMA_VERSION`` bump.
    """
    if top_k is not None and int(top_k) < 1:
        raise ValueError(f"top_k must be >= 1 or None (got {top_k})")
    g0 = next(iter(grids.values()))
    mesh_desc = (tuple(sorted(dict(mesh.shape).items()))
                 if mesh is not None else None)
    key = (kernel.name,
           tuple(sorted((n, g.shape, g.order, str(g.dtype))
                        for n, g in grids.items())),
           int(iters), _space_key(space),
           tuple(swap) if swap else None,
           int(steps) if swap else None,
           tuple(int(f) for f in fuse_space) if swap else None,
           tuple(int(t) for t in time_block_space) if swap else None,
           int(top_k) if top_k is not None else None,
           mesh_desc)
    if key in _CACHE:
        return _CACHE[key]
    cdir = cache_dir or cache_dir_from_env()
    digest = readable = None
    # the disk key carries no mesh descriptor; mesh-tuned results skip the
    # disk layer entirely (they hold live device references anyway)
    use_disk = cdir and mesh is None
    if use_disk:
        if cdir not in _PURGED:
            _PURGED.add(cdir)
            purge_stale(cdir)
        digest, readable = _disk_key(kernel, grids, iters, space, swap,
                                     steps, fuse_space, time_block_space,
                                     top_k)
        result = _disk_load(cdir, digest, readable)
        if result is not None:
            _CACHE[key] = result
            return result
    cands = _normalize_space(space, kernel.info.ndim, g0.shape, swap,
                             steps, fuse_space,
                             time_block_space if swap else (1,))

    # stage 1: rank by predicted cost (geometry + calibrated roofline,
    # no compilation) whenever pruning applies or a model was given
    preds: List[Optional[float]] = []
    if cost_model is not None or (top_k is not None
                                  and len(cands) > int(top_k)):
        cm = cost_model or _cost.default_model(cdir)
        for backend, fuse in cands:
            try:
                p = cm.predict(kernel, grids, backend, fuse, steps, swap,
                               mesh=mesh)
            except Exception:
                p = None
            preds.append(p)
            if verbose and p is not None:
                print(f"  predict {backend} fuse={fuse}: {p:.5f}s",
                      flush=True)
    measure_idx = list(range(len(cands)))
    pruned = 0
    if top_k is not None and len(cands) > int(top_k):
        measure_idx = shortlist_indices(preds, int(top_k))
        pruned = len(cands) - len(measure_idx)
        MEASURE_COUNT["pruned_candidates"] += pruned

    # stage 2: measure the shortlist
    trials = []
    for i in measure_idx:
        backend, fuse = cands[i]
        if swap is None:
            dt = _measure(kernel, grids, backend, iters, mesh=mesh)
        else:
            dt = _measure_timeloop(kernel, grids, backend, fuse, steps,
                                   swap, iters, mesh=mesh)
        MEASURE_COUNT["measured_candidates"] += 1
        trials.append((backend, fuse, dt))
        if verbose:
            print(f"  {backend} fuse={fuse}: {dt:.4f}s", flush=True)
    best = min(trials, key=lambda t: t[2])

    rank_error = None
    predicted = []
    if preds:
        predicted = [(cands[i][0], cands[i][1], preds[i])
                     for i in range(len(cands))]
        order = sorted((i for i, p in enumerate(preds) if p is not None),
                       key=lambda i: (preds[i], i))
        best_key = (best[0].cache_key(), best[1])
        for rank, i in enumerate(order):
            if (cands[i][0].cache_key(), cands[i][1]) == best_key:
                rank_error = rank
                break
    result = TuneResult(backend=best[0], seconds=best[2], trials=trials,
                        fuse_steps=best[1], predicted=predicted,
                        pruned_candidates=pruned,
                        measured_candidates=len(trials),
                        rank_error=rank_error,
                        top_k=int(top_k) if top_k is not None else None)
    _CACHE[key] = result
    if use_disk:
        _disk_store(cdir, digest, readable, result)
    return result
