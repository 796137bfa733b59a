"""Simulation-serving load benchmark: batched vs serial service of one
mixed-size request stream, plus cold-vs-warm persistent-autotune-cache
first-request latency.

Two measurements, both on the same run/machine so the guarded series are
machine-independent ratios and counters:

  * **serve_stream** — N star2d1r jobs with mixed interior shapes (all
    inside one (16, 32) pow2 bucket) and mixed step counts are served
    twice: *serially* (one unbatched fused engine per distinct request
    shape — the classic one-tenant-at-a-time path, engines reused across
    requests of the same shape) and *batched* (a ``SimServer`` packing
    waves of ``batch_cap`` scenarios into one compiled masked program).
    Reports requests/s for both, the batched-vs-serial speedup, and
    request-latency p50/p99 from the server's submit/done timestamps.
    Both paths include their compile cost — this is the cold-serve story,
    where sharing one program across the bucket is precisely the win.
  * **autotune_cache** — first-request wall time of a tuned server
    against a cold on-disk autotune cache (two-stage search: the cost
    model ranks every fuse candidate, only the ``tune_top_k`` cheapest
    are measured — ``pruned_candidates``/``pruning_factor`` report the
    saving) and against a warm one (a fresh process reading the previous
    entry).  ``warm.measured_candidates`` must be 0 and
    ``cold.measured_at_most_top_k`` must hold — the series CI asserts.

    PYTHONPATH=src python -m benchmarks.serve [--fast]
"""
from __future__ import annotations

import json
import os
import shutil
import tempfile
import time
from typing import Dict, List, Tuple

import numpy as np

from repro.core import autotune as _at
from repro.core import cost_model as _cm
from repro.core import dsl as st
from repro.core import suite
from repro.core import timeloop as _tl
from repro.serving.stencil_serve import SimServer

OUT_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_serve.json")

KERNEL = "star2d1r"
#: mixed request shapes, all bucketing to (16, 32)
SHAPES: Tuple[Tuple[int, int], ...] = (
    (12, 18), (14, 20), (16, 24), (10, 28), (16, 32), (9, 17))


def _make_stream(n: int, seed: int = 0):
    """n requests cycling through SHAPES with varied step counts."""
    k = suite.get_kernel(KERNEL)
    rng = np.random.default_rng(seed)
    stream = []
    for i in range(n):
        shape = SHAPES[i % len(SHAPES)]
        steps = int(rng.integers(4, 17))
        payload = {g: rng.standard_normal(shape).astype(np.float32)
                   for g in k.ir.grid_params}
        stream.append((shape, steps, payload))
    return stream


def _serve_serial(stream) -> float:
    """One unbatched fused xla engine per distinct request shape (reused
    across the stream), each request run back-to-back."""
    k = suite.get_kernel(KERNEL)
    swap = suite.swap_pair(KERNEL)
    order = k.info.order
    engines: Dict[Tuple[int, ...], _tl.TimeloopEngine] = {}
    t0 = time.perf_counter()
    for shape, steps, payload in stream:
        eng = engines.get(shape)
        if eng is None:
            halos = {g: (order,) * k.info.ndim for g in k.ir.grid_params}
            eng = _tl.TimeloopEngine(k.ir, halos, shape, st.xla(), swap=swap)
            engines[shape] = eng
        arrays = {}
        for g in k.ir.grid_params:
            full = np.zeros(tuple(s + 2 * order for s in shape), np.float32)
            full[tuple(slice(order, order + s) for s in shape)] = payload[g]
            arrays[g] = full
        eng.run(arrays, {}, steps, 8)
    return time.perf_counter() - t0


def _serve_batched(stream, batch_cap: int):
    """The same stream through a SimServer; returns (seconds, latencies)."""
    srv = SimServer(batch_cap=batch_cap, fuse_window=8)
    t0 = time.perf_counter()
    for shape, steps, payload in stream:
        srv.submit(KERNEL, shape, steps, payload)
    done = srv.run_until_drained()
    dt = time.perf_counter() - t0
    lat = np.array([r.done_at - r.submitted_at for r in done.values()])
    return dt, lat, srv.waves_run


def _bench_stream(n_requests: int, batch_cap: int) -> Dict:
    stream = _make_stream(n_requests)
    t_serial = _serve_serial(stream)
    t_batched, lat, waves = _serve_batched(stream, batch_cap)
    return {
        "kernel": KERNEL,
        "n_requests": n_requests,
        "batch_cap": batch_cap,
        "bucket": [16, 32],
        "shapes": [list(s) for s in SHAPES],
        "waves": waves,
        "serial_seconds": t_serial,
        "batched_seconds": t_batched,
        "serial_requests_per_s": n_requests / t_serial,
        "batched_requests_per_s": n_requests / t_batched,
        "batched_vs_serial_speedup": t_serial / t_batched,
        "p50_latency_s": float(np.percentile(lat, 50)),
        "p99_latency_s": float(np.percentile(lat, 99)),
    }


def _one_tuned_request(cache_dir: str) -> Tuple[float, int, int]:
    """Serve a single request on a tuned server as a fresh process would:
    cold in-process caches (tune results *and* cost-model calibration —
    the persisted roofline in ``cache_dir`` survives, like on disk).
    Returns (wall seconds, candidates measured, candidates pruned)."""
    _at.clear_cache()
    _at.reset_measure_count()
    _cm.reset_default_models()
    k = suite.get_kernel(KERNEL)
    rng = np.random.default_rng(7)
    shape = SHAPES[0]
    payload = {g: rng.standard_normal(shape).astype(np.float32)
               for g in k.ir.grid_params}
    srv = SimServer(batch_cap=4, autotune_cache=cache_dir)
    t0 = time.perf_counter()
    srv.submit(KERNEL, shape, 8, payload)
    srv.run_until_drained()
    dt = time.perf_counter() - t0
    return (dt, int(_at.MEASURE_COUNT["measured_candidates"]),
            int(_at.MEASURE_COUNT["pruned_candidates"]))


def _bench_autotune_cache() -> Dict:
    cdir = tempfile.mkdtemp(prefix="repro-autotune-bench-")
    try:
        cold_s, cold_n, cold_pruned = _one_tuned_request(cdir)
        warm_s, warm_n, _ = _one_tuned_request(cdir)
    finally:
        shutil.rmtree(cdir, ignore_errors=True)
    top_k = SimServer(batch_cap=1).tune_top_k
    space = cold_n + cold_pruned
    return {
        "cold": {"first_request_s": cold_s, "measured_candidates": cold_n,
                 "space_candidates": space, "top_k": top_k,
                 "pruned_candidates": cold_pruned,
                 "pruning_factor": space / cold_n if cold_n else 0.0,
                 "measured_at_most_top_k": bool(
                     top_k is None or cold_n <= top_k)},
        "warm": {"first_request_s": warm_s, "measured_candidates": warm_n},
        "warm_vs_cold_speedup": cold_s / warm_s if warm_s > 0 else 0.0,
    }


def run(fast: bool = False, verbose: bool = True) -> Dict[str, Dict]:
    results = {
        "serve_stream": _bench_stream(
            n_requests=12 if fast else 36,
            batch_cap=8),
        "autotune_cache": _bench_autotune_cache(),
    }
    with open(OUT_PATH, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
    if verbose:
        s = results["serve_stream"]
        print(f"serve_stream: {s['n_requests']} requests  "
              f"serial {s['serial_requests_per_s']:.1f} req/s  "
              f"batched {s['batched_requests_per_s']:.1f} req/s  "
              f"speedup {s['batched_vs_serial_speedup']:.2f}x  "
              f"p50 {s['p50_latency_s'] * 1e3:.0f}ms  "
              f"p99 {s['p99_latency_s'] * 1e3:.0f}ms", flush=True)
        a = results["autotune_cache"]
        print(f"autotune_cache: cold {a['cold']['first_request_s']:.2f}s "
              f"({a['cold']['measured_candidates']}/"
              f"{a['cold']['space_candidates']} measured, "
              f"{a['cold']['pruned_candidates']} pruned)  "
              f"warm {a['warm']['first_request_s']:.2f}s "
              f"({a['warm']['measured_candidates']} measured)", flush=True)
        print(f"wrote {OUT_PATH}")
    return results


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    return run(fast=args.fast)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
