"""Run one cell of the stencil benchmark and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs from the root of a checkout.  The cell, its configuration, traffic
mix, correctness limits and metric readers are found by name from
``BENCHMARK.json`` (see ``cells.py``).  One process: fields made on the
device from ``--seed``, one warm-up job (every program of the cell compiled
or read from the persistent compile cache in ``<checkout>/.jax_cache``),
then whole jobs until ``--seconds`` have passed, then the kept job compared
with the plain reference.  ``--trace 1`` records a profiler trace of the
window and reports the per-layer metrics instead of the end-to-end ones.

The last line of standard output is the result:
``{"correct", "attempted", "failed", "metrics", "device"[, "breakdown"],
"checks"}``; the last lines of standard error give each compared number
beside its limit.  Without a TPU, or with fewer chips than the cell asks
for, it prints no result and exits 2.  ``--rehearse`` runs the cell at the
configuration's tiny rehearsal extent on whatever JAX finds (the CPU, with
interpreted Pallas) and prints a ``rehearsal`` line with no metrics.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def _configure_jax(cache: bool):
    """The persistent compile cache at a fixed path inside the checkout,
    every program cached however short its compile."""
    import jax
    if not cache:
        return jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


class CompileCount:
    """Backend compilations (persistent-cache reads included) since it was
    made."""

    def __init__(self):
        from jax import monitoring
        self.n = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def load_reader(name: str):
    """``metrics/<name>.py``'s ``read``; names may hold dots."""
    import cells
    return cells.load_module("metrics", name).read


def cell_metrics(bench: dict, workload: str, trace: bool):
    """(name, unit) of the metrics this cell reports in this kind of run."""
    e2e = bench["end_to_end"]
    mine = [m for m in e2e if workload in m.get("workloads", [workload])]
    if not trace:
        return mine
    moved = {m["name"] for m in mine}
    return [m for m in bench["per_layer"]
            if workload in m.get("workloads", [workload])
            and m["moves"] in moved]


class Run:
    """What a metric reader reads."""

    def __init__(self, spec, peaks):
        self.spec, self.peaks = spec, peaks
        self.setup_s = self.window_s = None
        self.jobs = self.work_points = self.bytes_per_point = 0
        self.trace = None


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny extent on any platform; no metrics")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench: the system under test is not under {src}",
              file=sys.stderr)
        return 2
    sys.path[:0] = [src, BENCH]
    jax = _configure_jax(cache=not args.rehearse)
    import cells
    from reference.stencil import Kernel as RefKernel

    spec = cells.Spec(args.workload, ROOT, rehearse=args.rehearse)
    devices = jax.devices()
    dev = devices[0]
    peaks = cells.load_json(os.path.join(BENCH, "peaks.json"))
    if not args.rehearse:
        if dev.platform != "tpu":
            print(f"bench: no TPU (JAX's devices are {dev.platform}); "
                  "refusing to fall back", file=sys.stderr)
            return 2
        if len(devices) < spec.chips:
            print(f"bench: {args.workload} needs {spec.chips} chips, found "
                  f"{len(devices)}", file=sys.stderr)
            return 2
        if dev.device_kind not in peaks:
            print(f"bench: no peaks for device kind {dev.device_kind!r} in "
                  "peaks.json", file=sys.stderr)
            return 2
    used = devices[:spec.chips]
    compiles = CompileCount()

    job = cells.make_job(spec, args.seed, used)
    ref_kernel = RefKernel(spec.config["kernel"], spec.order)
    run = Run(spec, peaks.get(dev.device_kind))
    run.work_points = job.work_points()
    run.bytes_per_point = ref_kernel.compulsory_bytes_per_point(spec.shape)
    job.run_job(keep=True)          # warm-up: every program of the cell
    job.begin_window()
    # what set-up left behind is kept out of the window's collections
    gc.collect()
    gc.freeze()
    run.setup_s = time.perf_counter() - T_START
    c0 = compiles.n

    trace = bool(args.trace) and not args.rehearse
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # host spans without Python frames
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    t0 = time.perf_counter()
    ends = []
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            with jax.profiler.TraceAnnotation("bench.job"):
                job.run_job()
            ends.append(time.perf_counter() - t0)
            if ends[-1] >= args.seconds:
                break
    run.window_s = ends[-1]
    run.jobs = job.jobs
    if trace:
        jax.profiler.stop_trace()
    in_window = compiles.n - c0
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    plan = job.plan_counts()
    job.free()

    numbers = job.check()
    checks = job.check_limits(numbers)
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())
    failed = 0 if correct else 1

    diag = {"jobs": run.jobs, "window_s": run.window_s,
            "job_s": [b - a for a, b in zip([0.0] + ends, ends)],
            "setup_s": run.setup_s, "compiles_in_window": in_window,
            "compulsory_bytes_per_point": run.bytes_per_point,
            "flops_per_point": ref_kernel.flops_per_point(),
            "ops_per_byte": ref_kernel.flops_per_point()
            / run.bytes_per_point, **plan}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(used), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": run.jobs, "failed": failed}
    if args.rehearse:
        out = {"rehearsal": True, **out}
    else:
        if trace:
            import devtrace
            run.trace = devtrace.load(TRACE_DIR,
                                      devices=[d.id for d in used])
            device["busy_s"] = (sum(run.trace.busy_ns(d)
                                    for d in run.trace.devices)
                                / len(run.trace.devices) / 1e9)
            device["window_s"] = run.trace.window_ns / 1e9
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
        metrics = {}
        for m in cell_metrics(spec.bench, args.workload, trace):
            v = load_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["metrics"] = metrics
        out["device"] = device
        if trace:
            out["breakdown"] = {"device_ops": run.trace.top_ops(),
                                "idle_gaps": run.trace.idle_by_host()}
    out["checks"] = checks
    print("diagnostics " + json.dumps(diag), flush=True)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
