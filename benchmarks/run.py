"""Benchmark aggregator: one module per paper table + roofline.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--section NAME]

Emits a section per table; each section also prints ``name,value`` CSV
lines for machine consumption.  Every section runs in a child process of
its own (``--section NAME``) and the parent never touches JAX: a process
that holds the chip would make the next one that needs it fail or hang.
The dry-run/roofline section reads the baseline artifact JSON if present
(produced by repro.launch.dryrun — a separate process because it needs
512 placeholder devices).
"""
from __future__ import annotations

import argparse
import subprocess
import sys
import time


def _hdr(title):
    print(f"\n{'=' * 72}\n== {title}\n{'=' * 72}", flush=True)


def _accuracy(fast):
    _hdr("Accuracy suite (paper Table 4 / §6.1)")
    from benchmarks import accuracy_suite
    kernels = ("star2d2r", "star3d4r", "box2d1r", "box3d2r", "j2d5pt",
               "j3d27pt") if fast else None
    acc = accuracy_suite.run(kernels=kernels)
    print(f"csv,accuracy_versions,{len(acc)}")
    print(f"csv,accuracy_worst_max_err,{max(r['max_err'] for r in acc):.3e}")
    print(f"csv,accuracy_worst_rmsd,{max(r['rmsd'] for r in acc):.3e}")


def _templates(fast):
    _hdr("Template timing (paper Tables 6-8 / §6.2)")
    from benchmarks import template_timing
    tt = template_timing.run(
        shape=(16, 16, 128) if fast else (32, 32, 128),
        iters=1 if fast else 2,
        include_pallas=not fast)
    for r in tt:
        print(f"csv,tts_{r['template']}_{r['mem']},"
              f"{r['time_to_solution']:.3f}")


def _timeloop(fast):
    _hdr("Fused time loop (steps/s, fused vs per-step; BENCH_timeloop.json)")
    from benchmarks import timeloop as bench_timeloop
    tl = bench_timeloop.run(fast=fast)
    for name, r in tl.items():
        if name == "predicted_vs_measured":
            # two-stage autotuner quality: nested per-kernel rows
            for key, row in sorted(r.items()):
                print(f"csv,timeloop_pvm_{key}_measured,"
                      f"{row['measured_candidates_two_stage']}")
                print(f"csv,timeloop_pvm_{key}_pruned,"
                      f"{row['pruned_candidates']}")
                print(f"csv,timeloop_pvm_{key}_rank_of_best,"
                      f"{row['rank_of_measured_best']}")
        elif "fused_steps_per_s" in r:
            print(f"csv,timeloop_{name}_steps_per_s,"
                  f"{r['fused_steps_per_s']:.1f}")
            print(f"csv,timeloop_{name}_speedup,{r['speedup']:.2f}")
        else:   # pallas time_block sweep: nested rows
            for key, row in sorted(r.items()):
                print(f"csv,timeloop_{name}_{key}_steps_per_s,"
                      f"{row['fused_steps_per_s']:.1f}")
                print(f"csv,timeloop_{name}_{key}_hbm_bytes_per_step,"
                      f"{row['hbm_bytes_per_step']:.0f}")


def _productivity(fast):
    _hdr("Productivity (paper Table 11 / §6.3)")
    from benchmarks import productivity
    pr = productivity.run()
    print(f"csv,productivity_min_leverage,"
          f"{min(r['leverage'] for r in pr)}")


def _distributed(fast):
    _hdr("Distributed stencil (fused sharded timeloop; BENCH_distributed.json)")
    from benchmarks import distributed_stencil
    ds = distributed_stencil.run(fast=fast)
    fw = ds["fused_vs_per_window"]
    print(f"csv,dist_fused_vs_per_window_speedup,{fw['speedup']:.2f}")
    print(f"csv,dist_fused_steps_per_s,{fw['fused_steps_per_s']:.1f}")
    for mode in ("strong", "weak"):
        for n, row in sorted(ds["scaling"][mode].items(),
                             key=lambda kv: int(kv[0])):
            print(f"csv,dist_{mode}_{n}dev_steps_per_s,"
                  f"{row['steps_per_s']:.1f}")
    print(f"csv,dist_collective_model_match,"
          f"{int(all(r['match'] for r in ds['collective_model'].values()))}")
    pvm = ds["predicted_vs_measured_mesh"]
    print(f"csv,dist_pvm_measured,{pvm['measured_candidates']}")
    print(f"csv,dist_pvm_pruned,{pvm['pruned_candidates']}")


def _stencil_roofline(fast):
    _hdr("Stencil-template roofline (BlockSpec traffic model, §Perf)")
    from benchmarks import stencil_roofline
    sr = stencil_roofline.run()
    best = max((r for r in sr if r["vmem_ok"]),
               key=lambda r: r["roofline_frac"])
    print(f"csv,stencil_best_bpp,{best['bytes_per_point']}")
    print(f"csv,stencil_best_roofline_frac,{best['roofline_frac']}")


def _roofline(fast):
    _hdr("Roofline (from dry-run artifacts; see EXPERIMENTS.md §Roofline)")
    from benchmarks import roofline
    rl = roofline.main()
    if rl:
        fracs = [r["roofline_frac"] for r in rl if r["roofline_frac"]]
        if fracs:
            print(f"csv,roofline_cells,{len(rl)}")
            print(f"csv,roofline_best_frac,{max(fracs):.3f}")
            print(f"csv,roofline_worst_frac,{min(fracs):.3f}")


SECTIONS = {
    "accuracy": _accuracy,
    "templates": _templates,
    "timeloop": _timeloop,
    "productivity": _productivity,
    "distributed": _distributed,
    "stencil_roofline": _stencil_roofline,
    "roofline": _roofline,
}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced sweeps (CI mode)")
    ap.add_argument("--section", choices=sorted(SECTIONS),
                    help="run this one section in this process")
    args = ap.parse_args(argv)

    if args.section:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        SECTIONS[args.section](args.fast)
        return 0

    t0 = time.time()
    failed = []
    for name in SECTIONS:
        cmd = [sys.executable, "-m", "benchmarks.run", "--section", name]
        if subprocess.call(cmd + (["--fast"] if args.fast else [])):
            failed.append(name)
    print(f"\nall benchmarks done in {time.time() - t0:.0f}s")
    if failed:
        print(f"failed sections: {', '.join(failed)}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
