"""Readings for the limits of a cell's correctness check, on the chip.

    python bench/calibrate.py --workload <name>

In one process, for each of ``SEEDS`` seeds (drawn from ``BASE``): the
cell's fields and program built from the seed, one warm-up job, ``JOBS``
timed jobs, then the numbers the check compares for the kept job, and for
the first ``CONTROLS`` seeds the same numbers with the plain reference in
bfloat16 standing in for the program (the control).  Prints one JSON line
per seed and a summary: the largest program reading (the lower end of a
limit) and the smallest control reading (its upper end).  The limits
themselves are set by hand in ``limits/<cell>.json`` from these readings.
"""
import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SEEDS, CONTROLS, JOBS, BASE = 12, 3, 2, 1_900_000_000


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]
    import run as bench_run
    jax = bench_run._configure_jax(cache=True)
    import jax.numpy as jnp
    import numpy as np
    import cells

    spec = cells.Spec(args.workload, ROOT)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    seeds = [int(s) for s in np.random.SeedSequence(BASE)
             .generate_state(SEEDS, np.uint32)]
    lower, upper = {}, {}
    for i, seed in enumerate(seeds):
        job = cells.make_job(spec, seed, devices[:spec.chips])
        job.run_job(keep=True)
        job.begin_window()
        for _ in range(JOBS):
            job.run_job()
        job.free()
        rec = {"seed": seed, "program": job.check()}
        if i < CONTROLS:
            rec["control"] = job.check(dtype=jnp.bfloat16)
        print(json.dumps(rec), flush=True)
        for k, v in rec["program"].items():
            lower[k] = max(lower.get(k, 0.0), v)
        for k, v in rec.get("control", {}).items():
            upper[k] = min(upper.get(k, float("inf")), v)
        del job
    print("summary " + json.dumps({"workload": args.workload,
                                   "seeds": len(seeds), "lower": lower,
                                   "upper": upper}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
