"""Paper §6.2 / Tables 6–8: per-template time-to-solution phase breakdown
for the acoustic-ISO 25-point stencil.

The paper reports frontend / codegen / compile / kernel / time-to-solution
per (template × block × mem-type) on H100/A100/MI210.  Our runtime is CPU
(TPU is a compile target), so kernel numbers are CPU-XLA / interpret-Pallas
wall times: they demonstrate the framework's low overhead (frontend+codegen
≪ compile ≪ kernel), not TPU performance — the TPU performance story is
the roofline analysis (benchmarks/roofline.py).

``xla`` rows play the role of the paper's hand-written reference; Pallas
rows run in interpret mode and are expected to be slow in wall-time but
identical in numerics (accuracy_suite.py).
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from repro.core import acoustic, dsl as st

CONFIGS = [
    # (template, block, mem_type)
    ("gmem", (8, 8, 128), None),
    ("gmem", (8, 16, 128), None),
    ("smem", (8, 8, 128), None),
    ("f4", (8, 8, 128), None),
    ("shift", (16, 8, 128), "registers"),
    ("shift", (16, 8, 128), "vmem"),
    ("unroll", (16, 8, 128), "registers"),
    ("semi", (16, 8, 128), "vmem"),
]


def run(shape=(32, 32, 128), iters=2, include_pallas=True,
        verbose=True) -> List[Dict]:
    rows = []

    def one(label, backend):
        # fresh kernel cache per variant so codegen/compile are measured
        acoustic.acoustic_iso_kernel._cache.clear()
        t0 = time.perf_counter()
        _, prof = acoustic.run(shape=shape, iters=iters, backend=backend)
        total = time.perf_counter() - t0
        row = {"template": label[0], "block": label[1], "mem": label[2] or "-",
               "frontend": acoustic.acoustic_iso_kernel.frontend_time,
               "codegen": prof.get("codegen", 0.0),
               "comp": prof.get("comp", 0.0),
               "kernel": prof.get("kernel", 0.0),
               "time_to_solution": total}
        rows.append(row)
        if verbose:
            print(f"{row['template']:7s} {str(row['block']):15s} "
                  f"{row['mem']:9s} fe={row['frontend']:.4f} "
                  f"cg={row['codegen']:.4f} comp={row['comp']:.3f} "
                  f"kern={row['kernel']:.3f} tts={row['time_to_solution']:.3f}",
                  flush=True)

    one(("xla", "-", None), st.xla())
    if include_pallas:
        for template, block, mem in CONFIGS:
            one((template, block, mem),
                st.pallas(template=template, block=block, mem_type=mem))
    return rows


def main():
    rows = run()
    fe = max(r["frontend"] for r in rows)
    cg = max(r["codegen"] for r in rows)
    print(f"\nframework overhead: frontend ≤ {fe * 1e3:.1f} ms, "
          f"codegen ≤ {cg * 1e3:.1f} ms per variant "
          f"(paper: ~4 ms / ~1-6 ms)")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
