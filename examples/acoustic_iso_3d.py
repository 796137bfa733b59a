"""Acoustic-ISO seismic propagation (the paper's §6.2 production workload):
25-point star stencil, 8th order in space, 2nd in time, PML boundaries,
Ricker source — runnable single-device or domain-decomposed over a mesh.

    PYTHONPATH=src python examples/acoustic_iso_3d.py                # xla
    PYTHONPATH=src python examples/acoustic_iso_3d.py --template f4  # pallas
    PYTHONPATH=src python examples/acoustic_iso_3d.py --distributed  # 8 fake
                                                                     # devices
The distributed form re-execs itself with
XLA_FLAGS=--xla_force_host_platform_device_count=8 and decomposes the
domain (data, model) with ppermute halo exchange + interior/boundary
overlap (DESIGN.md §6).
"""
import argparse
import os
import subprocess
import sys
import time

import numpy as np


def run(args):
    import jax
    from repro.launch.mesh import make_mesh
    from repro.core import acoustic, dsl as st

    shape = tuple(args.shape)
    if args.distributed:
        mesh = make_mesh((4, 2), ("data", "model"))
        backend = st.distributed(grid_axes=("data", "model", None),
                                 overlap=True)
    else:
        mesh = None
        backend = (st.pallas(template=args.template)
                   if args.template else st.xla())

    t0 = time.perf_counter()
    p, prof = acoustic.run(shape=shape, iters=args.iters, backend=backend,
                           mesh=mesh, pml_width=args.pml,
                           fuse_steps=args.fuse)
    wall = time.perf_counter() - t0
    w = np.asarray(p.interior)
    pts = np.prod(shape) * args.iters
    print(f"grid {shape} × {args.iters} steps: {wall:.2f}s "
          f"({pts / wall / 1e6:.1f} Mpoints/s)")
    print(f"profile: {({k: round(v, 3) for k, v in prof.items()})}")
    print(f"wavefield energy {float((w ** 2).sum()):.4e}, "
          f"max |p| {float(np.abs(w).max()):.4e}")
    assert np.isfinite(w).all()
    # PML sanity: boundary energy should be tiny vs interior energy
    c = args.pml
    inner = w[c:-c, c:-c, c:-c]
    shell = float((w ** 2).sum() - (inner ** 2).sum())
    print(f"PML shell energy fraction: {shell / float((w**2).sum()):.3e}")
    print("OK")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", type=int, nargs=3, default=[48, 48, 48])
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--pml", type=int, default=8)
    ap.add_argument("--template", default=None,
                    choices=[None, "gmem", "smem", "f4", "shift", "unroll",
                             "semi"])
    ap.add_argument("--fuse", type=int, default=None, metavar="K",
                    help="fused time stepping: run K steps per compiled "
                         "program (source injected at window boundaries)")
    ap.add_argument("--distributed", action="store_true")
    ap.add_argument("--_child", action="store_true")
    args = ap.parse_args()

    if args.distributed and not args._child:
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env.setdefault("PYTHONPATH", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "src"))
        sys.exit(subprocess.call(
            [sys.executable, os.path.abspath(__file__), "--_child",
             "--distributed", "--iters", str(args.iters), "--pml",
             str(args.pml), "--shape", *map(str, args.shape)]
            + (["--fuse", str(args.fuse)] if args.fuse else []), env=env))
    run(args)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
