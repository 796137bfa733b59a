"""Analytic HBM-traffic model for the Pallas stencil templates (§Perf
stencil iteration 3).

On-TPU the generated kernel's HBM traffic is set by its BlockSpec geometry
(every input block fetched HBM→VMEM once per grid step, output written
once) — this is statically known, so the roofline can be computed without
hardware.  Per template, per point of a 3-D stencil with halo h and block
(Bx, By, Bz):

  gmem/f4  — each tap's neighbor-block ref re-fetches blocks: unique
             fetched volume per output block for star stencils is the
             center block + 6 axis slabs → (Bx+2h)(By)(Bz) + ... but the
             Pallas pipeline fetches whole neighbor BLOCKS: worst-case
             distinct fetched bytes = (#deltas) · block.
  smem     — same fetched blocks, assembled once into a VMEM scratch.
  shift/unroll — 2.5D streaming: x is the whole local extent, so only
             y/z halos re-fetch: per-point factor ≈ ((By+2h)(Bz+2h))/(ByBz)
             for the streamed grid; coefficient grids stream exactly once.
  semi     — like shift, plus the rolling partial-sum buffer stays in VMEM.

Reported: modeled B/pt, VMEM working set (must fit ~128 MB), step time at
819 GB/s for the 1024³/256-chip local domain (64×64×1024), and roofline
fraction vs the 20 B/pt floor (4 reads + 1 write × f32).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

from repro.core import acoustic

HBM_BW = 819e9
VMEM_BYTES = 128 * 2 ** 20
LOCAL = (64, 64, 1024)            # 1024³ over the (16,16) mesh, axes 0,1
FLOOR_BPP = 20.0                  # 4 array reads + 1 write, f32


def _deltas_star_3d() -> int:
    return 7                       # center + 6 axis neighbors


def model(template: str, block: Tuple[int, int, int], h: int = 4,
          n_center_grids: int = 3) -> Dict:
    """B/pt + VMEM working set for the acoustic-ISO star stencil
    (1 halo'd grid p1 + n_center_grids center-only grids + 1 output)."""
    bx, by, bz = block
    pts = bx * by * bz
    if template in ("gmem", "smem", "f4"):
        # p1 fetches its block + 6 axis-neighbor blocks (star shape-
        # directed: no corners); center grids + output fetch 1 block each
        fetched = _deltas_star_3d() * pts + n_center_grids * pts + pts
        vmem = (_deltas_star_3d() + n_center_grids + 1) * pts * 4
        if template == "smem":
            vmem += (bx + 2 * h) * (by + 2 * h) * (bz + 2 * h) * 4
    elif template in ("shift", "unroll"):
        # stream x through the local extent: p1 re-fetches only y/z halos
        eff = (by + 2 * h) * (bz + 2 * h) / (by * bz)
        fetched = pts * (eff + n_center_grids + 1)
        # window of 2h+1 y/z planes + one in-flight block per grid
        vmem = (2 * h + 1) * (by + 2 * h) * (bz + 2 * h) * 4 \
            + (n_center_grids + 1) * by * bz * 4 * 2
    elif template == "semi":
        eff = (by + 2 * h) * (bz + 2 * h) / (by * bz)
        fetched = pts * (eff + n_center_grids + 1)
        vmem = (2 * h + 1) * (by + 2 * h) * (bz + 2 * h) * 4 * 2 \
            + (n_center_grids + 1) * by * bz * 4 * 2
    else:
        raise ValueError(template)
    bpp = 4.0 * fetched / pts
    local_pts = LOCAL[0] * LOCAL[1] * LOCAL[2]
    step_s = bpp * local_pts / HBM_BW
    return {"template": template, "block": block,
            "bytes_per_point": round(bpp, 1),
            "vmem_bytes": int(vmem),
            "vmem_ok": vmem <= VMEM_BYTES,
            "step_ms": round(step_s * 1e3, 3),
            "roofline_frac": round(FLOOR_BPP / bpp, 3)}


CANDIDATES = [
    ("gmem", (8, 8, 128)), ("gmem", (16, 16, 256)),
    ("smem", (8, 8, 128)), ("f4", (8, 8, 256)),
    ("shift", (64, 8, 128)), ("shift", (64, 16, 256)),
    ("shift", (64, 32, 512)),
    ("unroll", (64, 16, 256)),
    ("semi", (64, 16, 256)),
]


def run(verbose: bool = True) -> List[Dict]:
    k = acoustic.acoustic_iso_kernel
    assert k.info.shape == "star" and k.info.order == 4
    rows = []
    for template, block in CANDIDATES:
        r = model(template, block)
        rows.append(r)
        if verbose:
            print(f"{r['template']:7s} {str(r['block']):15s} "
                  f"{r['bytes_per_point']:7.1f} B/pt  "
                  f"VMEM {r['vmem_bytes'] / 2**20:6.1f} MB "
                  f"{'ok ' if r['vmem_ok'] else 'OVER'} "
                  f"step {r['step_ms']:7.3f} ms  "
                  f"roofline {r['roofline_frac'] * 100:5.1f}%", flush=True)
    best = max((r for r in rows if r["vmem_ok"]),
               key=lambda r: r["roofline_frac"])
    if verbose:
        print(f"\nbest: {best['template']} {best['block']} → "
              f"{best['bytes_per_point']} B/pt = "
              f"{best['roofline_frac'] * 100:.1f}% of the HBM roofline "
              f"({best['step_ms']} ms/step on the 64×64×1024 local domain)")
    return rows


def main():
    return run()


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
