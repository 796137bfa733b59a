"""Roofline analysis from the dry-run's compiled artifacts (deliverable g).

Three terms per (arch × shape), single-pod mesh, TPU v5e constants:

    compute    = HLO_FLOPs_per_device / peak_FLOP/s          (197e12 bf16)
    memory     = HLO_bytes_per_device / HBM_bw               (819e9 B/s)
    collective = collective_bytes_per_device / link_bw       (50e9 B/s ICI)

HLO_FLOPs / bytes / collective-bytes come from the trip-count-aware HLO
walk (launch/hlo_analysis.py) over ``compiled.as_text()`` — XLA's own
cost_analysis counts while bodies once and reports no collectives.

MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE) per device; the ratio
MODEL_FLOPS/HLO_FLOPs shows how much of the compiled compute is useful
(remat recompute, MoE capacity slack, replicated small-dim compute all
push it down).

``stencil_table()`` (also run by ``main``) is the stencil-suite analog:
per-kernel modeled ``hbm_bytes_per_step`` for the temporally blocked
pallas plan, read through ``core/cost_model.CostModel.step_bytes`` — the
*same* accounting the two-stage autotuner ranks candidates with — so
this report and the tuner's predictions can never drift apart.  The
``modeled_vs_roofline`` column compares each plan against the streaming
floor (one read per input grid + one write per output per point): >1
means temporal blocking beats per-step streaming; <1 means halo overlap
overhead still dominates at that geometry.
"""
from __future__ import annotations

import json
import os
from typing import Dict, List, Optional

PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # B/s / chip
LINK_BW = 50e9               # B/s / ICI link

DEFAULT_RECORDS = os.path.join(os.path.dirname(__file__), "artifacts",
                               "dryrun_baseline.json")


def model_flops(rec: Dict) -> Optional[float]:
    """6·N(_active)·D per device for the cell's step kind."""
    n = rec.get("active_params")
    if not n:
        return None
    B, S = rec["global_batch"], rec["seq_len"]
    ndev = rec["n_devices"]
    if rec["kind"] == "train":
        tokens = B * S
        mult = 6.0                        # fwd 2 + bwd 4
    elif rec["kind"] == "prefill":
        tokens = B * S
        mult = 2.0
    else:                                 # decode: one token per sequence
        tokens = B * 1
        mult = 2.0
    return mult * n * tokens / ndev


def terms(rec: Dict) -> Optional[Dict]:
    hw = rec.get("hlo_walk")
    if not hw:
        return None
    t_c = hw["total_flops"] / PEAK_FLOPS
    t_m = hw["hbm_bytes"] / HBM_BW
    t_x = hw["total_collective_bytes"] / LINK_BW
    dom = max((t_c, "compute"), (t_m, "memory"), (t_x, "collective"))
    mf = model_flops(rec)
    bound = max(t_c, t_m, t_x)
    return {
        "compute_s": t_c, "memory_s": t_m, "collective_s": t_x,
        "dominant": dom[1],
        "step_lower_bound_s": bound,
        "model_flops_per_dev": mf,
        "useful_ratio": (mf / hw["total_flops"]) if mf and
        hw["total_flops"] else None,
        # roofline fraction: useful model FLOPs over the time the dominant
        # term pins the step to, vs peak
        "roofline_frac": (mf / bound / PEAK_FLOPS) if mf and bound else None,
    }


def load(path: str = DEFAULT_RECORDS) -> List[Dict]:
    """Load the merged baseline, or merge per-arch artifact JSONs."""
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    art = os.path.dirname(path)
    records = []
    for fn in sorted(os.listdir(art)) if os.path.isdir(art) else []:
        if fn.startswith("dryrun_") and fn.endswith(".json"):
            with open(os.path.join(art, fn)) as f:
                records.extend(json.load(f))
    return records


def table(records: List[Dict], mesh: str = "single",
          verbose: bool = True) -> List[Dict]:
    rows = []
    for rec in records:
        if rec.get("mesh") != mesh or rec.get("status") != "ok":
            continue
        t = terms(rec)
        if t is None:
            continue
        row = {"arch": rec["arch"], "shape": rec["shape"],
               "tag": rec.get("tag", ""), **t}
        rows.append(row)
        if verbose:
            rf = f"{t['roofline_frac'] * 100:5.1f}%" \
                if t["roofline_frac"] else "   - "
            ur = f"{t['useful_ratio'] * 100:5.1f}%" \
                if t["useful_ratio"] else "   - "
            print(f"{rec['arch']:18s} {rec['shape']:12s} "
                  f"C={t['compute_s']:8.3f}s M={t['memory_s']:8.3f}s "
                  f"X={t['collective_s']:8.3f}s → {t['dominant']:10s} "
                  f"useful={ur} roofline={rf}", flush=True)
    return rows


def stencil_table(kernels=("star2d1r", "star2d4r", "star3d1r", "star3d4r"),
                  time_blocks=(1, 2, 4), verbose: bool = True) -> List[Dict]:
    """Modeled HBM traffic for the stencil suite at the suite's default
    benchmark shapes, via the cost model's ``step_bytes`` (identical to
    what the autotuner ranks with).  Deterministic — no timing, no
    compilation on the pallas path."""
    from repro.core import cost_model, dsl as st, suite

    cm = cost_model.CostModel(calibrate=False)
    rows = []
    for name in kernels:
        k = suite.get_kernel(name)
        swap = suite.swap_pair(name)
        grids = suite.make_grids(name)
        g0 = next(iter(grids.values()))
        interior = tuple(g0.shape)
        halos = {n: g.halo for n, g in grids.items()}
        itemsize = 4  # f32 suite grids
        points = 1.0
        for s in interior:
            points *= s
        # streaming floor: every read grid streamed once, every output
        # written once, zero halo overlap
        n_in = len(k.ir.input_grids())
        n_out = len(k.ir.output_grids())
        floor_bpp = itemsize * (n_in + n_out)
        for tb in time_blocks:
            backend = st.pallas(template="gmem", time_block=tb)
            sb = cm.step_bytes(k, halos, interior, backend, swap, g0.dtype)
            per_step = sb[0] if sb else float("inf")
            feasible = sb is not None and per_step != float("inf")
            bpp = per_step / points if feasible else None
            row = {
                "kernel": name, "shape": list(interior),
                "template": "gmem", "time_block": tb,
                "feasible": feasible,
                "hbm_bytes_per_step": per_step if feasible else None,
                "bytes_per_point": bpp,
                "streaming_floor_bytes_per_point": floor_bpp,
                "modeled_vs_roofline": (floor_bpp / bpp) if bpp else None,
                "hbm_step_s_at_819GBps": (per_step / HBM_BW
                                          if feasible else None),
            }
            rows.append(row)
            if verbose:
                if feasible:
                    print(f"{name:10s} k={tb}  "
                          f"hbm/step {per_step:12.0f} B  "
                          f"{bpp:6.1f} B/pt (floor {floor_bpp} B/pt, "
                          f"{row['modeled_vs_roofline']:.2f}x roofline)  "
                          f"t_mem {row['hbm_step_s_at_819GBps'] * 1e6:.1f}us",
                          flush=True)
                else:
                    print(f"{name:10s} k={tb}  infeasible at {interior}",
                          flush=True)
    return rows


def main():
    print("— stencil suite: modeled HBM traffic (cost-model accounting) —")
    stencil_table()
    print()
    records = load()
    if not records:
        print(f"no dry-run records under {os.path.dirname(DEFAULT_RECORDS)};"
              f" run\n  PYTHONPATH=src python -m repro.launch.dryrun --out "
              f"{DEFAULT_RECORDS}")
        return []
    rows = table(records)
    if rows:
        worst = min((r for r in rows if r["roofline_frac"]),
                    key=lambda r: r["roofline_frac"])
        print(f"\nworst roofline fraction: {worst['arch']}/{worst['shape']} "
              f"= {worst['roofline_frac'] * 100:.1f}%")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
