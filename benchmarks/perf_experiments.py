"""§Perf hillclimb driver: hypothesis → change → re-lower → re-analyse.

Each experiment is one dry-run cell with an override set; results append to
``benchmarks/artifacts/perf_experiments.json``.  EXPERIMENTS.md §Perf
narrates the hypotheses and verdicts; this file is the executable record.

Run (needs the 512-device env, so it self-launches):
    PYTHONPATH=src python -m benchmarks.perf_experiments [--only PREFIX]
"""
import os
import subprocess
import sys

ART = os.path.join(os.path.dirname(os.path.abspath(__file__)), "artifacts")
_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")

# (name, kind, args) — kind: 'lm' → run_cell, 'stencil' → run_stencil_cell
EXPERIMENTS = [
    # -- pair 1: mixtral-8x7b train_4k (worst useful-ratio big cell) ------
    ("mixtral_train_mb4", "lm",
     dict(arch="mixtral-8x7b", shape="train_4k", multi_pod=False,
          overrides={"n_microbatches": 4}, tag="-mb4")),
    ("mixtral_train_mb2", "lm",
     dict(arch="mixtral-8x7b", shape="train_4k", multi_pod=False,
          overrides={"n_microbatches": 2}, tag="-mb2")),
    ("mixtral_train_dots", "lm",
     dict(arch="mixtral-8x7b", shape="train_4k", multi_pod=False,
          overrides={"remat_policy": "dots"}, tag="-dots")),
    ("mixtral_train_mb4_dots", "lm",
     dict(arch="mixtral-8x7b", shape="train_4k", multi_pod=False,
          overrides={"n_microbatches": 4, "remat_policy": "dots"},
          tag="-mb4-dots")),
    ("mixtral_train_group8k", "lm",
     dict(arch="mixtral-8x7b", shape="train_4k", multi_pod=False,
          overrides={"n_microbatches": 4}, tag="-mb4-g8k",
          moe_group=8192)),

    # -- pair 2: granite-8b decode_32k (most collective-bound) ------------
    ("granite_decode_kvrep", "lm",
     dict(arch="granite-8b", shape="decode_32k", multi_pod=False,
          overrides={}, tag="-kvrep", kv_seq_shard=False)),

    # -- pair 3: recurrentgemma train_4k (paper-technique representative) -
    ("rgemma_train_mb4", "lm",
     dict(arch="recurrentgemma-9b", shape="train_4k", multi_pod=False,
          overrides={"n_microbatches": 4}, tag="-mb4")),
    ("rgemma_train_mb4_dots", "lm",
     dict(arch="recurrentgemma-9b", shape="train_4k", multi_pod=False,
          overrides={"n_microbatches": 4, "remat_policy": "dots"},
          tag="-mb4-dots")),

    # -- the paper's own workload: overlapped tiling -----------------------
    ("acoustic_ts2", "stencil",
     dict(multi_pod=False, time_steps=2, tag="-ts2")),
    ("acoustic_ts4", "stencil",
     dict(multi_pod=False, time_steps=4, tag="-ts4")),
    ("acoustic_ts2_multi", "stencil",
     dict(multi_pod=True, time_steps=2, tag="-ts2")),

    # -- pair 2, iteration 2: seq-mode-aware decode attention landed in
    #    layers._sdpa (kv_mode) + cache DUS constraints -------------------
    ("granite_decode_seqflash", "lm",
     dict(arch="granite-8b", shape="decode_32k", multi_pod=False,
          overrides={}, tag="-seqflash")),
    ("mixtral_decode_seqflash", "lm",
     dict(arch="mixtral-8x7b", shape="decode_32k", multi_pod=False,
          overrides={}, tag="-seqflash")),

    # -- pair 2, iteration 3: grouped-query decode attention (no expanded
    #    KV materialization) --------------------------------------------
    ("granite_decode_grouped", "lm",
     dict(arch="granite-8b", shape="decode_32k", multi_pod=False,
          overrides={}, tag="-grouped")),
    ("mixtral_decode_grouped", "lm",
     dict(arch="mixtral-8x7b", shape="decode_32k", multi_pod=False,
          overrides={}, tag="-grouped")),
    ("mixtral_long500k_grouped", "lm",
     dict(arch="mixtral-8x7b", shape="long_500k", multi_pod=False,
          overrides={}, tag="-grouped")),

    # -- pair 1, iteration 2: grad accumulator pinned to param sharding
    #    (reduce-scatter instead of replicated all-reduce) ----------------
    ("mixtral_train_mb4_gshard", "lm",
     dict(arch="mixtral-8x7b", shape="train_4k", multi_pod=False,
          overrides={"n_microbatches": 4}, tag="-mb4-gshard")),
    ("mixtral_train_mb8_gshard", "lm",
     dict(arch="mixtral-8x7b", shape="train_4k", multi_pod=False,
          overrides={"n_microbatches": 8}, tag="-mb8-gshard")),
    ("rgemma_train_mb4_gshard", "lm",
     dict(arch="recurrentgemma-9b", shape="train_4k", multi_pod=False,
          overrides={"n_microbatches": 4}, tag="-mb4-gshard")),

    # -- pair 1, iteration 3: bf16 x-path norms keep the TP backward
    #    all-reduce in bf16 (f32 convert no longer hoisted before it) ----
    ("mixtral_train_mb4_bf16ar", "lm",
     dict(arch="mixtral-8x7b", shape="train_4k", multi_pod=False,
          overrides={"n_microbatches": 4}, tag="-mb4-bf16ar")),
    ("rgemma_train_mb4_bf16ar", "lm",
     dict(arch="recurrentgemma-9b", shape="train_4k", multi_pod=False,
          overrides={"n_microbatches": 4}, tag="-mb4-bf16ar")),
]

_CHILD = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import json, sys
import dataclasses
from repro.launch import dryrun
from repro import sharding
spec = json.loads(sys.argv[1])
kind = spec.pop("kind")
name = spec.pop("name")
if kind == "stencil":
    rec = dryrun.run_stencil_cell(spec["multi_pod"],
                                  time_steps=spec.get("time_steps", 1),
                                  overlap=spec.get("overlap", True),
                                  tag=spec.get("tag", ""), save_hlo=True)
else:
    if not spec.pop("kv_seq_shard", True):
        # experiment: replicate KV-cache seq dim instead of model-sharding
        orig = sharding._kv_cache_axes
        def no_seq(cfg, mesh, lead):
            return lead + ("batch", None, "kv_heads", "head_dim")
        sharding._kv_cache_axes = no_seq
    mg = spec.pop("moe_group", None)
    overrides = spec.pop("overrides", {})
    if mg:
        from repro import configs
        cfg = configs.get(spec["arch"])
        overrides["moe"] = dataclasses.replace(cfg.moe, group_size=mg)
    rec = dryrun.run_cell(spec["arch"], spec["shape"], spec["multi_pod"],
                          save_hlo=True, overrides=overrides,
                          tag=spec.get("tag", ""))
rec["experiment"] = name
print("RESULT " + json.dumps(rec))
"""


def run_experiment(name, kind, args):
    import json
    spec = dict(args, kind=kind, name=name)
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC
    env.pop("XLA_FLAGS", None)
    # the dry-run compiles against 512 placeholder host devices and must
    # never claim an accelerator; the parent stays off JAX altogether
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", _CHILD, json.dumps(spec)],
                       capture_output=True, text=True, env=env, timeout=3000)
    for line in r.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[7:])
    raise RuntimeError(f"{name} failed:\n{r.stdout[-2000:]}\n"
                       f"{r.stderr[-2000:]}")


def main(argv=None):
    import argparse
    import json
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args(argv)
    out_path = os.path.join(ART, "perf_experiments.json")
    results = []
    if os.path.exists(out_path):
        results = json.load(open(out_path))
    done = {r.get("experiment") for r in results}
    for name, kind, spec in EXPERIMENTS:
        if args.only and not name.startswith(args.only):
            continue
        if name in done:
            print(f"[cached ] {name}")
            continue
        try:
            rec = run_experiment(name, kind, spec)
        except Exception as e:
            print(f"[FAILED ] {name}: {e}")
            continue
        results.append(rec)
        hw = rec.get("hlo_walk") or {}
        mem = (rec.get("memory") or {}).get("per_device_total_bytes", 0)
        print(f"[ok     ] {name:28s} mem={mem / 2**30:6.1f}GB "
              f"flops={hw.get('total_flops', 0):.3e} "
              f"hbm={hw.get('hbm_bytes', 0):.3e} "
              f"coll={hw.get('total_collective_bytes', 0):.3e}", flush=True)
        with open(out_path, "w") as f:
            json.dump(results, f, indent=1)
    print(f"wrote {out_path}")


if __name__ == "__main__":
    main()
