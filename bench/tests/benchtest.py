"""Helpers for the benchmark's own tests: run ``bench/run.py`` in a child
process on the CPU, optionally with the system under test broken first."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def root_with(tmp, config: dict, workload: dict) -> str:
    """A checkout in ``tmp`` (the benchmark copied, the system under test
    linked) whose BENCHMARK.json also lists ``workload``."""
    import shutil
    shutil.copytree(BENCH, os.path.join(tmp, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "src"), os.path.join(tmp, "src"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append(config)
    spec["workloads"].append(workload)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return str(tmp)


def cells_of(root: str = ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return {w["name"]: w for w in json.load(f)["workloads"]}


def run_cell(workload: str, *, patch: str = "",
             root: str = ROOT, rehearse: bool = True, seed: int = 2**31 + 7,
             timeout: float = 600):
    """Run one cell through ``run.py``'s ``main`` in a fresh process with
    ``JAX_PLATFORMS=cpu`` after executing ``patch``; returns (exit code,
    last stdout line as JSON or None, stdout, stderr)."""
    run_py = os.path.join(root, os.path.basename(BENCH), "run.py")
    args = ["--workload", workload, "--seed", str(seed), "--seconds", "0",
            "--trace", "0"] + (["--rehearse"] if rehearse else [])
    code = textwrap.dedent(f"""
        import sys, importlib.util
        sys.path[:0] = [{os.path.join(root, 'src')!r},
                        {os.path.dirname(run_py)!r}]
        spec = importlib.util.spec_from_file_location("bench_run", {run_py!r})
        run = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(run)
    """) + textwrap.dedent(patch) + textwrap.dedent(f"""
        sys.exit(run.main({args!r}))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=root,
                       capture_output=True, text=True, timeout=timeout)
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    last = None
    if lines:
        try:
            last = json.loads(lines[-1])
        except json.JSONDecodeError:
            last = None
    return p.returncode, last, p.stdout, p.stderr
