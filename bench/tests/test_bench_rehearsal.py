"""Every cell of BENCHMARK.json runs through bench/run.py at its tiny
rehearsal extent on the CPU (interpreted Pallas), passes its correctness check and prints no device
metric; without a TPU the benchmark itself prints no result."""
import json
import os
import shutil

import pytest

import benchtest

CELLS = benchtest.cells_of()


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_cell_rehearsal_is_correct(workload):
    rc, last, out, err = benchtest.run_cell(workload)
    assert rc == 0, err[-3000:]
    assert last["rehearsal"] is True and last["correct"] is True, last
    assert "metrics" not in last and "device" not in last
    assert list(last)[-1] == "checks"
    for name, c in last["checks"].items():
        assert c["value"] <= c["limit"], (name, c)
        assert f"check {name} " in err


def test_no_tpu_no_result():
    rc, last, out, err = benchtest.run_cell("star3d4r-512.fused100",
                                            rehearse=False)
    assert rc != 0 and last is None
    assert "no TPU" in err


def test_bare_benchmark_files_refuse(tmp_path):
    """A directory with only BENCHMARK.json and the files under paths has
    no system under test: the run exits non-zero with no result."""
    shutil.copy(os.path.join(benchtest.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(benchtest.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    rc, last, out, err = benchtest.run_cell(
        "star3d4r-512.fused100", root=str(tmp_path), rehearse=False)
    assert rc != 0 and last is None


def test_new_cell_found_by_name(tmp_path):
    """A configuration, a traffic mix of a new job kind, a new field kind,
    a limits file and a metric added as new files, with new BENCHMARK.json
    entries, run without any existing file of the harness edited."""
    bench = tmp_path / "bench"
    root = benchtest.root_with(
        tmp_path,
        config={"name": "star3d2r-test", "source": "test",
                "file": "bench/configs/star3d2r-test.json",
                "reduced": [], "why": "test"},
        workload={"name": "star3d2r-test.fused7", "config": "star3d2r-test",
                  "traffic": "fused7", "chips": 1, "why": "test"})
    with open(bench / "configs" / "star3d4r-512.json") as f:
        cfg = json.load(f)
    cfg["name"] = "star3d2r-test"
    cfg["kernel"] = ("def star3d2r(u: st.grid, v: st.grid):\n"
                     "    v.at(0, 0, 0).set(0.5 * u.at(0, 0, 0)"
                     " + 0.25 * u.at(0, 0, -2) + 0.25 * u.at(-1, 0, 0))\n")
    cfg["order"] = 2
    cfg["fields"]["u"] = {"init": "ramp", "slope": 3.0}
    with open(bench / "configs" / "star3d2r-test.json", "w") as f:
        json.dump(cfg, f)
    with open(bench / "fields" / "ramp.py", "w") as f:
        f.write("import jax.numpy as jnp\n\n\n"
                "def make(p, shape, key, made):\n"
                "    x = jnp.linspace(-1.0, 1.0, shape[-1])\n"
                "    return p['slope'] * jnp.broadcast_to(x, shape)\n")
    # a new job kind: each job's steps run as two launches
    with open(bench / "jobs" / "propagate_split.py", "w") as f:
        f.write("import cells\n\n\n"
                "class Job(cells.load_module('jobs', 'propagate').Job):\n"
                "    def propagate(self, steps):\n"
                "        super().propagate(steps // 2)\n"
                "        super().propagate(steps - steps // 2)\n")
    with open(bench / "traffic" / "fused7.json", "w") as f:
        json.dump({"job": "propagate_split", "steps": 7, "fuse_steps": 3,
                   "source": False, "rehearsal": {"steps": 5}}, f)
    with open(bench / "limits" / "star3d2r-test.fused7.json", "w") as f:
        json.dump({"rel_err": {"limit": 1e-5}}, f)
    with open(bench / "metrics" / "jobs_done.py", "w") as f:
        f.write("def read(run):\n    return float(run.jobs)\n")
    with open(tmp_path / "BENCHMARK.json") as f:
        spec = json.load(f)
    spec["end_to_end"][0]["workloads"].append("star3d2r-test.fused7")
    spec["per_layer"].append({"name": "jobs_done", "unit": "jobs",
                              "better": "higher", "source": "host_clock",
                              "layer": "engine", "moves": "gpts_per_s",
                              "workloads": ["star3d2r-test.fused7"]})
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(spec, f)
    rc, last, out, err = benchtest.run_cell("star3d2r-test.fused7",
                                            root=root)
    assert rc == 0, err[-3000:]
    assert last["correct"] is True and last["attempted"] == 1, last
    # the new kinds were the ones that ran
    rc, last, out, err = benchtest.run_cell(
        "star3d2r-test.fused7", root=root, patch="""
        import cells
        spec = cells.Spec("star3d2r-test.fused7", rehearse=True)
        import jax
        job = cells.make_job(spec, 5, jax.devices())
        u = job.g["u"].data
        found = run.cell_metrics(spec.bench, "star3d2r-test.fused7", True)
        class R: jobs = 3
        print(__import__("json").dumps({
            "kind": type(job).__module__,
            "u_edge": float(u[4, 4, 2]),
            **{n["name"]: run.load_reader(n["name"])(R())
               for n in found if n["name"] == "jobs_done"}}))
        sys.exit(0)
        """)
    assert rc == 0, err[-3000:]
    assert last == {"kind": "bench_jobs_propagate_split", "u_edge": -3.0,
                    "jobs_done": 3.0}
