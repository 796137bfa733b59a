"""One cell of the benchmark: a configuration driven by a traffic mix.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration in the file that entry names (sizes, fields, backend and
the kernel's DSL source), its traffic mix in ``traffic/<mix>.json`` and the
limits of its correctness check in ``limits/<cell>.json``.  The mix's
``job`` names a job kind, ``jobs/<kind>.py``, whose ``Job`` class drives the
cell; every field's ``init`` names a field kind, ``fields/<kind>.py``, whose
``make`` draws it from the seed; the configuration's ``backend.kind`` names
the system's backend constructor (``st.pallas``, ``st.xla``, ...), called
with the rest of that entry.  So a new mix of a known kind is a new data
file, and a new kind is a new file beside the others.

A job kind subclasses ``Job`` below: it builds its state from the seed,
runs one job per ``_job`` call, and once the window has closed compares the
job the reservoir kept with the plain reference (``check``).
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import sys
from typing import Dict, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def by_name(entries, name: str, what: str):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` of the benchmark, loaded once by its path;
    names may hold dots."""
    key = f"bench_{kind}_{name}".replace(".", "_").replace("-", "_")
    if key not in sys.modules:
        path = os.path.join(BENCH, kind, name + ".py")
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key]


def seed_state(seed: int) -> np.random.SeedSequence:
    """Any whole number (negative or above 64 bits too) as a seed sequence."""
    return np.random.SeedSequence(int(seed) % (1 << 64))


def seed_key(seed: int):
    import jax
    return jax.random.key(int(seed_state(seed).generate_state(1)[0]))


class Spec:
    """The cell's entries and files, resolved from ``BENCHMARK.json``."""

    def __init__(self, workload: str, root: str = ROOT,
                 rehearse: bool = False):
        self.bench = load_json(os.path.join(root, "BENCHMARK.json"))
        self.workload = by_name(self.bench["workloads"], workload, "workload")
        cfg_entry = by_name(self.bench["configs"], self.workload["config"],
                            "config")
        self.config = load_json(os.path.join(root, cfg_entry["file"]))
        here = os.path.join(root, self.bench["paths"][0])
        self.traffic = load_json(os.path.join(
            here, "traffic", self.workload["traffic"] + ".json"))
        self.limits = load_json(os.path.join(here, "limits",
                                             workload + ".json"))
        self.chips = int(self.workload["chips"])
        self.rehearse = rehearse
        small = self.config["rehearsal"] if rehearse else {}
        self.shape = tuple(small.get("shape", self.config["shape"]))
        self.steps = int((self.traffic.get("rehearsal", {}) if rehearse
                          else {}).get("steps", self.traffic["steps"]))
        self.fuse = self.traffic.get("fuse_steps")
        #: steps per checkpointed segment of the reference gradient
        self.segment = small.get("segment", self.config.get(
            "inversion", {}).get("segment"))
        self.order = int(self.config["order"])
        self.swap = tuple(self.config["swap"])
        self.scalars = {k: np.float32(v)
                        for k, v in self.config.get("scalars", {}).items()}

    @property
    def points(self) -> int:
        return int(np.prod(self.shape))

    def source_index(self):
        """Index of the wavelet's cell in the halo-padded grid arrays."""
        pos = self.config["wavelet"]["position"]
        return tuple(self.order + min(int(f * n), n - 1)
                     for f, n in zip(pos, self.shape))


# -- fields: made on the device from the seed, in one jitted call ---------
def field_program(spec: Spec, specs: Dict[str, dict], sharding):
    """Jitted ``make(key, n) -> {grid: halo-padded array}`` placed by
    ``sharding``: each field of ``specs`` drawn by its kind's
    ``make(params, shape, key, made)`` from ``key`` folded with ``n``, in
    order, so that a field may derive from the ones before it."""
    import jax
    import jax.numpy as jnp
    makers = {g: load_module("fields", f["init"]).make
              for g, f in specs.items()}
    h, shape = spec.order, spec.shape

    def make(key, n):
        keys = jax.random.split(jax.random.fold_in(key, n), len(specs))
        made = {}
        for k, (g, f) in zip(keys, specs.items()):
            made[g] = makers[g](f, shape, k, made)
        return {g: jnp.pad(a, h) for g, a in made.items()}

    return jax.jit(make, out_shardings=sharding)


def device_copy(tree):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(jnp.copy, tree)


class Reservoir:
    """Keeps one job of the window, each equally likely, drawn from the
    seed: job ``j`` replaces the kept one with probability ``1/(j+1)``."""

    def __init__(self, seed: int):
        self.rng = np.random.default_rng(seed_state(seed).spawn(1)[0])
        self.seen = 0
        self.kept = None

    def take_next(self) -> bool:
        self.seen += 1
        return self.rng.random() < 1.0 / self.seen


# -- the program under test -----------------------------------------------
def program_kernel(spec: Spec):
    """The configuration's kernel source compiled by the system's DSL."""
    from repro.core import dsl as st
    src = spec.config["kernel"]
    ns = {"st": st}
    exec(compile(src, f"<config:{spec.config['name']}>", "exec",  # noqa: S102
                 dont_inherit=True), ns)
    fn = [v for k, v in ns.items() if k != "st" and callable(v)][0]
    fn.__stencil_source__ = src
    return st.kernel(fn)


def program_backend(spec: Spec):
    """``st.<kind>(**rest)`` of the configuration's ``backend`` entry."""
    from repro.core import dsl as st
    be = dict(spec.config["backend"])
    kind = be.pop("kind")
    return getattr(st, kind)(**{k: tuple(v) if isinstance(v, list) else v
                                for k, v in be.items()})


class Job:
    """Shared set-up of the job kinds.  A kind sets ``STATE`` (the
    attributes that hold the program's device state) and defines
    ``_job(keep)``, ``work_points()`` and ``check(dtype=None)``."""

    STATE: tuple = ()

    def __init__(self, spec: Spec, seed: int, devices):
        from repro.core import dsl as st
        self.spec, self.seed, self.st = spec, seed, st
        self.key = seed_key(seed)
        self.sharding = self.place(devices)
        self.kernel = program_kernel(spec)
        self.backend = program_backend(spec)
        self.reservoir = Reservoir(seed)
        self.jobs = 0

    def place(self, devices):
        """Where the fields live: on one chip.  A kind whose cells shard
        their fields over several chips overrides this."""
        import jax
        if self.spec.chips != 1:
            raise ValueError(f"job kind {self.spec.traffic['job']!r} runs "
                             f"on one chip; the cell asks for "
                             f"{self.spec.chips}")
        return jax.sharding.SingleDeviceSharding(devices[0])

    def make_fields(self, extra: Optional[Dict[str, dict]] = None):
        """The configuration's fields (and ``extra`` ones after them),
        halo-padded, drawn from the seed."""
        specs = dict(self.spec.config["fields"], **(extra or {}))
        return field_program(self.spec, specs, self.sharding)(self.key, 0)

    def grids(self, fields) -> Dict[str, object]:
        st = self.st
        out = {}
        for g in self.spec.config["fields"]:
            out[g] = st.grid(dtype=st.f32, shape=self.spec.shape,
                             order=self.spec.order, data=fields[g])
        return out

    def hook_fn(self):
        """``between(t, grids)``: the Ricker sample added at the source."""
        src = self.spec.config["wavelet"]
        idx = self.spec.source_index()
        from reference.stencil import ricker

        def between(t, grids):
            g = grids[src["grid"]]
            g.data = g.data.at[idx].add(src.get("amp", 1.0)
                                        * ricker(t, src["f0"], src["t0"]))
        return between

    def run_job(self, keep: Optional[bool] = None) -> None:
        keep = self.reservoir.take_next() if keep is None else keep
        sample = self._job(keep)
        self.jobs += 1
        if keep:
            self.reservoir.kept = sample

    def begin_window(self) -> None:
        """Forget the warm-up: count and sample only the window's jobs."""
        self.reservoir = Reservoir(self.seed)
        self.jobs = 0

    def free(self) -> None:
        """Drop the program's device state before the reference runs."""
        for k in self.STATE:
            setattr(self, k, None)
        self.kernel._cache.clear()
        gc.collect()

    def plan_counts(self) -> Dict[str, float]:
        """What the system's Pallas plan models it fetches (its own counts,
        printed as diagnostics; the roofline does not use them)."""
        out = {}
        for key, eng in self.kernel._cache.items():
            plan = getattr(eng, "_plan", None)
            if isinstance(key, tuple) and plan is not None:
                out["plan_hbm_bytes_per_step"] = plan.hbm_bytes_per_step()
                out["plan_layout_bytes_per_window"] = \
                    plan.layout_bytes_per_window()
        if out:
            from repro.kernels.stencil import codegen
            out["pad_count"] = dict(codegen.PAD_COUNT)
            out["traffic_count"] = dict(codegen.TRAFFIC_COUNT)
        return out

    def check_limits(self, numbers: Dict[str, float]) -> Dict[str, dict]:
        return {k: {"value": v, "limit": self.spec.limits[k]["limit"]}
                for k, v in numbers.items()}


def make_job(spec: Spec, seed: int, devices) -> Job:
    """The ``Job`` of ``jobs/<kind>.py`` for the mix's ``job`` kind."""
    return load_module("jobs", spec.traffic["job"]).Job(spec, seed, devices)
