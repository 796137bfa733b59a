"""Job kind ``propagate``: propagation jobs of ``steps`` leapfrog steps.

Mix parameters: ``steps``; ``fuse_steps`` (steps per window, ``null`` for
one window per job); ``source`` (add the configuration's wavelet through
the ``between`` hook before every window); ``start`` (optional field specs
of the grids a job starts from: each job is then a shot that starts those
grids afresh, drawn from the seed and the shot's number; without it every
job continues from the state the last one left).
"""
from __future__ import annotations

from typing import Dict

import cells


class Job(cells.Job):
    STATE = ("g", "args")

    def __init__(self, spec, seed: int, devices):
        super().__init__(spec, seed, devices)
        t = spec.traffic
        self.start = t.get("start")
        self.start_fields = (cells.field_program(spec, self.start,
                                                 self.sharding)
                             if self.start else None)
        self.source = bool(t.get("source", False))
        self.between = self.hook_fn() if self.source else None
        self.g = self.grids(self.make_fields())
        self.args = list(self.g.values()) + list(spec.scalars.values())
        self.shots = 0

    def propagate(self, steps: int) -> None:
        st, spec = self.st, self.spec
        st.launch(backend=self.backend, fuse_steps=spec.fuse)(
            lambda: st.timeloop(steps, swap=spec.swap,
                                between=self.between)(self.kernel)(
                *self.args))()

    def _job(self, keep: bool):
        import jax
        spec = self.spec
        shot, self.shots = self.shots, self.shots + 1
        inp = None
        if self.start:
            # a shot's start is made again from the seed by the check
            for g, a in self.start_fields(self.key, shot + 1).items():
                self.g[g].data = a
        elif keep:
            inp = cells.device_copy({g: self.g[g].data for g in spec.swap})
        if self.source:
            self.between(0, self.g)
        self.propagate(spec.steps)
        out = None
        if keep:
            out = {g: self.g[g].data for g in spec.swap}
            if not self.start:
                # the next job continues from (and may donate) these arrays;
                # a shot replaces them before it runs
                out = cells.device_copy(out)
        jax.block_until_ready([g.data for g in self.g.values()])
        return (shot, inp, out) if keep else None

    def work_points(self) -> int:
        """Interior point updates of one job."""
        return self.spec.points * self.spec.steps

    def check(self, dtype=None) -> Dict[str, float]:
        """Reference the kept job; with ``dtype`` the reference in that
        precision stands in for the program (the control)."""
        import jax.numpy as jnp
        from reference import stencil as ref
        spec = self.spec
        shot, inp, out = self.reservoir.kept
        arrays = dict(self.make_fields())
        if self.start:
            arrays.update(self.start_fields(self.key, shot + 1))
        else:
            arrays.update(inp)
        k = ref.Kernel(spec.config["kernel"], spec.order)
        src = None
        if self.source:
            src = dict(spec.config["wavelet"], index=spec.source_index())
        every = spec.fuse or spec.steps

        def run(dt):
            return ref.leapfrog(k, spec.swap, spec.steps, dtype=dt,
                                source=src, every=every)(
                arrays, spec.scalars)
        want = run(jnp.float32)
        got = out if dtype is None else run(dtype)
        return {"rel_err": ref.arrays_rel_err(got, want, spec.swap,
                                              spec.order)}
