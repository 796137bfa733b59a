"""Job kind ``sharded``: ``propagate``'s jobs on grids split over the chips.

The configuration's ``mesh`` (``shape``, ``axes``) is built over the
cell's chips and every field is made on it, sharded as the backend's
``grid_axes`` split the domain; each job's launch passes that mesh.  Mix
parameters are ``propagate``'s, less ``source`` and ``start``, plus
``initial``: field specs of grids drawn once from the seed in place of
the configuration's, so that jobs that continue from the state the last
one left carry a wavefield in motion.  A rehearsal on fewer devices than
the mesh asks for runs the same program on a mesh of one device.

The check runs the plain reference in shards of its own
(``reference/sharded.py``) on the same sharded inputs.
"""
from __future__ import annotations

import math
from typing import Dict

import cells

Base = cells.load_module("jobs", "propagate").Job


class Job(Base):
    def place(self, devices):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh
        m = self.spec.config["mesh"]
        shape = tuple(m["shape"])
        if self.spec.rehearse and len(devices) < math.prod(shape):
            shape = (1,) * len(shape)
        n = math.prod(shape)
        if len(devices) < n:
            raise ValueError(f"the mesh {shape} needs {n} devices, the cell "
                             f"has {len(devices)}")
        self.mesh = make_mesh(shape, m["axes"], devices=devices[:n])
        return NamedSharding(self.mesh,
                             P(*self.spec.config["backend"]["grid_axes"]))

    def make_fields(self, extra=None):
        return super().make_fields(dict(self.spec.traffic.get("initial", {}),
                                        **(extra or {})))

    def propagate(self, steps: int) -> None:
        st, spec = self.st, self.spec
        st.launch(backend=self.backend, mesh=self.mesh,
                  fuse_steps=spec.fuse)(
            lambda: st.timeloop(steps, swap=spec.swap)(self.kernel)(
                *self.args))()

    def check(self, dtype=None) -> Dict[str, float]:
        """Reference the kept job on the mesh; with ``dtype`` the reference
        in that precision stands in for the program (the control)."""
        import jax.numpy as jnp
        from reference import sharded, stencil as ref
        spec = self.spec
        _, inp, out = self.reservoir.kept
        arrays = dict(self.make_fields())
        arrays.update(inp)
        k = ref.Kernel(spec.config["kernel"], spec.order)
        axis = spec.config["mesh"]["axes"][0]

        def run(dt):
            return sharded.leapfrog(k, spec.swap, spec.steps, self.mesh, axis,
                                    dtype=dt)(arrays, spec.scalars)
        want = run(jnp.float32)
        got = out if dtype is None else run(dtype)
        return {"rel_err": sharded.rel_errs(got, want, spec.swap,
                                            spec.order)}
