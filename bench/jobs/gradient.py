"""Job kind ``gradient``: misfit gradients of an inversion, one per job.

Each job is one jitted ``value_and_grad`` through
``st.differentiable_timeloop`` of the misfit between the final wavefield of
the current model and that of the true model, then a steepest-descent step
of ``model_step`` × the background's squared velocity on the model.  Mix
parameters: ``steps``, ``fuse_steps`` (the window, and the cadence of the
wavelet added by the hook), ``model_step``.  The configuration's
``inversion`` names the parameter grid, the grids compared, the true
model's field spec and the reference's checkpoint segment.
"""
from __future__ import annotations

from typing import Dict

import cells


class Job(cells.Job):
    STATE = ("grad", "model", "base", "observed")

    def __init__(self, spec, seed: int, devices):
        import jax
        import jax.numpy as jnp
        super().__init__(spec, seed, devices)
        st, inv = self.st, spec.config["inversion"]
        self.param, self.compare = inv["param"], tuple(inv["compare"])
        self.true = self.param + ".true"
        self.step_size = float(spec.traffic["model_step"])
        fields = self.make_fields({self.true: inv["true"]})
        self.model = fields[self.param]
        src, idx = spec.config["wavelet"], spec.source_index()
        from reference.stencil import ricker
        w0 = src.get("amp", 1.0) * ricker(0, src["f0"], src["t0"])
        between = self.hook_fn()
        scal = list(spec.scalars.values())

        # the observed wavefield: the plain forward run on the true model
        obs = self.grids(dict(fields, **{self.param: fields[self.true]}))
        between(0, obs)
        st.launch(backend=self.backend, fuse_steps=spec.fuse)(
            lambda: st.timeloop(spec.steps, swap=spec.swap,
                                between=between)(self.kernel)(
                *obs.values(), *scal))()
        observed = {g: obs[g].data for g in self.compare}

        fn = st.differentiable_timeloop(
            self.kernel, *self.grids(fields).values(), *scal,
            steps=spec.steps, swap=spec.swap, fuse_steps=spec.fuse,
            between=between, backend=self.backend)
        base = {g: fields[g] for g in spec.config["fields"]}
        h, src_grid = spec.order, src["grid"]
        inner = tuple(slice(h, h + n) for n in spec.shape)

        def misfit(model, base, observed):
            a = dict(base)
            a[self.param] = model
            a[src_grid] = a[src_grid].at[idx].add(w0)
            out = fn(a, fn.scalars)
            return 0.5 * sum(jnp.sum((out[g] - observed[g])[inner] ** 2)
                             for g in self.compare)

        self.base, self.observed = base, observed
        self.grad = jax.jit(jax.value_and_grad(misfit))
        self.bg = spec.config["fields"][self.param]["vp"] ** 2

    def _job(self, keep: bool):
        import jax
        import jax.numpy as jnp
        model = self.model
        value, g = self.grad(model, self.base, self.observed)
        jax.block_until_ready(g)
        # steepest descent with a step of a fixed share of the background
        scale = self.step_size * self.bg / jnp.maximum(
            jnp.max(jnp.abs(g)), 1e-30)
        self.model = model - scale * g
        return (model, value, g) if keep else None

    def work_points(self) -> int:
        return 0

    def check(self, dtype=None) -> Dict[str, float]:
        import jax.numpy as jnp
        from reference import stencil as ref
        spec = self.spec
        model, value, g = self.reservoir.kept
        fields = self.make_fields({self.true:
                                   spec.config["inversion"]["true"]})
        arrays = {g_: fields[g_] for g_ in spec.config["fields"]}
        k = ref.Kernel(spec.config["kernel"], spec.order)
        src = dict(spec.config["wavelet"], index=spec.source_index())
        h = spec.order
        inner = tuple(slice(h, h + n) for n in spec.shape)

        def grad(dt):
            true = dict(arrays, **{self.param: fields[self.true]})
            observed = ref.leapfrog(k, spec.swap, spec.steps, dtype=dt,
                                    source=src, every=spec.fuse)(
                true, spec.scalars)
            f = ref.misfit_gradient(
                k, spec.swap, spec.steps, param=self.param,
                compare=self.compare, source=src, every=spec.fuse,
                segment=spec.segment, dtype=dt)
            return f(model[inner], arrays, spec.scalars, observed)

        want_v, want_g = grad(jnp.float32)
        if dtype is None:
            got_v, got_g = value, g[inner]
        else:
            got_v, got_g = grad(dtype)
        return {"misfit_rel_err": abs(float(got_v) - float(want_v))
                / max(abs(float(want_v)), 1e-30),
                "grad_rel_err": ref.rel_err(got_g, want_g)}
