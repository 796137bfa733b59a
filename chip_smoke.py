"""Chip smoke test: the main path of the stencil engine on one TPU.

    python chip_smoke.py                  # one TPU chip
    python chip_smoke.py --chips 4        # the sharded paths on 4 chips
    python chip_smoke.py --cpu-rehearsal  # same phases, tiny, interpreted

Every phase drives the user entry points (``st.launch`` + ``st.timeloop``,
``st.differentiable_timeloop``, ``SimServer``), checks its outputs against
the XLA backend on the same device with the tolerance stated next to the
phase, and prints one ``phase`` line (wall time, backend compile time,
peak device memory).  A failing phase raises, so the script exits
non-zero.  Only a run on a TPU prints the last line,
``{"ok": true, "device": {...}}``; without a TPU (and without
``--cpu-rehearsal``) the script exits non-zero before any phase.

One chip:
  1. device   — the default backend is a TPU;
  2. star3d4r — the paper's 25-point kernel at 512³ f32, 20 fused steps on
     Pallas ``gmem`` (``time_block`` 1 and 4; at 4 every 3-D template runs
     the plane-by-plane 'vmem' shift body, named in its ``*_body`` field)
     and ``shift``, each equal to ``st.xla()`` within ``REL_TOL``;
  3. acoustic — acoustic-ISO at 256³ with PML and a Ricker source injected
     by the ``between`` hook, Pallas ``gmem`` against XLA, finite field,
     and the absorbing PML shell holding a smaller share of the energy
     than of the volume once the wavefront has reached it;
  4. gradient — an FWI misfit gradient through
     ``st.differentiable_timeloop`` on a 256² model (320 steps): finite
     and non-zero, its primal bit-equal to the plain (donating) forward
     run, and equal to the XLA gradient within ``GRAD_TOL``;
  5. serving  — ``SimServer`` answers requests of mixed shapes, each equal
     to a serial ``st.timeloop`` of the same request.

``--chips 4`` runs only the sharded fused timeloop of star3d4r on a
4-device mesh and one distributed-adjoint gradient, each against the same
computation on one device of the host.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

#: star3d4r / acoustic: max |pallas − xla| over max |xla| after the run
REL_TOL = 1e-5
#: FWI gradient: max |g_pallas − g_xla| over max |g_xla|
GRAD_TOL = 1e-4
#: serving: per-cell tolerance against the serial run (as the tests use)
SERVE_RTOL, SERVE_ATOL = 1e-5, 1e-6


class _CompileClock:
    """Backend compile seconds (a persistent-cache hit counts its read
    time only) and cache hits, from JAX's monitoring events."""

    def __init__(self, monitoring):
        self.seconds, self.hits = 0.0, 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)


def _rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


class Smoke:
    def __init__(self, rehearsal: bool):
        import jax
        from jax import monitoring
        self.jax = jax
        self.rehearsal = rehearsal
        self.clock = _CompileClock(monitoring)

    def phase(self, name, fn):
        """Run one phase; its exceptions propagate (the run then fails)."""
        c0 = self.clock.seconds
        t0 = time.perf_counter()
        info = fn() or {}
        dev = self.jax.devices()[0]
        stats = dev.memory_stats() or {}
        rec = {"phase": name, "wall_s": time.perf_counter() - t0,
               "compile_s": self.clock.seconds - c0,
               "peak_bytes_in_use": stats.get("peak_bytes_in_use")}
        rec.update(info)
        print("phase " + json.dumps(rec), flush=True)

    # -- fields made on the device from a seed -----------------------------
    def field(self, shape, order, seed, scale=1.0, batch=None):
        import jax.numpy as jnp
        from repro.core import dsl as st
        full = ((batch,) if batch else ()) + tuple(shape)
        x = scale * self.jax.random.normal(self.jax.random.key(seed), full,
                                           jnp.float32)
        pad = ((0, 0),) * bool(batch) + ((order, order),) * len(shape)
        return st.grid(dtype=st.f32, shape=shape, order=order, batch=batch,
                       data=jnp.pad(x, pad))

    # -- phase 2: the headline kernel --------------------------------------
    def star3d4r(self):
        from repro.core import dsl as st, suite
        from repro.kernels.stencil.codegen import temporal_body
        k = suite.get_kernel("star3d4r")
        n = (32, 32, 128) if self.rehearsal else (512, 512, 512)
        steps, order = 20, k.info.order

        def run(backend):
            u = self.field(n, order, seed=1)
            v = self.field(n, order, seed=2)
            res = st.launch(backend=backend)(
                lambda: st.timeloop(steps, swap=("v", "u"))(k)(u, v))()
            return {"u": u.interior, "v": v.interior}, res.value.seconds

        want, t_xla = run(st.xla())
        out = {"shape": n, "steps": steps, "xla_s": t_xla}
        for template, tb in (("gmem", 1), ("gmem", 4), ("shift", 1)):
            be = st.pallas(template=template, time_block=tb)
            assert be.interpret == self.rehearsal, be
            got, secs = run(be)
            err = max(_rel_err(got[g], want[g]) for g in want)
            tag = f"{template}_k{tb}"
            out[f"{tag}_s"], out[f"{tag}_rel_err"] = secs, err
            if tb > 1:
                out[f"{tag}_body"] = "/".join(
                    temporal_body(template, be.mem_type, 3))
            assert err <= REL_TOL, (tag, err)
            del got
        return out

    # -- phase 3: acoustic-ISO with PML and a source -----------------------
    def acoustic(self):
        from repro.core import acoustic, dsl as st
        n = (32, 32, 32) if self.rehearsal else (256, 256, 256)
        pml = 6 if self.rehearsal else 16
        # the front (vp·dt = 0.45 cells per step from the center) enters
        # the PML well before the last step
        iters = 40 if self.rehearsal else 300
        fuse = 10

        def run(backend):
            p, prof = acoustic.run(shape=n, iters=iters, backend=backend,
                                   pml_width=pml, fuse_steps=fuse)
            return np.asarray(p.interior), prof

        want, _ = run(st.xla())
        got, prof = run(st.pallas(template="gmem"))
        err = _rel_err(got, want)
        assert err <= REL_TOL, ("acoustic", err)
        assert np.isfinite(got).all()
        inner = got[pml:-pml, pml:-pml, pml:-pml]
        total = float((got.astype(np.float64) ** 2).sum())
        shell = (total - float((inner.astype(np.float64) ** 2).sum())) / total
        volume = 1.0 - inner.size / got.size
        assert shell < volume, ("PML shell energy share", shell, volume)
        return {"shape": n, "iters": iters, "fuse_steps": fuse,
                "rel_err": err, "pml_shell_energy_share": shell,
                "pml_shell_volume_share": volume,
                "kernel_s": prof.get("kernel")}

    # -- phase 4: FWI gradient through the adjoint -------------------------
    def gradient(self):
        import jax.numpy as jnp
        from repro.core import dsl as st
        n = 32 if self.rehearsal else 256
        prob = _fwi_problem(n)

        def grads(backend):
            fn = prob.forward(backend)
            obs = fn(prob.arrays(prob.vp2_true))
            arrays = prob.arrays(prob.vp2_background)
            primal, pull = self.jax.vjp(lambda a: fn(a), arrays)
            cot = {g: primal[g] - obs[g] for g in ("p0", "p1")}
            cot.update({g: jnp.zeros_like(primal[g])
                        for g in primal if g not in cot})
            (g_arrays,) = pull(cot)
            return fn, arrays, primal, np.asarray(g_arrays["vp2"])

        fn, arrays, primal, g_pal = grads(st.pallas(template="gmem"))
        assert np.isfinite(g_pal).all() and np.abs(g_pal).max() > 0
        # the plain forward run: the same windows on a non-differentiable
        # engine, which donates its buffers on the chip
        fwd = prob.timeloop(st.pallas(template="gmem"), arrays)
        for g in fwd:
            assert np.array_equal(np.asarray(fwd[g]), np.asarray(primal[g])), g
        _, _, _, g_xla = grads(st.xla())
        err = _rel_err(g_pal, g_xla)
        assert err <= GRAD_TOL, ("gradient", err)
        return {"shape": (n, n), "steps": prob.steps, "rel_err_vs_xla": err}

    # -- phase 5: serving ---------------------------------------------------
    def serving(self):
        from repro.core import dsl as st, suite
        from repro.serving.stencil_serve import SimServer, default_swap
        shapes = ([("star2d1r", (24, 40), 12), ("star2d1r", (17, 29), 7),
                   ("star3d2r", (12, 16, 20), 5)] if self.rehearsal else
                  [("star2d1r", (1000, 700), 40),
                   ("star2d1r", (600, 900), 25),
                   ("star2d1r", (1024, 1024), 33),
                   ("star3d2r", (100, 120, 128), 12),
                   ("star3d2r", (128, 128, 128), 20)])
        rng = np.random.default_rng(0)
        srv = SimServer(batch_cap=2, fuse_window=8)
        reqs = {}
        for name, shape, steps in shapes:
            payload = {"u": rng.standard_normal(shape).astype(np.float32)}
            reqs[srv.submit(name, shape, steps, payload)] = (name, shape,
                                                              steps, payload)
        done = srv.run_until_drained()
        for uid, (name, shape, steps, payload) in reqs.items():
            k = suite.get_kernel(name)
            gs = {g: st.grid(st.f32, shape, k.info.order)
                  for g in k.ir.grid_params}
            gs["u"].interior = payload["u"]
            st.launch(backend=st.xla())(lambda: st.timeloop(
                steps, swap=default_swap(k))(k)(*gs.values()))()
            for g, want in gs.items():
                np.testing.assert_allclose(done[uid].result[g],
                                           np.asarray(want.interior),
                                           rtol=SERVE_RTOL, atol=SERVE_ATOL,
                                           err_msg=f"request {uid} {g}")
        return {"requests": len(reqs), "waves": srv.waves_run}

    # -- four chips ---------------------------------------------------------
    def sharded_timeloop(self, mesh):
        from repro.core import dsl as st, suite
        k = suite.get_kernel("star3d4r")
        n = (32, 16, 16) if self.rehearsal else (512, 512, 512)
        steps = 20

        def run(backend, mesh=None):
            u = self.field(n, k.info.order, seed=1)
            v = self.field(n, k.info.order, seed=2)
            st.launch(backend=backend, mesh=mesh)(
                lambda: st.timeloop(steps, swap=("v", "u"))(k)(u, v))()
            return {"u": u, "v": v}

        got = run(st.distributed(grid_axes=("data", None, None),
                                 swap=("v", "u")), mesh)
        devices = {s.device for s in got["u"].data.addressable_shards}
        assert devices == set(mesh.devices.flat), devices
        want = run(st.xla())
        err = max(_rel_err(got[g].interior, want[g].interior) for g in got)
        assert err <= REL_TOL, ("sharded", err)
        return {"shape": n, "steps": steps, "devices": len(devices),
                "rel_err_vs_one_device": err}

    def sharded_gradient(self, mesh):
        import jax.numpy as jnp
        from repro.core import dsl as st
        n = 32 if self.rehearsal else 256
        prob = _fwi_problem(n)

        def grad(backend, mesh=None):
            fn = prob.forward(backend, mesh)
            obs = fn(prob.arrays(prob.vp2_true))

            def misfit(vp2):
                a = prob.arrays(prob.vp2_background)
                a["vp2"] = a["vp2"].at[1:-1, 1:-1].set(vp2)
                out = fn(a)
                # interiors only: the distributed carry keeps grid halos
                return 0.5 * sum(jnp.sum((out[g] - obs[g])[1:-1, 1:-1] ** 2)
                                 for g in ("p0", "p1"))
            return np.asarray(self.jax.grad(misfit)(
                jnp.asarray(prob.vp2_background)))

        g_mesh = grad(st.distributed(grid_axes=("data", None)), mesh)
        g_one = grad(st.xla())
        assert np.isfinite(g_mesh).all() and np.abs(g_one).max() > 0
        err = _rel_err(g_mesh, g_one)
        assert err <= GRAD_TOL, ("sharded gradient", err)
        return {"shape": (n, n), "steps": prob.steps,
                "rel_err_vs_one_device": err}


class _fwi_problem:
    """``examples/fwi.py``'s setup: a 2-D acoustic shot near the surface
    over a background model with a fast circular inclusion, run for
    ``5n/4`` steps (its 60 steps at n=48) so the wavefront (0.35 cells
    per step) reaches the inclusion and the misfit has a gradient.  The
    source is injected every ``FUSE`` steps: each hook time is a constant
    of its window's program, so a per-step cadence would compile one
    program per step."""

    FUSE = 8

    def __init__(self, n: int):
        self.kernel = _wave2d()
        self.n, self.steps, self.dt = n, 5 * n // 4, 0.35
        self.vp2_background = np.full((n, n), 1.0, np.float32)
        self.vp2_true = self.vp2_background.copy()
        yy, xx = np.mgrid[0:n, 0:n]
        self.vp2_true[((yy - n // 2) ** 2 + (xx - n // 2) ** 2)
                      < (n // 6) ** 2] = 1.69
        self.src = (2, n // 2)

    def grids(self, vp2):
        from repro.core import dsl as st
        p0, p1, c = (st.grid(st.f32, (self.n, self.n), order=1)
                     for _ in range(3))
        c.interior = vp2
        return p0, p1, c

    def arrays(self, vp2):
        return {g: x.data for g, x in zip(("p0", "p1", "vp2"),
                                          self.grids(vp2))}

    def between(self, t, grids):
        from repro.core import acoustic
        g = grids["p1"]
        idx = (g.order + self.src[0], g.order + self.src[1])
        g.data = g.data.at[idx].add(acoustic.source_wavelet(t, f0=0.06,
                                                            t0=10))

    def forward(self, backend, mesh=None):
        from repro.core import dsl as st
        fn = st.differentiable_timeloop(
            self.kernel, *self.grids(self.vp2_background), self.dt,
            steps=self.steps, swap=("p0", "p1"), fuse_steps=self.FUSE,
            between=self.between, backend=backend, mesh=mesh)
        return lambda arrays: fn(arrays, fn.scalars)

    def timeloop(self, backend, arrays):
        from repro.core import dsl as st
        gs = self.grids(self.vp2_background)
        for g, x in zip(gs, ("p0", "p1", "vp2")):
            g.data = arrays[x]
        st.launch(backend=backend, fuse_steps=self.FUSE)(
            lambda: st.timeloop(self.steps, swap=("p0", "p1"),
                                between=self.between)(self.kernel)(
                *gs, self.dt))()
        return {x: g.data for g, x in zip(gs, ("p0", "p1", "vp2"))}


def _wave2d():
    """The 2-D acoustic kernel of ``examples/fwi.py``."""
    from repro.core import dsl as st

    @st.kernel
    def wave2d(p0: st.grid, p1: st.grid, vp2: st.grid, dt: st.f32):
        lap = (p1.at(-1, 0) + p1.at(1, 0) + p1.at(0, -1) + p1.at(0, 1)
               - 4.0 * p1.at(0, 0))
        p0.at(0, 0).set(2.0 * p1.at(0, 0) - p0.at(0, 0)
                        + vp2.at(0, 0) * dt * dt * lap)
    return wave2d


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes, interpreted Pallas, no result line")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(_SRC, "repro")):
        print(f"chip_smoke: the repro package is not under {_SRC}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, _SRC)
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()

    import jax
    dev = jax.devices()[0]
    if not args.cpu_rehearsal and dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX's devices are {dev.platform}); "
              "refusing to fall back", file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(jax.devices())}", file=sys.stderr)
        return 2
    print("device " + json.dumps({
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()), "jax": jax.__version__,
        "compile_cache": cache}), flush=True)

    smoke = Smoke(args.cpu_rehearsal)
    t0 = time.perf_counter()
    if args.chips == 4:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("data",), devices=jax.devices()[:4])
        smoke.phase("sharded_timeloop",
                    lambda: smoke.sharded_timeloop(mesh))
        smoke.phase("sharded_gradient",
                    lambda: smoke.sharded_gradient(mesh))
    else:
        smoke.phase("star3d4r", smoke.star3d4r)
        smoke.phase("acoustic", smoke.acoustic)
        smoke.phase("gradient", smoke.gradient)
        smoke.phase("serving", smoke.serving)
    print("summary " + json.dumps({
        "wall_s": time.perf_counter() - t0,
        "compile_s": smoke.clock.seconds,
        "compile_cache_hits": smoke.clock.hits}), flush=True)
    if args.cpu_rehearsal:
        print("chip_smoke: CPU rehearsal passed (no result line: no TPU)")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
