"""Quickstart: paper Listing 1 — a star2d4r stencil in the StencilPy DSL.

    PYTHONPATH=src python examples/quickstart.py

Writes the kernel once, runs it on the portable XLA backend and on the
TPU Pallas backend (interpret mode on CPU), and prints the framework's
phase profile (frontend / codegen / compile / kernel — paper Tables 6-8
columns).
"""
import numpy as np

from repro.core import dsl as st


@st.kernel
def kernel_star2d4r(u: st.grid, v: st.grid):
    v.at(0, 0).set(0.25005 * u.at(0, 0)
                   + 0.11111 * (u.at(-4, 0) + u.at(4, 0))
                   + 0.06251 * (u.at(-3, 0) + u.at(3, 0))
                   + 0.06255 * (u.at(-2, 0) + u.at(2, 0))
                   + 0.06245 * (u.at(-1, 0) + u.at(1, 0))
                   + 0.06248 * (u.at(0, -1) + u.at(0, 1))
                   + 0.06243 * (u.at(0, -2) + u.at(0, 2))
                   + 0.06253 * (u.at(0, -3) + u.at(0, 3))
                   - 0.22220 * (u.at(0, -4) + u.at(0, 4)))


@st.target
def target_star2d4r(u: st.grid, v: st.grid, iters: st.i32):
    for _t in range(iters):
        st.map(e=u.shape)(kernel_star2d4r)(u, v)
        (u.data, v.data) = (v.data, u.data)


def main():
    print(kernel_star2d4r)          # parsed stencil info (shape/order/FLOPs)

    u = st.grid(dtype=st.f32, shape=(256, 256), order=4).randomize(0)
    v = st.grid(dtype=st.f32, shape=(256, 256), order=4)

    # portable XLA backend
    res = st.launch(backend=st.xla())(target_star2d4r)(u, v, 50)
    ref = np.asarray(u.interior)
    print("xla profile:", {k: round(t, 4) for k, t in res.profile.items()})

    # TPU Pallas backend (paper's st.cuda(...) Listing-1 form also works)
    u2 = st.grid(dtype=st.f32, shape=(256, 256), order=4).randomize(0)
    v2 = st.grid(dtype=st.f32, shape=(256, 256), order=4)
    res2 = st.launch(backend=st.cuda(computeCapability="9.0",
                                     threadsPerBlock=(8, 128),
                                     template="gmem"))(
        target_star2d4r)(u2, v2, 50)
    got = np.asarray(u2.interior)
    print("pallas profile:", {k: round(t, 4) for k, t in res2.profile.items()})
    err = float(np.abs(got - ref).max())
    scale = max(1.0, float(np.abs(ref).max()))
    print(f"max |pallas - xla| = {err:.3e} (relative {err / scale:.3e})")
    # this stencil amplifies oscillatory modes (paper's own coefficients),
    # so compare at fp32-relative accuracy
    assert err / scale < 1e-5

    # fused time loop: the same 50 steps traced once and executed as a
    # single compiled program (st.timeloop) — one host sync total instead
    # of one per step
    u3 = st.grid(dtype=st.f32, shape=(256, 256), order=4).randomize(0)
    v3 = st.grid(dtype=st.f32, shape=(256, 256), order=4)

    @st.target
    def target_fused(u: st.grid, v: st.grid, iters: st.i32):
        return st.timeloop(iters, swap=("v", "u"))(kernel_star2d4r)(u, v)

    res3 = st.launch(backend=st.xla())(target_fused)(u3, v3, 50)
    tl = res3.value
    err3 = float(np.abs(np.asarray(u3.interior) - ref).max())
    print(f"fused timeloop: {tl.steps} steps in {tl.windows} window(s), "
          f"{tl.steps_per_s:.0f} steps/s, max |fused - per-step| = {err3:.3e}")
    assert err3 / scale < 1e-6
    print("OK")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
