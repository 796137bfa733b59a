"""Mosaic compiles of the fused window programs at full size.

Each test lowers one ``TimeloopEngine.window_arrays`` program for a
described (not attached) TPU v5e and compiles it with the TPU compiler,
which refuses what interpret mode accepts: unaligned window DMAs, value
``dynamic_slice``/scatter, and more VMEM than a kernel may use.  Nothing
runs, so these say nothing about results; ``chip_smoke.py`` checks those
on the chip.

The topology is described inside a module-scope fixture, never at import:
only one process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import acoustic, dsl as st, suite
from repro.core.timeloop import TimeloopEngine
from repro.launch.mesh import make_mesh


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_window(kernel, shape, backend, swap, kw, sharding,
                    mesh=None):
    """HLO text of the compiled ``kw``-step window program."""
    halos = {g: kernel.info.halo for g in kernel.ir.grid_params}
    eng = TimeloopEngine(kernel.ir, halos, shape, backend, swap=swap,
                         mesh=mesh)
    arrays = {g: jax.ShapeDtypeStruct(
        tuple(s + 2 * h for s, h in zip(shape, halos[g])), jnp.float32,
        sharding=sharding) for g in kernel.ir.grid_params}
    scalars = {n: jax.ShapeDtypeStruct((), jnp.float32, sharding=sharding)
               for n, _ in kernel.ir.scalar_params}
    fn = eng.window_arrays(kw)
    return jax.jit(fn).lower(arrays, scalars).compile().as_text()


@pytest.mark.parametrize("name,shape,template,time_block", [
    ("star3d4r", (512, 512, 512), "gmem", 1),
    ("star3d4r", (512, 512, 512), "gmem", 4),
    ("star3d4r", (512, 512, 512), "shift", 1),
    ("star2d4r", (4096, 4096), "gmem", 4),
])
def test_star_window_compiles(one_chip, name, shape, template, time_block):
    """The paper's 25-point kernel at 512³ f32, and the 2-D temporal body
    (whole-block sub-steps) at 4096²; a 5-step window at
    ``time_block=4`` holds both the 4-step and the remainder kernel."""
    k = suite.get_kernel(name)
    backend = st.pallas(template=template, time_block=time_block,
                        interpret=False)
    hlo = _compile_window(k, shape, backend, ("v", "u"),
                          5 if time_block > 1 else 2, one_chip)
    assert hlo.count("tpu_custom_call") >= (2 if time_block > 1 else 1)


def test_acoustic_256_window_compiles(one_chip):
    """The four-grid acoustic-ISO window (two wavefields, vp², damping)."""
    backend = st.pallas(template="gmem", interpret=False)
    hlo = _compile_window(acoustic.acoustic_iso_kernel, (256, 256, 256),
                          backend, ("p0", "p1"), 10, one_chip)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("block", [(8, 8, 64), (16, 12, 128)])
def test_unaligned_block_refused_for_the_chip(one_chip, block):
    """A user block off the (8, 128) tile keeps exact windows, which the
    interpreter runs; for the chip the lowering refuses it by name rather
    than fetching a misplaced window."""
    k = suite.get_kernel("star3d4r")
    backend = st.pallas(template="gmem", block=block, interpret=False)
    with pytest.raises(Exception, match="divisible by 8 and 128"):
        _compile_window(k, (256, 256, 256), backend, ("v", "u"), 2, one_chip)


def test_sharded_window_compiles(topo):
    """The ``--chips 4`` path of ``chip_smoke.py``: the fused sharded
    star3d4r window at 512³ over the four chips of a v5e host exchanges
    its halos with collective permutes."""
    mesh = make_mesh((4,), ("data",), devices=topo.devices)
    backend = st.distributed(grid_axes=("data", None, None), swap=("v", "u"))
    hlo = _compile_window(suite.get_kernel("star3d4r"), (512, 512, 512),
                          backend, ("v", "u"), 20,
                          NamedSharding(mesh, P("data", None, None)), mesh)
    assert "collective-permute" in hlo
