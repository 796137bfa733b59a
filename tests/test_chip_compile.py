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
import re

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


def _computations(hlo):
    """Compiled HLO text as {computation name: its lines}."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif line == "}":
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps


def _loop_computations(comps):
    """Every computation that runs inside a while loop: the loop bodies
    and conditions and whatever they call, transitively."""
    def called(lines, keys):
        out = set()
        for line in lines:
            for ref in re.findall(r"\b(%s)=(\{[^}]*\}|%%[\w.\-]+)"
                                  % "|".join(keys), line):
                out |= set(re.findall(r"%([\w.\-]+)", ref[1]))
        return out
    todo = called((ln for c in comps.values() for ln in c),
                  ("body", "condition"))
    seen = set()
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        todo |= called(comps[c], ("calls", "to_apply", "body", "condition",
                                  "branch_computations"))
    return seen


def _padded_copies(lines, padded_shape):
    """Lines that copy a whole padded buffer."""
    shape = re.escape("f32[%s]" % ",".join(map(str, padded_shape)))
    return [ln for ln in lines
            if re.search(shape + r"\S*\s+copy(-start)?\(", ln)]


@pytest.mark.parametrize("name,kw,time_block", [
    ("star3d4r", 1, 1), ("star3d4r", 2, 1), ("star3d4r", 3, 1),
    ("star3d4r", 100, 1), ("acoustic", 1, 1), ("acoustic", 8, 1),
    ("star3d4r", 9, 4), ("star3d4r", 17, 4),
])
def test_window_loop_keeps_buffers_in_place(one_chip, name, kw, time_block):
    """The program ``_run_window`` sends at 512³ keeps every padded buffer
    in its loop slot: the loop runs steps (or k-step invocations) in
    pairs that net the leapfrog rename to the identity, so no iteration
    copies a 528×528×768 buffer, and an odd last step runs in place with
    the host renaming.  The only whole-buffer copies left in the program
    are the ``time_block>1`` spares, once per window."""
    kernel, swap = ((acoustic.acoustic_iso_kernel, ("p0", "p1"))
                    if name == "acoustic"
                    else (suite.get_kernel(name), ("v", "u")))
    halos = {g: kernel.info.halo for g in kernel.ir.grid_params}
    eng = TimeloopEngine(kernel.ir, halos, (512, 512, 512),
                         st.pallas(template="gmem", time_block=time_block,
                                   interpret=False), swap=swap)
    plan = eng._plan
    padded = {g: jax.ShapeDtypeStruct(plan.padded_shape, jnp.float32,
                                      sharding=one_chip)
              for g in plan.opnd_grids}
    scalars = {n: jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
               for n, _ in kernel.ir.scalar_params}
    hlo = eng._window(kw, donate=True).lower(padded, scalars) \
        .compile().as_text()
    comps = _computations(hlo)
    loops = _loop_computations(comps)
    if kw >= 4 * time_block:
        assert loops, "the window has no loop left to check"
    in_loop = [ln for c in loops for ln in _padded_copies(comps[c],
                                                          plan.padded_shape)]
    assert not in_loop, [ln[:100] for ln in in_loop]
    spares = len(plan.step_out_grids) if time_block > 1 else 0
    copies = [ln for c in comps.values()
              for ln in _padded_copies(c, plan.padded_shape)]
    assert len(copies) == spares, [ln[:100] for ln in copies]


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


def test_sharded_acoustic_1k_window_fits_four_chips(topo):
    """The ``acoustic-iso-1k-4chip`` cell's window: acoustic-ISO at
    1024×1024×512 f32 over the four chips of a v5e host, 100 steps, the
    grids donated as on the chip.  The crop to interiors and the write-back
    stay in shards (no all-gather, no replicated output), so each chip
    needs well under its 16 GB where the grids alone are 8.6 GB."""
    mesh = make_mesh((4,), ("data",), devices=topo.devices)
    kernel = acoustic.acoustic_iso_kernel
    shape, swap = (1024, 1024, 512), ("p0", "p1")
    halos = {g: kernel.info.halo for g in kernel.ir.grid_params}
    eng = TimeloopEngine(kernel.ir, halos, shape,
                         st.distributed(grid_axes=("data", None, None),
                                        swap=swap), swap=swap, mesh=mesh)
    sharding = NamedSharding(mesh, P("data", None, None))
    arrays = {g: jax.ShapeDtypeStruct(
        tuple(s + 2 * h for s, h in zip(shape, halos[g])), jnp.float32,
        sharding=sharding) for g in kernel.ir.grid_params}
    scalars = {n: jax.ShapeDtypeStruct((), jnp.float32,
                                       sharding=NamedSharding(mesh, P()))
               for n, _ in kernel.ir.scalar_params}
    c = eng._window(100, donate=True).lower(arrays, scalars) \
        .compile()
    hlo = c.as_text()
    assert "collective-permute" in hlo and "all-gather" not in hlo
    outs = jax.tree.leaves(c.output_shardings)
    assert outs and all(s.is_equivalent_to(sharding, 3) for s in outs), outs
    m = c.memory_analysis()
    per_chip = (m.argument_size_in_bytes + m.output_size_in_bytes
                + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert m.alias_size_in_bytes >= 0.99 * m.output_size_in_bytes
    assert per_chip < 12e9, per_chip
