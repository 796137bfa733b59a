"""jit'd public wrappers around the generated Pallas stencil kernels.

``stencil_apply`` is the standalone array-level API (used by the LM
substrate, e.g. the conv1d kernel); the DSL's ``st.map`` goes through
``codegen.lower_pallas`` directly.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import dsl as st

from . import codegen


def stencil_apply(kernel: "st.Kernel",
                  arrays: Dict[str, jnp.ndarray],
                  scalars: Optional[Mapping[str, jnp.ndarray]] = None,
                  *,
                  halos: Optional[Mapping[str, Tuple[int, ...]]] = None,
                  template: str = "gmem",
                  block: Optional[Tuple[int, ...]] = None,
                  mem_type: Optional[str] = None,
                  interpret: Optional[bool] = None,
                  region=None) -> Dict[str, jnp.ndarray]:
    """Apply a ``@st.kernel`` to raw halo-padded arrays.

    ``arrays`` maps grid-param name → array whose shape is
    interior + 2*halo per axis.  Returns the dict with outputs updated on
    the interior (or ``region``).
    """
    k_ir = kernel.ir
    if halos is None:
        h = kernel.info.halo
        halos = {g: h for g in k_ir.grid_params}
    some = next(iter(arrays.values()))
    g0 = k_ir.grid_params[0]
    interior = tuple(s - 2 * hh for s, hh in zip(arrays[g0].shape, halos[g0]))
    backend = st.pallas(template=template, block=block, mem_type=mem_type,
                        interpret=interpret)
    fn = codegen.lower_pallas(k_ir, dict(halos), interior, region, backend)
    return jax.jit(fn)(dict(arrays), dict(scalars or {}))


def stencil_timeloop(kernel: "st.Kernel",
                     arrays: Dict[str, jnp.ndarray],
                     steps: int,
                     *,
                     swap: Tuple[str, str],
                     scalars: Optional[Mapping[str, jnp.ndarray]] = None,
                     halos: Optional[Mapping[str, Tuple[int, ...]]] = None,
                     template: str = "gmem",
                     block: Optional[Tuple[int, ...]] = None,
                     mem_type: Optional[str] = None,
                     interpret: Optional[bool] = None,
                     fuse_steps: Optional[int] = None,
                     time_block: int = 1,
                     batch: int = 0) -> Dict[str, jnp.ndarray]:
    """Fused time stepping on raw halo-padded arrays (the array-level twin
    of ``st.timeloop``): ``steps`` applications + leapfrog rotation of the
    ``swap`` pair, executed on the persistent block-padded layout with one
    halo pad per grid per fusion window (``fuse_steps``, default: fully
    fused).  ``time_block=k`` advances k steps per kernel invocation with
    expanded k·h halos (in-kernel temporal blocking).  Returns the final
    arrays under the name-rotation convention (the newest field ends up
    under the *read* grid's name after each swap, exactly like a
    ``(u.data, v.data) = (v.data, u.data)`` loop).  ``batch=B`` advances B
    scenarios (arrays carry a leading scenario axis) in one program.
    """
    from repro.core import timeloop as _tl

    k_ir = kernel.ir
    if halos is None:
        h = kernel.info.halo
        halos = {g: h for g in k_ir.grid_params}
    g0 = k_ir.grid_params[0]
    spatial = arrays[g0].shape[1:] if batch else arrays[g0].shape
    interior = tuple(s - 2 * hh for s, hh in zip(spatial, halos[g0]))
    backend = st.pallas(template=template, block=block, mem_type=mem_type,
                        interpret=interpret, time_block=time_block)
    return _tl.run_timeloop(k_ir, dict(arrays), dict(scalars or {}), steps,
                            halos=dict(halos), interior_shape=interior,
                            backend=backend, swap=swap,
                            fuse_steps=fuse_steps, batch=batch)
