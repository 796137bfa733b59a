"""Production mesh builders.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
initialization; smoke tests and benches keep the default single device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the stencil lowerings
    place shards with shard_map and update sharded arrays with ``.at[]``,
    which the ``Explicit`` axes ``jax.make_mesh`` defaults to refuse.
    Every mesh of the repo is built here."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single-pod (data=16, model=16) = 256 chips; multi-pod adds a
    leading pod axis: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Degenerate mesh for single-device smoke runs."""
    n = len(jax.devices())
    return make_mesh((n // model, model), ("data", "model"))


def make_scaling_mesh(n_devices: int, axis: str = "data"):
    """1-D mesh over the first ``n_devices`` devices — the weak/strong
    scaling ladder of ``benchmarks/distributed_stencil.py`` (1/2/4/8
    forced host devices share one process, so each rung is a sub-mesh).
    """
    devs = jax.devices()
    if n_devices > len(devs):
        raise ValueError(
            f"mesh wants {n_devices} devices, only {len(devs)} available "
            f"(set XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    return make_mesh((n_devices,), (axis,), devices=devs[:n_devices])


def mesh_axes(mesh) -> dict:
    """Plain {axis name: size} view of a mesh — the device-free geometry
    descriptor ``core.halo.HaloSpec`` and the autotune/cost-model keys
    consume (also accepts a mapping, passed through)."""
    return dict(mesh.shape) if hasattr(mesh, "shape") else dict(mesh)
