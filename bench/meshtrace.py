"""What the mesh metrics read: the collectives of a device trace, and the
halo bytes a sharded stencil step must move.

A collective is classified by its opcode, which XLA gives an instruction
as its name (``collective-permute-start.3``, ``all-reduce.7``): the
prefixes are copied here, so ``devtrace.py`` keeps no list of its own.
On a TPU v5e host of four chips the profiler gives the ``Async XLA Ops``
line, where an asynchronous ``-start`` event spans start to done, for
the first chip only; the others show the issue (``-start``, a few µs)
and the wait (``-done``) on the ``XLA Ops`` line.  A start-to-done span
is where the schedule placed the done, not the transfer, so no reader
takes it for the exchange's duration.

The compulsory halo bytes are the benchmark's own count, not the
program's: for each axis the backend's ``grid_axes`` split over the
configuration's ``mesh``, each grid the plain reference's kernel reads at
a nonzero offset along that axis moves a slab as deep as the largest such
offset across every shard boundary, once each way.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

from devtrace import Interval

COLLECTIVES = ("collective-permute", "all-reduce", "all-gather",
               "all-to-all", "reduce-scatter")


def is_collective(name: str) -> bool:
    return name.lstrip("%").startswith(COLLECTIVES)


def split_ops(trace, dev: int) -> Tuple[List[Interval], List[Interval]]:
    """(collective intervals, every other operation's intervals) of one
    device, clipped to the window."""
    lo, hi = trace.window
    coll, other = [], []
    for o in trace.devices[dev]:
        a, b = max(o.start, lo), min(o.end, hi)
        if b > a:
            (coll if is_collective(o.name) else other).append((a, b))
    return coll, other


# -- the compulsory halo ---------------------------------------------------
class _Any:
    """Stands in for every value of a kernel expression."""

    def _self(self, *_):
        return self

    __add__ = __radd__ = __sub__ = __rsub__ = __mul__ = __rmul__ = _self
    __truediv__ = __rtruediv__ = __neg__ = set = _self


class _Probe:
    def __init__(self, name: str, seen: Dict[str, List[Tuple[int, ...]]]):
        self.name, self.seen = name, seen

    def at(self, *offs):
        self.seen.setdefault(self.name, []).append(tuple(offs))
        return _Any()


def read_offsets(kernel) -> Dict[str, List[Tuple[int, ...]]]:
    """Every ``g.at(offsets)`` of the reference kernel, by grid."""
    seen: Dict[str, List[Tuple[int, ...]]] = {}
    args = [_Probe(p, seen) if p in kernel.grid_params else _Any()
            for p in kernel.params]
    kernel.fn(*args)
    return seen


def halo_bytes_per_chip_step(spec, itemsize: int = 4) -> Optional[float]:
    """Compulsory halo bytes of one step, per chip, for the cell's
    configuration; None where it splits no axis."""
    from reference.stencil import Kernel
    cfg = spec.config
    mesh = cfg.get("mesh")
    axes = cfg.get("backend", {}).get("grid_axes")
    if not mesh or not axes:
        return None
    sizes = dict(zip(mesh["axes"], mesh["shape"]))
    chips = math.prod(mesh["shape"])
    offsets = read_offsets(Kernel(cfg["kernel"], cfg["order"]))
    shape = spec.shape
    total = 0
    for ax, m in enumerate(axes):
        if m is None or sizes[m] == 1:
            continue
        depth = [max(abs(o[ax]) for o in offs) for offs in offsets.values()]
        slab = math.prod(n for a, n in enumerate(shape) if a != ax)
        total += (2 * (sizes[m] - 1) * slab * itemsize
                  * sum(d for d in depth if d))
    return total / chips if total else None
