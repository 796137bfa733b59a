"""Host spans: the program's one timing mechanism.

``span(name, phase)`` opens a ``jax.profiler.TraceAnnotation``, so a
profiler session sees each span on the calling thread beside the device
operations it issued.  Inside ``st.launch`` (which calls ``collect``) the
span's wall time is also added to ``phase`` of the launch's
``LaunchResult.profile``; without a launch only the annotation remains,
which costs well under a microsecond while no profiler runs.

The names below are stable: the benchmark's span readers and the tests
find the engine's host path by them.  The adjoint's device work, and the
distributed window's layout stage and halo exchange, are named by
``jax.named_scope`` (``ADJOINT_*``, ``MeshScope``), which only
reaches the compiled program's ``op_name`` metadata.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, Iterator, Optional

import jax

# -- spans on the host ------------------------------------------------------
#: one ``st.launch`` of a target (feeds ``total``)
LAUNCH = "dsl.launch"
#: kernel and engine builds in ``core/dsl.py`` (feeds ``codegen``)
CODEGEN = "dsl.codegen"
#: the ahead-of-time compile of an ``st.map`` program (feeds ``comp``)
MAP_COMPILE = "dsl.compile"
#: one ``st.map`` application, blocked until ready (feeds ``kernel``)
MAP = "dsl.map"
#: one fusion window of the engine, dispatch through sync (feeds ``kernel``)
WINDOW = "engine.window"
#: the pad into the engine's padded layout (feeds ``layout``)
TO_PADDED = "layout.to_padded"
#: the call into the compiled window program
DISPATCH = "engine.dispatch"
#: the leapfrog parity rotation and the write-back of padded interiors
FROM_PADDED = "layout.from_padded"
#: ``block_until_ready`` on the window's outputs
SYNC = "engine.sync"
#: the ``between`` hook at a window boundary
HOOK = "engine.hook"
#: a window-program cache miss (feeds ``comp``)
COMPILE = "engine.compile"

# -- named scopes in the adjoint's compiled program --------------------------
ADJOINT_FORWARD = "adjoint_forward"
ADJOINT_REPLAY = "adjoint_replay"
ADJOINT_VJP = "adjoint_vjp"


# -- named scopes in the distributed programs (``core/distributed.py``) -----
class MeshScope:
    """Device-side names only, so kept apart from the host spans above."""
    #: the crop of the halo-padded, sharded grids to their sharded interiors
    LAYOUT_CROP = "layout.crop"
    #: the write-back of the outputs' interiors into the halo-padded grids
    LAYOUT_WRITE_BACK = "layout.write_back"
    #: the ppermute halo slabs and the zero pads of one exchange
    HALO_EXCHANGE = "halo_exchange"
    #: the deep-interior pre-pass, which reads no exchanged cell
    HALO_INTERIOR = "halo_interior"
    #: the boundary bands, which wait for the exchanged slabs
    HALO_BANDS = "halo_bands"


class _Active(threading.local):
    phases: Optional[Dict[str, float]] = None


_ACTIVE = _Active()


@contextlib.contextmanager
def collect() -> Iterator[Dict[str, float]]:
    """Make a fresh launch profile the calling thread's active one for the
    block: spans with a ``phase`` add their seconds to it.  Nested calls
    get their own profile and restore the outer one on exit."""
    prev = _ACTIVE.phases
    _ACTIVE.phases = phases = {}
    try:
        yield phases
    finally:
        _ACTIVE.phases = prev


@contextlib.contextmanager
def span(name: str, phase: Optional[str] = None) -> Iterator[None]:
    """A profiler annotation named ``name``; with ``phase``, its duration is
    also added to that phase of the active launch profile, if any."""
    phases = _ACTIVE.phases if phase is not None else None
    with jax.profiler.TraceAnnotation(name):
        if phases is None:
            yield
            return
        t0 = time.perf_counter()
        try:
            yield
        finally:
            phases[phase] = phases.get(phase, 0.0) + time.perf_counter() - t0
