"""The device's idle time attributed to the program's own host spans.

The program opens ``jax.profiler.TraceAnnotation`` spans on its host path
(``src/repro/core/trace.py``); ``devtrace.load`` keeps the spans of the
thread that ran the window, the program's beside the benchmark's and
JAX's.  A program span's self time is its interval less the program spans
nested inside it; JAX's own spans (``PjitFunction``, ``DevicePut``) inside
it count as its time.  The names are copied here, not imported, so that a
renamed span reads 0 instead of moving the yardstick with the program.
"""
from __future__ import annotations

import bisect
import importlib.util
from typing import Iterable, List, Optional, Sequence

from devtrace import Interval, clip, gaps, length, subtract, union

#: every span the program opens
PROGRAM = ("dsl.launch", "dsl.codegen", "dsl.compile", "dsl.map",
           "engine.window", "layout.to_padded", "engine.dispatch",
           "layout.from_padded", "engine.sync", "engine.hook",
           "engine.compile")
#: the engine's serial host work between two windows' device programs
HOST_PATH = ("layout.to_padded", "engine.dispatch", "layout.from_padded",
             "engine.hook")
#: the host waiting on the window's outputs
SYNC = ("engine.sync",)


def intersect(a: Iterable[Interval], b: Iterable[Interval]
              ) -> List[Interval]:
    """The parts covered by both ``union(a)`` and ``union(b)``."""
    a, b = union(a), union(b)
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            s, e = max(lo, b[k][0]), min(hi, b[k][1])
            if e > s:
                out.append((s, e))
            k += 1
    return out


def self_time(host: Sequence, names: Sequence[str]) -> List[Interval]:
    """Where the innermost open program span is one of ``names``: each such
    span less the program spans that start inside it.  Spans of one
    thread nest, so those also end inside it."""
    # by start, and an outer span before the inner one that starts with it
    prog = sorted(((s, e, n) for n, s, e in host if n in PROGRAM),
                  key=lambda x: (x[0], -x[1]))
    starts = [s for s, _, _ in prog]
    out = []
    for i, (s, e, n) in enumerate(prog):
        if n not in names:
            continue
        stop = bisect.bisect_left(starts, e, lo=i + 1)
        out += subtract([(s, e)], [(s2, e2) for s2, e2, _ in prog[i + 1:stop]])
    return union(out)


def has_spans() -> bool:
    """Whether the program under test opens host spans at all."""
    return importlib.util.find_spec("repro.core.trace") is not None


def idle_share(run, names: Sequence[str]) -> Optional[float]:
    """% of the window in which the most idle device is idle while the
    innermost open program span is one of ``names``."""
    tr = run.trace
    if tr is None or not tr.devices or not has_spans():
        return None
    dev = max(tr.devices, key=tr.idle_share)
    lo, hi = tr.window
    idle = gaps(union(tr.ops(dev)), lo, hi)
    under = clip(self_time(tr.host, names), lo, hi)
    return 100.0 * length(intersect(idle, under)) / tr.window_ns
