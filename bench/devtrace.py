"""Reduction of a JAX profiler trace to the device intervals the per-layer
metrics read.

``load(trace_dir)`` reads the ``.xplane.pb`` that ``jax.profiler.trace``
wrote with ``jax.profiler.ProfileData`` and keeps, for each device, the
operations that ran on it (start, end, name, whether it is a generated
stencil kernel) and, from the host, the spans of the thread
that opened the ``bench.window`` annotation.  Everything is clipped to that
window.  On a TPU the operations are the events of each ``/device:TPU:<n>``
plane's ``XLA Ops`` and ``Async XLA Ops`` lines, less the control-flow
operations whose events span their bodies (``while``); on the CPU (the
tests) they are the host events that carry an ``hlo_op`` statistic, keyed
by their ``device_ordinal``.

The arithmetic is in plain functions on lists of ``(start, end)`` pairs so
that it can be checked on synthetic events.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
KERNEL_PREFIX = "stencil_"
#: device lines of a TPU plane that hold operations (synchronous ones, and
#: the asynchronous copies and collectives that overlap them)
OP_LINES = ("XLA Ops", "Async XLA Ops")
#: control flow whose event spans the operations of its body
CONTAINERS = {"while", "conditional", "call"}
Interval = Tuple[float, float]


# -- interval arithmetic (ns) ----------------------------------------------
def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of ``intervals``."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in union(intervals))


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a: Iterable[Interval], b: Iterable[Interval]) -> List[Interval]:
    """The parts of ``union(a)`` that ``union(b)`` does not cover."""
    out = []
    bs = union(b)
    for lo, hi in union(a):
        cur = lo
        for blo, bhi in bs:
            if bhi <= cur or blo >= hi:
                continue
            if blo > cur:
                out.append((cur, blo))
            cur = max(cur, bhi)
            if cur >= hi:
                break
        if cur < hi:
            out.append((cur, hi))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """Idle intervals of the window ``[lo, hi)``."""
    return subtract([(lo, hi)], busy)


# -- the trace ----------------------------------------------------------------
@dataclasses.dataclass
class Op:
    name: str
    start: float
    end: float
    kernel: bool


def parse_op(text: str) -> Tuple[str, str]:
    """(name, opcode) of a device operation event.  A TPU trace names the
    event by its HLO instruction text, ``%name = shape opcode(operands)``;
    the CPU by the bare instruction name, whose opcode is not given."""
    m = re.match(r"%?([^\s=]+)\s*=\s*", text)
    if not m:
        return text, ""
    rest = text[m.end():]
    if rest.startswith("("):            # a tuple shape: skip its parentheses
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    op = re.match(r"\s*([a-z][\w\-]*)\(", rest)
    return m.group(1), op.group(1) if op else ""


def classify(text: str) -> Tuple[str, str, bool]:
    """(name, opcode, is a generated stencil kernel).  A kernel's
    instruction is named ``stencil_*`` by the system's code generator."""
    name, opcode = parse_op(text)
    return name, opcode, name.startswith(KERNEL_PREFIX)


@dataclasses.dataclass
class Trace:
    window: Interval
    devices: Dict[int, List[Op]]
    host: List[Tuple[str, float, float]]

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def ops(self, dev: int, kind: Optional[str] = None) -> List[Interval]:
        lo, hi = self.window
        sel = [o for o in self.devices[dev]
               if kind is None
               or (kind == "kernel" and o.kernel)
               or (kind == "other" and not o.kernel)]
        return clip([(o.start, o.end) for o in sel], lo, hi)

    def busy_ns(self, dev: int) -> float:
        return length(self.ops(dev))

    def summed_ns(self, dev: int, kind: Optional[str] = None) -> float:
        """Summed durations (not the union) of the chosen operations."""
        return sum(b - a for a, b in self.ops(dev, kind))

    def idle_share(self, dev: int) -> float:
        return 1.0 - self.busy_ns(dev) / self.window_ns

    def has(self, kind: str) -> bool:
        return any(self.ops(d, kind) for d in self.devices)

    # -- breakdown -------------------------------------------------------
    def top_ops(self, n: int = 10) -> List[List]:
        """Device seconds by operation, averaged over the devices; the
        instances of one operation (``copy.9``, ``copy.10``) are summed
        under their common name."""
        tot: Dict[str, float] = defaultdict(float)
        lo, hi = self.window
        for ops in self.devices.values():
            for o in ops:
                a, b = max(o.start, lo), min(o.end, hi)
                if b > a:
                    key = re.sub(r"\.\d+$", "", o.name)
                    tot[key] += (b - a) / 1e9 / len(self.devices)
        return [[k, v] for k, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def idle_by_host(self, n: int = 10) -> List[List]:
        """Idle time of the most idle device, summed by the innermost host
        span that was open at the middle of each gap."""
        if not self.devices:
            return []
        dev = max(self.devices, key=self.idle_share)
        lo, hi = self.window
        tot: Dict[str, float] = defaultdict(float)
        for a, b in gaps(union(self.ops(dev)), lo, hi):
            mid = (a + b) / 2
            inner = [(e - s, name) for name, s, e in self.host
                     if s <= mid < e and name != WINDOW]
            name = min(inner)[1] if inner else "host idle"
            tot[name] += (b - a) / 1e9
        return [[k, v] for k, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:n]]


def newest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(trace_dir: str, devices: Optional[Sequence[int]] = None) -> Trace:
    """Read the newest trace under ``trace_dir``; ``devices`` (ids) keeps
    only those devices' operations."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(newest_xplane(trace_dir))
    window = None
    host_lines = []
    ops: Dict[int, List[Op]] = defaultdict(list)
    for plane in pd.planes:
        m = re.match(r"/device:[A-Z]+:(\d+)$", plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name not in OP_LINES:
                    continue
                for e in line.events:
                    name, opcode, k = classify(e.name)
                    if opcode not in CONTAINERS:
                        ops[dev].append(Op(name, e.start_ns,
                                           e.start_ns + e.duration_ns, k))
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = []
            for e in line.events:
                st = dict(e.stats)
                if "hlo_op" in st and "device_ordinal" in st:
                    # the CPU backend runs its operations on host threads
                    name, _, k = classify(str(st["hlo_op"]))
                    ops[int(st["device_ordinal"])].append(
                        Op(name, e.start_ns, e.start_ns + e.duration_ns, k))
                    continue
                spans.append((e.name, e.start_ns,
                              e.start_ns + e.duration_ns))
                if e.name == WINDOW:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
            host_lines.append(spans)
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    host = next(s for s in host_lines if any(n == WINDOW for n, _, _ in s))
    if devices is not None:
        ops = {d: ops.get(d, []) for d in devices}
    return Trace(window=window, devices=dict(ops), host=host)
