"""Plain ``jax.numpy`` reference for kernels written in the stencil DSL.

The configuration file holds the kernel as a user writes it (the body of an
``@st.kernel`` function).  This module executes that same source with a
namespace of its own: ``g.at(offsets)`` is a shifted slice of the grid's
halo-padded array and ``g.at(0, ...).set(value)`` writes the interior of
the output grid.  Nothing of the system under test is imported, so the
equations are evaluated independently of its frontend, lowering, kernels
and distributed runtime.

Every grid array carries ``order`` halo cells on each side of every axis
(the DSL's convention); halos are zero and stay zero.  All reads of a step
see the state before the step, as in the DSL.
"""
from __future__ import annotations

import re
from typing import Callable, Dict, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class _Value:
    """An array in a kernel expression.  A grid access counts as a read
    once its value is used; ``.set`` on a center access writes the output
    grid."""

    __slots__ = ("_a", "_read", "_sink")

    def __init__(self, a, read=None, sink=None):
        self._a, self._read, self._sink = a, read, sink

    @property
    def a(self):
        if self._read is not None:
            self._read()
        return self._a

    def set(self, value):
        if self._sink is None:
            raise ValueError(".set is only valid on a center access g.at(0, ...)")
        self._sink(value.a if isinstance(value, _Value) else value)

    def _op(self, other, f):
        return _Value(f(self.a, other.a if isinstance(other, _Value) else other))

    def __add__(self, o):
        return self._op(o, lambda a, b: a + b)

    def __radd__(self, o):
        return self._op(o, lambda a, b: b + a)

    def __sub__(self, o):
        return self._op(o, lambda a, b: a - b)

    def __rsub__(self, o):
        return self._op(o, lambda a, b: b - a)

    def __mul__(self, o):
        return self._op(o, lambda a, b: a * b)

    def __rmul__(self, o):
        return self._op(o, lambda a, b: b * a)

    def __truediv__(self, o):
        return self._op(o, lambda a, b: a / b)

    def __rtruediv__(self, o):
        return self._op(o, lambda a, b: b / a)

    def __neg__(self):
        return _Value(-self.a)


class _GridView:
    def __init__(self, name, data, order, shape, reads, writes):
        self.name, self.data, self.order, self.shape = name, data, order, shape
        self._reads, self._writes = reads, writes

    def at(self, *offs):
        if len(offs) != len(self.shape):
            raise ValueError(f"{self.name}.at needs {len(self.shape)} offsets")
        h = self.order
        idx = tuple(slice(h + o, h + o + n) for o, n in zip(offs, self.shape))
        sink = None
        if not any(offs):
            def sink(value, name=self.name):
                self._writes[name] = value
        return _Value(self.data[idx], lambda: self._reads.add(self.name),
                      sink)


class _Namespace:
    """Stands in for the DSL module while the kernel source is executed:
    only the annotations ``st.grid`` / ``st.f32`` are looked up."""
    grid = object()
    f32 = object()
    i32 = object()


class Kernel:
    """A DSL kernel evaluated with plain jnp on halo-padded arrays."""

    def __init__(self, source: str, order: int):
        ns = {"st": _Namespace}
        exec(compile(source, "<bench-config-kernel>", "exec", dont_inherit=True), ns)  # noqa: S102
        fns = [v for k, v in ns.items() if callable(v) and k != "st"
               and getattr(v, "__code__", None) is not None]
        if len(fns) != 1:
            raise ValueError("the kernel source must define exactly one function")
        self.fn = fns[0]
        self.source = source
        self.name = self.fn.__name__
        self.order = int(order)
        code = self.fn.__code__
        self.params = code.co_varnames[:code.co_argcount]
        ann = self.fn.__annotations__
        self.grid_params = tuple(p for p in self.params
                                 if ann.get(p) is _Namespace.grid)
        self.scalar_params = tuple(p for p in self.params
                                   if p not in self.grid_params)

    def apply(self, arrays: Mapping[str, jnp.ndarray],
              scalars: Mapping[str, object]):
        """One application: returns (new arrays, grids read, grids written)."""
        shape = None
        reads, writes = set(), {}
        views = {}
        for g in self.grid_params:
            a = arrays[g]
            shape = tuple(s - 2 * self.order for s in a.shape)
            views[g] = _GridView(g, a, self.order, shape, reads, writes)
        args = [views[p] if p in views else scalars[p] for p in self.params]
        self.fn(*args)
        out = dict(arrays)
        h = self.order
        idx = tuple(slice(h, h + n) for n in shape)
        for g, v in writes.items():
            a = arrays[g]
            out[g] = a.at[idx].set(jnp.asarray(v, a.dtype))
        return out, frozenset(reads), frozenset(writes)

    def access(self, shape: Sequence[int]) -> Tuple[frozenset, frozenset]:
        """(grids read, grids written) of one application, found by tracing
        it on abstract arrays of the interior ``shape``."""
        full = tuple(int(s) + 2 * self.order for s in shape)
        box = {}

        def probe(arrays, scalars):
            out, r, w = self.apply(arrays, scalars)
            box["rw"] = (r, w)
            return out

        jax.eval_shape(probe,
                       {g: jax.ShapeDtypeStruct(full, jnp.float32)
                        for g in self.grid_params},
                       {s: jax.ShapeDtypeStruct((), jnp.float32)
                        for s in self.scalar_params})
        return box["rw"]

    def flops_per_point(self) -> int:
        """Floating-point operations of one application per interior point:
        the elementwise arithmetic in its jaxpr (each covers the interior
        once; slices and the write are data movement)."""
        full = (2 * self.order + 1,) * 3
        arith = {"add", "sub", "mul", "div", "neg"}
        jaxpr = jax.make_jaxpr(lambda a, s: self.apply(a, s)[0])(
            {g: jax.ShapeDtypeStruct(full[:self._ndim()], jnp.float32)
             for g in self.grid_params},
            {s: jax.ShapeDtypeStruct((), jnp.float32)
             for s in self.scalar_params})
        return sum(1 for e in jaxpr.jaxpr.eqns if e.primitive.name in arith
                   and e.outvars[0].aval.shape)

    def _ndim(self) -> int:
        """Number of axes: the offsets of the first ``.at`` in the source."""
        m = re.search(r"\.at\(([^)]*)\)", self.source)
        return len(m.group(1).split(","))

    def compulsory_bytes_per_point(self, shape, itemsize: int = 4) -> int:
        """Bytes one step must move per interior point: every grid it reads
        read once, every grid it writes written once."""
        reads, writes = self.access(shape)
        return itemsize * (len(reads) + len(writes))


def ricker(t, f0: float, t0: float) -> float:
    """Ricker wavelet sample at integer step ``t`` (float64 on the host)."""
    a = (np.pi * f0 * (t - t0)) ** 2
    return float((1.0 - 2.0 * a) * np.exp(-a))


def leapfrog(kernel: Kernel, swap: Tuple[str, str], steps: int, *,
             dtype=jnp.float32, source: Optional[Mapping] = None,
             every: int = 1) -> Callable:
    """Jitted ``run(arrays, scalars) -> arrays``: ``steps`` applications of
    the kernel, the ``swap`` pair trading names after each.  With
    ``source`` (``grid``, ``index``, ``f0``, ``t0``, ``amp``) a Ricker sample
    is added to ``source['grid']`` before every step ``t`` with
    ``t % every == 0``, as a hook at the program's window boundaries does.
    ``dtype`` is the precision the whole computation runs in."""
    written, other = swap
    table = None
    if source is not None:
        table = np.array([source.get("amp", 1.0)
                          * ricker(t, source["f0"], source["t0"])
                          if t % every == 0 else 0.0
                          for t in range(steps)], np.float32)
        index = tuple(int(i) for i in source["index"])
        src_grid = source["grid"]

    def run(arrays, scalars):
        arrays = {g: jnp.asarray(a, dtype) for g, a in arrays.items()}
        scalars = {s: jnp.asarray(v, dtype) for s, v in scalars.items()}
        wav = None if table is None else jnp.asarray(table, dtype)

        def body(t, arrs):
            if wav is not None:
                arrs = dict(arrs)
                arrs[src_grid] = arrs[src_grid].at[index].add(wav[t])
            out, _, _ = kernel.apply(arrs, scalars)
            out[written], out[other] = out[other], out[written]
            return out
        out = jax.lax.fori_loop(0, steps, body, arrays)
        return {g: a.astype(jnp.float32) for g, a in out.items()}

    return jax.jit(run)


def misfit_gradient(kernel: Kernel, swap: Tuple[str, str], steps: int, *,
                    param: str, compare: Sequence[str], source: Mapping,
                    every: int, segment: int, dtype=jnp.float32) -> Callable:
    """Jitted ``f(arrays, scalars, observed) -> (misfit, gradient)``: the
    value and the gradient, with respect to the interior of grid ``param``,
    of ``0.5 · Σ_g Σ (final_g − observed_g)²`` over the interiors of the
    grids ``compare`` after ``steps`` leapfrog steps with the source added
    every ``every`` steps.  Reverse mode through a scan over checkpointed
    segments of ``segment`` steps keeps one carry per segment."""
    if steps % segment or segment % every:
        raise ValueError("segment must divide steps and be a multiple of every")
    written, other = swap
    h = kernel.order
    table = np.array([source.get("amp", 1.0)
                      * ricker(t, source["f0"], source["t0"])
                      if t % every == 0 else 0.0
                      for t in range(steps)], np.float32)
    index = tuple(int(i) for i in source["index"])
    src_grid = source["grid"]

    def value(inner, arrays, scalars, observed):
        arrays = {g: jnp.asarray(a, dtype) for g, a in arrays.items()}
        scalars = {s: jnp.asarray(v, dtype) for s, v in scalars.items()}
        idx = tuple(slice(h, h + n) for n in inner.shape)
        arrays[param] = arrays[param].at[idx].set(inner.astype(dtype))
        wav = jnp.asarray(table, dtype).reshape(steps // segment, segment)
        const = {g: a for g, a in arrays.items() if g not in swap}
        carry0 = {g: arrays[g] for g in swap}

        @jax.checkpoint
        def seg(carry, w):
            def body(i, c):
                arrs = dict(const)
                arrs.update(c)
                arrs[src_grid] = arrs[src_grid].at[index].add(w[i])
                out, _, _ = kernel.apply(arrs, scalars)
                return {written: out[other], other: out[written]}
            return jax.lax.fori_loop(0, segment, body, carry), None

        final, _ = jax.lax.scan(seg, carry0, wav)
        total = 0.0
        for g in compare:
            d = (final[g][idx].astype(jnp.float32)
                 - jnp.asarray(observed[g], jnp.float32)[idx])
            total = total + 0.5 * jnp.sum(d * d)
        return total

    grad = jax.value_and_grad(value)
    return jax.jit(grad)


def rel_err(got, want) -> float:
    """max |got − want| / max |want|, reduced on the device in float32 (the
    difference of two nearby float32 numbers is exact)."""
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    num = float(jnp.max(jnp.abs(got - want)))
    den = float(jnp.max(jnp.abs(want)))
    return num / max(den, 1e-30)


def interior(a, order: int):
    return a[tuple(slice(order, s - order) for s in a.shape)]


def arrays_rel_err(got: Dict[str, object], want: Dict[str, object],
                   grids: Sequence[str], order: int) -> float:
    """Largest ``rel_err`` over ``grids``, interiors only."""
    return max(rel_err(interior(got[g], order), interior(want[g], order))
               for g in grids)
