"""Field kinds draw the same field from the same key and a different one
from another; a shot's start (``modes``) carries signal up to the domain's
edges, so the check compares the absorbing layer and the edges too."""
import glob
import os
import sys

import pytest

import benchtest

sys.path.insert(0, benchtest.BENCH)
import cells  # noqa: E402

KINDS = sorted(os.path.basename(p)[:-3] for p in
               glob.glob(os.path.join(benchtest.BENCH, "fields", "*.py")))
PARAMS = {"normal": {"scale": 2.0}, "layered": {"vp": 1.4, "spread": 0.05},
          "pml": {"width": 3, "strength": 0.2},
          "inclusion": {"base": "b", "radius": 0.2, "vp": 2.0,
                        "jitter": 0.05},
          "modes": {"count": 8, "max_order": 8, "scale": 1.0}}


@pytest.mark.parametrize("kind", KINDS)
def test_field_kind_is_drawn_from_its_key(kind):
    import jax
    import jax.numpy as jnp
    make = cells.load_module("fields", kind).make
    shape = (12, 10, 16)
    made = {"b": jnp.ones(shape, jnp.float32)}
    a = make(PARAMS.get(kind, {}), shape, jax.random.key(3), made)
    b = make(PARAMS.get(kind, {}), shape, jax.random.key(3), made)
    assert a.shape == shape and a.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(a))) and bool(jnp.all(a == b))
    if kind in ("normal", "modes", "layered", "inclusion"):
        c = make(PARAMS[kind], shape, jax.random.key(4), made)
        assert not bool(jnp.all(a == c))


def test_modes_reach_every_face():
    import jax
    import jax.numpy as jnp
    make = cells.load_module("fields", "modes").make
    a = make(PARAMS["modes"], (16, 16, 128), jax.random.key(11), {})
    scale = float(jnp.max(jnp.abs(a)))
    for ax in range(3):
        for end in (0, -1):
            face = jnp.take(a, end, axis=ax)
            assert float(jnp.max(jnp.abs(face))) > 0.05 * scale, (ax, end)
