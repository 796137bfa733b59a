"""Field kind ``pml``: the damping of a quadratic absorbing layer ``width``
cells thick on every face, rising to ``strength`` at the domain's edge and
zero in the inner region."""
import jax.numpy as jnp


def make(p, shape, key, made):
    w = max(int(p["width"]), 1)
    total = jnp.zeros(shape, jnp.float32)
    for ax, n in enumerate(shape):
        x = jnp.arange(n, dtype=jnp.float32)
        d = jnp.maximum(w - x, 0) + jnp.maximum(x - (n - 1 - w), 0)
        prof = p["strength"] * (d / w) ** 2
        total = total + prof.reshape([n if a == ax else 1
                                      for a in range(len(shape))])
    return total
