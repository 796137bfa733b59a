"""Field kind ``layered``: the squared velocity of a layered medium.  The
velocity varies with depth (axis 0) by up to ``±spread`` around ``vp``, one
uniform draw per layer."""
import jax
import jax.numpy as jnp


def make(p, shape, key, made):
    vp = p["vp"] * (1.0 + p["spread"] * jax.random.uniform(
        key, (shape[0],), jnp.float32, -1.0, 1.0))
    vp2 = (vp * vp).reshape((-1,) + (1,) * (len(shape) - 1))
    return jnp.broadcast_to(vp2, shape).astype(jnp.float32)
