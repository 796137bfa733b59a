"""Field kind ``normal``: independent normal values of standard deviation
``scale``."""
import jax
import jax.numpy as jnp


def make(p, shape, key, made):
    return p.get("scale", 1.0) * jax.random.normal(key, shape, jnp.float32)
