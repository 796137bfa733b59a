"""Field kind ``zeros``: a grid at rest."""
import jax.numpy as jnp


def make(p, shape, key, made):
    return jnp.zeros(shape, jnp.float32)
