"""Field kind ``modes``: a wavefield in motion, nonzero up to the domain's
edges.  It is the sum of ``count`` standing waves, each a product of one
sine per axis with a whole number of half-wavelengths from 1 to
``max_order`` across the extent and a random phase, with a normal
amplitude of standard deviation ``scale / sqrt(count)``."""
import math

import jax
import jax.numpy as jnp


def make(p, shape, key, made):
    n = int(p["count"])
    ko, kp, ka = jax.random.split(key, 3)
    orders = jax.random.randint(ko, (n, len(shape)), 1,
                                int(p["max_order"]) + 1).astype(jnp.float32)
    phases = jax.random.uniform(kp, (n, len(shape)), jnp.float32,
                                0.0, 2 * math.pi)
    amps = p["scale"] / math.sqrt(n) * jax.random.normal(ka, (n,),
                                                         jnp.float32)
    total = jnp.zeros(shape, jnp.float32)
    for i in range(n):
        term = amps[i]
        for ax, m in enumerate(shape):
            x = jnp.arange(m, dtype=jnp.float32) + 0.5
            s = jnp.sin(math.pi * orders[i, ax] * x / m + phases[i, ax])
            term = term * s.reshape([m if a == ax else 1
                                     for a in range(len(shape))])
        total = total + term
    return total
