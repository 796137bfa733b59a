"""Field kind ``inclusion``: the field ``base`` made before it with a
sphere of squared velocity ``vp**2`` and radius ``radius`` (a share of the
smallest extent), whose centre lies within ``jitter`` (a share of each
extent) of the domain's centre."""
import jax
import jax.numpy as jnp


def make(p, shape, key, made):
    c = [n / 2 + p["jitter"] * n * u for n, u in
         zip(shape, jax.random.uniform(key, (len(shape),), jnp.float32,
                                       -1.0, 1.0))]
    r = p["radius"] * min(shape)
    d2 = sum(((jnp.arange(n, dtype=jnp.float32) - ci) ** 2).reshape(
        [n if a == ax else 1 for a in range(len(shape))])
        for ax, (n, ci) in enumerate(zip(shape, c)))
    return jnp.where(d2 < r * r, jnp.float32(p["vp"] ** 2), made[p["base"]])
