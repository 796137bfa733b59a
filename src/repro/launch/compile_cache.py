"""Persistent XLA compilation cache for the repo's entry points.

Only scripts call ``enable_compile_cache`` (``chip_smoke.py``, the
examples, the benchmark mains, ``launch/serve.py`` and ``launch/train.py``),
never library code, so importing ``repro`` or running the tests leaves
the cache off.
"""
from __future__ import annotations

import os

import jax

#: fixed cache location inside the checkout; the path is part of what the
#: cache is keyed by, so it never depends on a temporary name, a process
#: id or the time
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is what JAX uses and no other
    directory is set; otherwise the cache lives at ``DEFAULT_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
