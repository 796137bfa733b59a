"""Pallas kernels: the generated stencil kernels and two LM kernels.

Every Pallas entry point takes ``interpret=None`` and resolves it here, so
the execution mode follows the platform: compiled Mosaic on a TPU, the
Pallas interpreter only where JAX finds no TPU.
"""
from __future__ import annotations

from typing import Optional

import jax


def on_tpu() -> bool:
    """Whether JAX's default backend is a TPU."""
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` → interpret exactly when no TPU is present.  An explicit
    ``False`` off-TPU raises: a compiled kernel cannot run there, and
    falling back to the interpreter would hide it."""
    if interpret is None:
        return not on_tpu()
    if not interpret:
        require_tpu()
    return bool(interpret)


def require_tpu() -> None:
    """Raise unless compiled (``interpret=False``) Pallas kernels can run
    on the default backend."""
    if not on_tpu():
        raise ValueError(
            "interpret=False runs Pallas kernels compiled by Mosaic, which "
            f"needs a TPU; JAX's default backend is "
            f"'{jax.default_backend()}' (leave interpret=None to follow "
            "the platform)")
