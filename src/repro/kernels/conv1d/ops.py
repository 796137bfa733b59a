"""jit'd wrapper for the causal conv1d kernel."""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax

from repro.kernels import resolve_interpret

from .conv1d import causal_conv1d_pallas


def causal_conv1d(x, w, interpret: Optional[bool] = None):
    """Causal depthwise conv1d; ``interpret=None`` follows the platform
    (``repro.kernels.resolve_interpret``)."""
    return _causal_conv1d(x, w, interpret=resolve_interpret(interpret))


@partial(jax.jit, static_argnames=("interpret",))
def _causal_conv1d(x, w, interpret: bool):
    return causal_conv1d_pallas(x, w, interpret=interpret)
