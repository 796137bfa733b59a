"""jit'd wrapper for the flash decode-attention kernel."""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax

from repro.kernels import resolve_interpret

from .decode_attn import decode_attention_pallas


def decode_attention(q, k, v, lengths, block_s: int = 512,
                     interpret: Optional[bool] = None):
    """Flash decode attention; ``interpret=None`` follows the platform
    (``repro.kernels.resolve_interpret``)."""
    return _decode_attention(q, k, v, lengths, block_s=block_s,
                             interpret=resolve_interpret(interpret))


@partial(jax.jit, static_argnames=("block_s", "interpret"))
def _decode_attention(q, k, v, lengths, block_s: int, interpret: bool):
    return decode_attention_pallas(q, k, v, lengths, block_s=block_s,
                                   interpret=interpret)
