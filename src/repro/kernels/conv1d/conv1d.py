"""Causal depthwise 1-D convolution Pallas kernel (temporal stencil).

Used by the Griffin/RecurrentGemma recurrent block and demonstrating the
Whisper conv-stem op.  Structure mirrors the stencil codegen's
neighbor-block scheme: the time axis is blocked and each output block reads
its own block plus the previous one (causal halo = conv_width − 1).
Weights are runtime values (learned), which is why this kernel is built
directly rather than through the literal-coefficient DSL.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu



def _kernel(prev_ref, cur_ref, w_ref, out_ref, *, cw: int, bt: int):
    prev = prev_ref[...]
    cur = cur_ref[...]
    w = w_ref[...]
    hist = prev[:, bt - (cw - 1):] if cw > 1 else cur[:, :0]
    x = jnp.concatenate([hist, cur], axis=1) if cw > 1 else cur
    acc = jnp.zeros_like(cur)
    for k in range(cw):
        acc = acc + x[:, k:k + bt] * w[k][None, None, :]
    out_ref[...] = acc


def causal_conv1d_pallas(x: jnp.ndarray, w: jnp.ndarray, *,
                         block_t: int = 128, block_w: int = 128,
                         interpret: bool) -> jnp.ndarray:
    """x: [B, T, W]; w: [cw, W] → causal depthwise conv, same length
    (zero history)."""
    B, T, W = x.shape
    cw = w.shape[0]
    bt = min(block_t, -(-T // 8) * 8)
    bt = max(bt, cw - 1, 1)  # causal halo must fit in one previous block
    bw = min(block_w, W)
    nT = -(-T // bt)
    nW = -(-W // bw)
    Tp, Wp = nT * bt, nW * bw
    xp = jnp.pad(x, ((0, 0), (bt, Tp - T), (0, Wp - W)))  # 1 halo block front
    wp = jnp.pad(w, ((0, 0), (0, Wp - W)))

    out = pl.pallas_call(
        functools.partial(_kernel, cw=cw, bt=bt),
        grid=(B, nT, nW),
        in_specs=[
            pl.BlockSpec((1, bt, bw), lambda b, t, c: (b, t, c)),      # prev
            pl.BlockSpec((1, bt, bw), lambda b, t, c: (b, t + 1, c)),  # cur
            pl.BlockSpec((cw, bw), lambda b, t, c: (0, c)),
        ],
        out_specs=pl.BlockSpec((1, bt, bw), lambda b, t, c: (b, t, c)),
        out_shape=jax.ShapeDtypeStruct((B, Tp, Wp), x.dtype),
        interpret=interpret,
        name="causal_conv1d",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
    )(xp, xp, wp)   # padded array feeds both the prev- and cur-block refs
    return out[:, :T, :W]
