"""Flash decode attention as a Pallas TPU kernel (§Perf pair-2 'next
target'): one query token per sequence against a long KV cache, streamed
in sequence blocks with an online softmax — the cache is read exactly
once from HBM (the analytic decode floor), never materialized expanded or
transposed.

Grid: (B, S/bs) with the sequence dimension 'arbitrary' — the VMEM scratch
(running max m, normalizer l, accumulator acc in f32) persists across the
sequence steps of one batch row and is reset at s == 0; the final step
writes the normalized output block.  GQA handled by reshaping q to
[K, G, hd] so KV blocks are used directly (no head expansion).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu



def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, bs: int, scale: float):
    b = pl.program_id(0)
    s = pl.program_id(1)
    n_s = pl.num_programs(1)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                       # [K, G, hd] (pre-reshaped)
    kb = k_ref[0]                      # [bs, K, hd]
    vb = v_ref[0]
    length = len_ref[b]

    logits = jax.lax.dot_general(
        q.astype(jnp.float32), kb.astype(jnp.float32),
        (((2,), (2,)), ((0,), (1,)))) * scale        # [K, G, bs]
    pos = s * bs + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bs), 2)
    logits = jnp.where(pos < length, logits, -jnp.inf)

    m_prev = m_ref[...]                               # [K, G]
    m_new = jnp.maximum(m_prev, logits.max(axis=-1))
    # guard fully-masked blocks: exp(-inf - -inf) → use finite stand-in
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(logits - m_safe[..., None])           # [K, G, bs]
    p = jnp.where(pos < length, p, 0.0)
    corr = jnp.where(jnp.isfinite(m_prev),
                     jnp.exp(m_prev - m_safe), 0.0)   # [K, G]
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
    pv = jax.lax.dot_general(
        p, vb.astype(jnp.float32),
        (((2,), (0,)), ((0,), (1,))))                 # [K, G, hd]
    acc_ref[...] = acc_ref[...] * corr[..., None] + pv
    m_ref[...] = m_new

    @pl.when(s == n_s - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


def decode_attention_pallas(q, k, v, lengths, *, block_s: int = 512,
                            interpret: bool):
    """q: [B,H,hd]; k,v: [B,S,K,hd]; lengths: [B] → [B,H,hd]."""
    B, H, hd = q.shape
    S, K = k.shape[1], k.shape[2]
    G = H // K
    scale = hd ** -0.5
    bs = min(block_s, S)
    n_s = -(-S // bs)
    Sp = n_s * bs
    if Sp != S:
        k = jnp.pad(k, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    qg = q.reshape(B, K, G, hd)

    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs, scale=scale),
        grid=(B, n_s),
        in_specs=[
            pl.BlockSpec((B,), lambda b, s: (0,)),            # lengths
            pl.BlockSpec((1, K, G, hd), lambda b, s: (b, 0, 0, 0)),
            pl.BlockSpec((1, bs, K, hd), lambda b, s: (b, s, 0, 0)),
            pl.BlockSpec((1, bs, K, hd), lambda b, s: (b, s, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, K, G, hd), lambda b, s: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((K, G), jnp.float32),          # running max
            pltpu.VMEM((K, G), jnp.float32),          # normalizer
            pltpu.VMEM((K, G, hd), jnp.float32),      # accumulator
        ],
        interpret=interpret,
        name="flash_decode_attention",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(lengths, qg, k, v)
    return out.reshape(B, H, hd)
