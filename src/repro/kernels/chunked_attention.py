"""Flash-decoding attention Pallas kernel: KV cache chunks streamed HBM->VMEM.

This is the paper's Chunk1 order applied to decode attention (DESIGN.md §4.2):
Q and the output accumulator are *stationary* in VMEM (they are tiny: one query
token per sequence), the big operand — the KV cache, which for 500k-token contexts
exceeds even HBM per chip — is *streamed* in (bs_kv x d) chunks with an online
softmax taking the place of the fused multiply-add accumulator.

GQA layout: q [B, Hkv, G, D] (G = query heads per KV head), K/V [B, S, Hkv, D].
Grid (B, Hkv, S/bs_kv); per-sequence valid length is scalar-prefetched and masks
the tail chunk. Running max m, denominator l, and the weighted value accumulator
live in VMEM scratch across the S-chunk loop.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bs_kv: int, n_chunks: int, scale: float):
    b = pl.program_id(0)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]            # [G, D]
    k = k_ref[0, :, 0]         # [bs_kv, D]
    v = v_ref[0, :, 0]         # [bs_kv, D]
    scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # [G, bs_kv]
    pos = s * bs_kv + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(pos < len_ref[b], scores, NEG_INF)

    m_prev = m_ref[...]                         # [G, 1]
    m_new = jnp.maximum(m_prev, scores.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)             # rescale of old accumulator
    p = jnp.exp(scores - m_new)                 # [G, bs_kv]
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v.astype(jnp.float32), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(s == n_chunks - 1)
    def _flush():
        o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array, lengths: jax.Array,
                     bs_kv: int = 512, interpret: bool = False) -> jax.Array:
    """q: [B, Hkv, G, D]; k, v: [B, S, Hkv, D]; lengths: int32[B]. Returns
    [B, Hkv, G, D]."""
    bsz, hkv, g, d = q.shape
    _, s_len, _, _ = k.shape
    assert s_len % bs_kv == 0, f"S={s_len} not divisible by bs_kv={bs_kv}"
    n_chunks = s_len // bs_kv
    scale = 1.0 / (d ** 0.5)
    grid = (bsz, hkv, n_chunks)
    return pl.pallas_call(
        functools.partial(_kernel, bs_kv=bs_kv, n_chunks=n_chunks, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, g, d), lambda b, h, s, ln: (b, h, 0, 0)),
                pl.BlockSpec((1, bs_kv, 1, d), lambda b, h, s, ln: (b, s, h, 0)),
                pl.BlockSpec((1, bs_kv, 1, d), lambda b, h, s, ln: (b, s, h, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, g, d), lambda b, h, s, ln: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((bsz, hkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q, k, v)
