"""Flash-attention prefill Pallas kernel: the training/prefill twin of
chunked_attention.py.

Grid (B, Hkv, nQ, nK) with the K loop innermost ("arbitrary"): for each Q block
the online-softmax state (m, l, acc) lives in VMEM scratch across K steps while
(bq x d) Q stays resident and (bk x d) KV blocks stream HBM->VMEM — the paper's
Chunk1 order. Causality is enforced two ways:
  * whole KV blocks strictly in the future are SKIPPED via pl.when (no MXU work
    — the Pallas analogue of "skip columns of A outside the range"), and
  * the diagonal block is masked elementwise.
Sliding windows additionally skip blocks entirely behind the window.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bq: int, bk: int, n_k: int, window: int, scale: float, g: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q0 = qi * bq
    k0 = ki * bk
    # visible iff the block intersects the causal (and window) band
    visible = k0 <= q0 + bq - 1
    if window:
        visible = visible & (k0 + bk - 1 > q0 - window)

    @pl.when(visible)
    def _step():
        q = q_ref[0, :, 0].astype(jnp.float32)     # [bq*g, d] (g folded into rows)
        k = k_ref[0, :, 0]                          # [bk, d]
        v = v_ref[0, :, 0]
        s = jax.lax.dot_general(
            q, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq*g, bk]
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // g
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = qpos >= kpos
        if window:
            mask = mask & (qpos - kpos < window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _flush():
        o_ref[0, :, 0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


def flash_prefill(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  bq: int = 256, bk: int = 512, window: int = 0,
                  interpret: bool = False) -> jax.Array:
    """q: [B, S, H, D]; k, v: [B, S, Hkv, D] -> [B, S, H, D]. Causal.

    GQA is handled by folding the q-heads-per-kv-head factor g into the Q-block
    rows ([bq*g, d] tiles), so every kernel instance is a plain matmul pair."""
    b, s, h, d = q.shape
    _, sk, hkv, _ = k.shape
    g = h // hkv
    assert s % bq == 0 and sk % bk == 0, (s, bq, sk, bk)
    n_q, n_k = s // bq, sk // bk
    scale = 1.0 / (d ** 0.5)
    # [B, S, Hkv, g, D] -> [B, nq*(bq*g), Hkv, D] with q-position major
    qr = (q.reshape(b, s, hkv, g, d)
           .transpose(0, 2, 1, 3, 4)           # [B, Hkv, S, g, D]
           .reshape(b, hkv, s * g, d)
           .transpose(0, 2, 1, 3))             # [B, S*g, Hkv, D]
    grid = (b, hkv, n_q, n_k)
    out = pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, n_k=n_k, window=window,
                          scale=scale, g=g),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq * g, 1, d), lambda bi, hi, qi, ki: (bi, qi, hi, 0)),
            pl.BlockSpec((1, bk, 1, d), lambda bi, hi, qi, ki: (bi, ki, hi, 0)),
            pl.BlockSpec((1, bk, 1, d), lambda bi, hi, qi, ki: (bi, ki, hi, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq * g, 1, d),
                               lambda bi, hi, qi, ki: (bi, qi, hi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s * g, hkv, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq * g, 1), jnp.float32),
            pltpu.VMEM((bq * g, 1), jnp.float32),
            pltpu.VMEM((bq * g, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(qr, k, v)
    # [B, S*g, Hkv, D] -> [B, S, H, D]
    return (out.transpose(0, 2, 1, 3)
               .reshape(b, hkv, s, g, d)
               .transpose(0, 2, 1, 3, 4)
               .reshape(b, s, h, d))
