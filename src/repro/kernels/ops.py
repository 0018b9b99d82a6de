"""Public jit'd wrappers around the Pallas kernels.

Each wrapper does the host-side symbolic planning (NumPy), appends the zero-sentinel
blocks, and dispatches the pallas_call. ``interpret`` defaults to
``default_interpret()``: the interpreter on the CPU, a Mosaic compile on the TPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.sparse.bsr import BSR
from repro.kernels import bsr_spgemm as _spgemm
from repro.kernels import bsr_spmm as _spmm
from repro.kernels import grouped_matmul as _gmm
from repro.kernels import chunked_attention as _attn
from repro.kernels.ranged_spgemm import default_interpret


def _with_zero_block(blocks: jax.Array) -> jax.Array:
    """Append the guaranteed-zero sentinel block (slot index = old length)."""
    bs = blocks.shape[-1]
    return jnp.concatenate(
        [blocks, jnp.zeros((1, bs, bs), blocks.dtype)], axis=0
    )


def bsr_spgemm(A: BSR, B: BSR, meta: _spgemm.BsrSpgemmMeta | None = None,
               skip_zero: bool = True, interpret: bool | None = None) -> BSR:
    """C = A @ B as BSR with host-planned block structure."""
    if A.shape[1] != B.shape[0] or A.block_size != B.block_size:
        raise ValueError(f"incompatible operands {A.shape} x {B.shape}")
    meta = meta or _spgemm.bsr_spgemm_symbolic(A, B)
    interpret = default_interpret() if interpret is None else interpret
    blocks = _spgemm.bsr_spgemm_blocks(
        _with_zero_block(A.blocks),
        _with_zero_block(B.blocks),
        jnp.asarray(meta.a_slots),
        jnp.asarray(meta.b_slots),
        nc_pad=meta.nc_pad,
        u_max=meta.u_max,
        bs=A.block_size,
        out_dtype=jnp.float32,
        skip_zero=skip_zero,
        interpret=interpret,
    )
    per_row = meta.c_indptr[1:] - meta.c_indptr[:-1]
    return BSR(
        block_indptr=jnp.asarray(meta.c_indptr),
        block_indices=jnp.asarray(meta.c_indices),
        blocks=blocks,
        shape=(A.shape[0], B.shape[1]),
        block_size=A.block_size,
        max_row_blocks=int(per_row.max()) if per_row.size else 0,
    )


def bsr_spmm(A: BSR, x: jax.Array, meta: _spmm.BsrSpmmMeta | None = None,
             bn: int = 128, interpret: bool | None = None) -> jax.Array:
    """y = A @ x with dense x [A.shape[1], nf]."""
    if x.shape[0] != A.shape[1]:
        raise ValueError(f"incompatible {A.shape} @ {x.shape}")
    meta = meta or _spmm.bsr_spmm_symbolic(A)
    interpret = default_interpret() if interpret is None else interpret
    nf = x.shape[1]
    bn_eff = min(bn, nf)
    if nf % bn_eff:
        raise ValueError(f"nf={nf} not divisible by bn={bn_eff}")
    return _spmm.bsr_spmm_blocks(
        _with_zero_block(A.blocks),
        x,
        jnp.asarray(meta.a_slots),
        jnp.asarray(meta.a_cols),
        mb=A.mb,
        u_max=meta.u_max,
        bs=A.block_size,
        bn=bn_eff,
        interpret=interpret,
    )


def grouped_matmul(x: jax.Array, w: jax.Array, group_sizes,
                   bt: int = 128, bn: int = 128, bk: int = 128,
                   interpret: bool | None = None):
    """Ragged grouped GEMM over *unsorted-by-tile* data already grouped by expert:
    x rows [sum(group_sizes), K] laid out group-contiguously.

    Returns (y [T_pad, N], padded_offsets) where rows [padded_offsets[g],
    padded_offsets[g] + group_sizes[g]) of y hold group g's outputs.
    """
    interpret = default_interpret() if interpret is None else interpret
    offsets, tile_group, t_pad = _gmm.plan_groups(np.asarray(group_sizes), bt)
    kdim = x.shape[1]
    # scatter group-contiguous rows into padded layout
    sizes = np.asarray(group_sizes, np.int64)
    src_off = np.concatenate([[0], np.cumsum(sizes)])
    dst_rows = np.concatenate(
        [np.arange(sizes[g]) + offsets[g] for g in range(sizes.size)]
    ) if sizes.size else np.zeros(0, np.int64)
    xp = jnp.zeros((t_pad, kdim), x.dtype).at[jnp.asarray(dst_rows)].set(
        x[: int(src_off[-1])]
    )
    y = _gmm.grouped_matmul_padded(
        xp, w, jnp.asarray(tile_group), bt=bt, bn=bn, bk=bk, interpret=interpret
    )
    return y, offsets


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array, lengths: jax.Array,
                     bs_kv: int = 512, interpret: bool | None = None) -> jax.Array:
    interpret = default_interpret() if interpret is None else interpret
    return _attn.decode_attention(q, k, v, lengths, bs_kv=bs_kv, interpret=interpret)


def flash_prefill(q: jax.Array, k: jax.Array, v: jax.Array, bq: int = 256,
                  bk: int = 512, window: int = 0,
                  interpret: bool | None = None) -> jax.Array:
    from repro.kernels import flash_prefill as _fp

    interpret = default_interpret() if interpret is None else interpret
    return _fp.flash_prefill(q, k, v, bq=bq, bk=bk, window=window,
                             interpret=interpret)
