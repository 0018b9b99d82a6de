"""Ragged grouped GEMM Pallas kernel — MoE expert compute as chunked block-sparse
matmul (the production descendant of the paper's technique; DESIGN.md §4.1).

y[t] = x[t] @ w[g(t)] for tokens pre-sorted by group (expert), with each group's
token count padded to a multiple of the token tile ``bt`` so no tile straddles two
groups. The per-tile group id is scalar-prefetched; the expert weight chunk
(bk x bn of w[g]) streams HBM->VMEM per grid step — the Chunk2 order (weights
streamed, activations stationary per tile).

Grid: (T/bt, N/bn, K/bk), accumulator in VMEM scratch over the K loop.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(gid_ref, x_ref, w_ref, out_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def grouped_matmul_padded(x: jax.Array, w: jax.Array, tile_group: jax.Array,
                          bt: int = 128, bn: int = 128, bk: int = 128,
                          out_dtype=None, interpret: bool = False) -> jax.Array:
    """x: [T_pad, K] tokens sorted+padded by group; w: [E, K, N];
    tile_group: int32[T_pad // bt] group id per token tile. Returns [T_pad, N]."""
    t_pad, kdim = x.shape
    _, _, ndim = w.shape
    assert t_pad % bt == 0 and kdim % bk == 0 and ndim % bn == 0, (
        f"shapes ({t_pad},{kdim},{ndim}) not divisible by tiles ({bt},{bk},{bn})"
    )
    nk = kdim // bk
    grid = (t_pad // bt, ndim // bn, nk)
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bt, bk), lambda t, n, k, g: (t, k)),
                pl.BlockSpec((1, bk, bn), lambda t, n, k, g: (g[t], k, n)),
            ],
            out_specs=pl.BlockSpec((bt, bn), lambda t, n, k, g: (t, n)),
            scratch_shapes=[pltpu.VMEM((bt, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((t_pad, ndim), out_dtype or x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(tile_group, x, w)


def plan_groups(group_sizes: np.ndarray, bt: int):
    """Host-side plan: padded offsets + per-tile group ids for ragged groups.

    Returns (padded_offsets[E+1], tile_group[T_pad//bt], t_pad)."""
    sizes = np.asarray(group_sizes, np.int64)
    padded = -(-sizes // bt) * bt
    offsets = np.concatenate([[0], np.cumsum(padded)])
    t_pad = int(offsets[-1])
    tile_group = np.repeat(np.arange(sizes.size, dtype=np.int32), padded // bt)
    return offsets.astype(np.int64), tile_group, max(t_pad, bt)
