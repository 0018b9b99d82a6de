"""BSR x dense SpMM Pallas kernel (paper's SpMM comparison point, Zheng et al. [24]).

Y = A_bsr @ X with X dense. Grid (mb, nf_tiles, U): each step stages one (bs x bs)
A block and the matching (bs x bn) X row-slab into VMEM; dense accumulation in a
VMEM scratch tile. Scalar-prefetched per-block-row slot/column tables realize the
"skip empty blocks" logic; padding points at the appended zero block, whose column
table entry 0 makes the X fetch harmless.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


from repro.sparse.bsr import BSR


@dataclasses.dataclass(frozen=True)
class BsrSpmmMeta:
    a_slots: np.ndarray   # int32[mb, U] -> index into A.blocks (zero sentinel = nbl_pad)
    a_cols: np.ndarray    # int32[mb, U] -> block-column of that slot (sentinel -> 0)
    u_max: int
    flops: int


def bsr_spmm_symbolic(A: BSR) -> BsrSpmmMeta:
    a_ptr = np.asarray(A.block_indptr, np.int64)
    a_idx = np.asarray(A.block_indices, np.int64)
    mb = A.mb
    lens = a_ptr[1:] - a_ptr[:-1]
    u_max = int(lens.max()) if mb else 1
    u_max = max(u_max, 1)
    slots = np.full((mb, u_max), A.nbl_pad, np.int32)
    cols = np.zeros((mb, u_max), np.int32)
    for i in range(mb):
        s, e = int(a_ptr[i]), int(a_ptr[i + 1])
        slots[i, : e - s] = np.arange(s, e, dtype=np.int32)
        cols[i, : e - s] = a_idx[s:e]
    return BsrSpmmMeta(a_slots=slots, a_cols=cols, u_max=u_max,
                       flops=2 * int(lens.sum()) * A.block_size ** 2)


def _kernel(a_slots_ref, a_cols_ref, a_blocks_ref, x_ref, out_ref, acc_ref, *,
            u_max: int):
    u = pl.program_id(2)

    @pl.when(u == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_blocks_ref[0], x_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(u == u_max - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def bsr_spmm_blocks(a_blocks: jax.Array, x: jax.Array, a_slots: jax.Array,
                    a_cols: jax.Array, mb: int, u_max: int, bs: int, bn: int,
                    out_dtype=jnp.float32, interpret: bool = False) -> jax.Array:
    """Y[mb*bs, nf] = A @ X. ``a_blocks`` carries the appended zero block."""
    nf = x.shape[1]
    grid = (mb, nf // bn, u_max)
    return pl.pallas_call(
        functools.partial(_kernel, u_max=u_max),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bs, bs), lambda i, j, u, sl, co: (sl[i, u], 0, 0)),
                pl.BlockSpec((bs, bn), lambda i, j, u, sl, co: (co[i, u], j)),
            ],
            out_specs=pl.BlockSpec((bs, bn), lambda i, j, u, sl, co: (i, j)),
            scratch_shapes=[pltpu.VMEM((bs, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((mb * bs, nf), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a_slots, a_cols, a_blocks, x)
