"""BSR x BSR SpGEMM Pallas kernel — the paper's chunked numeric phase, TPU-native.

Mapping from the paper (DESIGN.md §2):
  * fast memory  = VMEM; slow memory = HBM.
  * `copy2Fast`  = the Pallas pipeline: each grid step DMAs one (bs x bs) block of A
    and B into VMEM while the MXU works on the previous pair (double-buffering — the
    paper's "future work" — is native here).
  * hashmap accumulator -> dense (bs x bs) fp32 VMEM scratch tile per C block.
  * "skip columns of A outside the range" -> scalar-prefetched (SMEM) slot tables:
    the index_map only ever schedules contributing blocks; padding slots point at a
    guaranteed all-zero block so the pipeline stays branch-free.

Grid: (n_c_blocks_pad, U) where U = max contributors (k-blocks) to any C block.
Work is proportional to nnz-blocks of C — entry-level sparsity inside a block is
given up in exchange for MXU-shaped dense tiles (the TPU trade the paper's GPU
hashmaps cannot make).

The symbolic phase (host, NumPy) is KKMEM's compression in block form: C's block
structure is the union of B's block-rows selected by A's block-columns.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import scoped_vmem
from repro.sparse.bsr import BSR


@dataclasses.dataclass(frozen=True)
class BsrSpgemmMeta:
    """Host-computed symbolic structure of C = A x B at block granularity."""

    c_indptr: np.ndarray     # int32[mb + 1]
    c_indices: np.ndarray    # int32[nc_pad]
    a_slots: np.ndarray      # int32[nc_pad, U]  (zero-sentinel = A's appended zero block)
    b_slots: np.ndarray      # int32[nc_pad, U]
    n_c_blocks: int
    nc_pad: int
    u_max: int
    flops: int               # 2 * bs^3 * total contributor pairs (MXU flops)


def bsr_spgemm_symbolic(A: BSR, B: BSR, pad_multiple: int = 8,
                        nc_pad: int | None = None,
                        u_max: int | None = None) -> BsrSpgemmMeta:
    """Block-level symbolic phase: structure of C and contributor slot tables.

    The zero-sentinel slot is ``A.nbl_pad`` / ``B.nbl_pad`` — the wrapper appends one
    guaranteed-zero block to each blocks array before the pallas_call.

    ``nc_pad`` / ``u_max``, when given, are envelope-level *floors* (from
    ``repro.core.symbolic.bsr_plan_caps``): the tables are shaped to them so
    every (strip, chunk) pair under one envelope compiles to one kernel
    geometry. A realized structure exceeding a floor raises ``ValueError``
    loudly — the kernel would otherwise drop contributor pairs (table
    columns past ``u_max``) or C blocks (rows past ``nc_pad``) silently.
    """
    a_ptr = np.asarray(A.block_indptr, np.int64)
    a_idx = np.asarray(A.block_indices, np.int64)
    b_ptr = np.asarray(B.block_indptr, np.int64)
    b_idx = np.asarray(B.block_indices, np.int64)
    mb = A.mb
    n_a = int(a_ptr[-1])
    a_rows = np.repeat(np.arange(mb, dtype=np.int64), a_ptr[1:] - a_ptr[:-1])
    a_cols = a_idx[:n_a]
    a_slot = np.arange(n_a, dtype=np.int64)
    # fan each A block out over B's block-row a_cols[s]
    lens = b_ptr[a_cols + 1] - b_ptr[a_cols]
    total = int(lens.sum())
    cum = np.concatenate([[0], np.cumsum(lens)])
    p = np.arange(total, dtype=np.int64)
    t = np.searchsorted(cum, p, side="right") - 1
    pos_in_row = p - cum[t]
    pair_a_slot = a_slot[t]
    pair_b_slot = b_ptr[a_cols[t]] + pos_in_row
    pair_i = a_rows[t]
    pair_j = b_idx[pair_b_slot]
    # group pairs by C block (i, j)
    key = pair_i * np.int64(B.nb) + pair_j
    order = np.argsort(key, kind="stable")
    key_s = key[order]
    uniq, start = np.unique(key_s, return_index=True)
    counts = np.diff(np.concatenate([start, [total]]))
    n_c = int(uniq.size)
    u = int(counts.max()) if n_c else 1
    if u_max is None:
        u_max = u
    elif u > u_max:
        raise ValueError(
            f"u_max={u_max} < realized contributor count {u}: the envelope's "
            f"block caps do not dominate this instance — rebuild the envelope "
            f"(bsr_plan_caps) from the instances it serves"
        )
    if nc_pad is None:
        nc_pad = -(-max(n_c, 1) // pad_multiple) * pad_multiple
    elif n_c > nc_pad:
        raise ValueError(
            f"nc_pad={nc_pad} < realized C block count {n_c}: the envelope's "
            f"block caps do not dominate this instance — rebuild the envelope "
            f"(bsr_plan_caps) from the instances it serves"
        )
    a_zero, b_zero = A.nbl_pad, B.nbl_pad  # appended zero-block slots
    a_tab = np.full((nc_pad, u_max), a_zero, np.int32)
    b_tab = np.full((nc_pad, u_max), b_zero, np.int32)
    # scatter contributor slots into the per-C-block tables
    grp = np.repeat(np.arange(n_c), counts)
    col = p - np.repeat(start, counts)  # position within group (pairs are sorted)
    a_tab[grp, col] = pair_a_slot[order].astype(np.int32)
    b_tab[grp, col] = pair_b_slot[order].astype(np.int32)
    c_i = (uniq // B.nb).astype(np.int64)
    c_j = (uniq % B.nb).astype(np.int32)
    c_indptr = np.zeros(mb + 1, np.int64)
    np.add.at(c_indptr, c_i + 1, 1)
    c_indptr = np.cumsum(c_indptr).astype(np.int32)
    c_indices = np.zeros(nc_pad, np.int32)
    c_indices[:n_c] = c_j
    # padding invariants consumers rely on: c_indptr spans exactly the n_c
    # real blocks (so a scatter driven by it can never touch a padding row),
    # and padding table rows are all-sentinel (their grid steps MAC nothing,
    # flushing a zero tile). c_indices past n_c stays 0 — aliasing real block
    # (i, 0) if a consumer scattered the padded tail, which is why every
    # consumer must crop the kernel output to n_c_blocks first.
    assert int(c_indptr[-1]) == n_c, (c_indptr[-1], n_c)
    assert (a_tab[n_c:] == a_zero).all() and (b_tab[n_c:] == b_zero).all()
    return BsrSpgemmMeta(
        c_indptr=c_indptr,
        c_indices=c_indices,
        a_slots=a_tab,
        b_slots=b_tab,
        n_c_blocks=n_c,
        nc_pad=nc_pad,
        u_max=u_max,
        flops=2 * (A.block_size ** 3) * total,
    )


def _kernel(a_slots_ref, b_slots_ref, a_blocks_ref, b_blocks_ref, out_ref, acc_ref,
            *, u_max: int, skip_zero: bool, a_zero_slot: int):
    """One (C block e, contributor u) step: acc += A_blk @ B_blk."""
    u = pl.program_id(1)

    @pl.when(u == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # HIGHEST: on the TPU the default f32 matmul rounds its operands to
    # bf16, which thins the product's structure where terms cancel
    if skip_zero:
        e = pl.program_id(0)
        valid = a_slots_ref[e, u] != a_zero_slot

        @pl.when(valid)
        def _mac():
            acc_ref[...] += jnp.dot(
                a_blocks_ref[0], b_blocks_ref[0], preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            )
    else:
        acc_ref[...] += jnp.dot(
            a_blocks_ref[0], b_blocks_ref[0], preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )

    @pl.when(u == u_max - 1)
    def _flush():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def bsr_spgemm_blocks(a_blocks: jax.Array, b_blocks: jax.Array, a_slots: jax.Array,
                      b_slots: jax.Array, nc_pad: int, u_max: int, bs: int,
                      out_dtype=jnp.float32, skip_zero: bool = True,
                      interpret: bool = False) -> jax.Array:
    """Run the kernel. ``a_blocks``/``b_blocks`` must already carry the appended
    zero block at index nbl_pad (i.e. shapes (nbl_pad + 1, bs, bs))."""
    a_zero_slot = a_blocks.shape[0] - 1
    grid = (nc_pad, u_max)
    kernel = functools.partial(
        _kernel, u_max=u_max, skip_zero=skip_zero, a_zero_slot=a_zero_slot
    )
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (1, bs, bs), lambda e, u, a_s, b_s: (a_s[e, u], 0, 0)
                ),
                pl.BlockSpec(
                    (1, bs, bs), lambda e, u, a_s, b_s: (b_s[e, u], 0, 0)
                ),
            ],
            out_specs=pl.BlockSpec((1, bs, bs), lambda e, u, a_s, b_s: (e, 0, 0)),
            scratch_shapes=[pltpu.VMEM((bs, bs), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((nc_pad, bs, bs), out_dtype),
        compiler_params=scoped_vmem.compiler_params(
            blocked=[((1, bs, bs), a_blocks.dtype), ((1, bs, bs), b_blocks.dtype),
                     ((1, bs, bs), out_dtype)],
            scratch=[((bs, bs), jnp.float32)],
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a_slots, b_slots, a_blocks, b_blocks)
