"""KKMEM-style two-phase SpGEMM in pure JAX (the paper's baseline, §2.1).

KKMEM assigns rows of A to threads and multiplications within a row to vector lanes,
accumulating into sparse hashmap accumulators. A scalar hashmap has no efficient
SIMD/XLA analogue, so the TPU/JAX-idiomatic equivalent keeps the *two-phase*
row-wise structure but realizes the accumulator as **sort + segment-reduce** over the
expanded product stream — the same multiset-union semantics, fully vectorized:

  expand:     every nonzero a_ik fans out into products with B's row k
              (the access pattern of Fig. 1 — A streamed, B's rows gathered)
  accumulate: one sort on the (row, col) key, carrying the values, brings duplicate
              products together; a segmented sum coalesces them (== hashmap
              insert) and a second sort compacts the run totals into C

Shapes are static: the product buffer has capacity nnzA_pad x B.max_row_nnz, the
output CSR has a caller-provided capacity from the symbolic phase. Everything here
jits and vmaps cleanly. Operands are canonical CSR (no (row, col) stored twice):
the segmented sum has a fixed depth that holds only then, and the host symbolic
phase, which sizes every call, refuses other operands.

``spgemm_ranged`` is the paper's *modified KKMEM sub-procedure* used by the chunked
algorithms: it multiplies only the columns of A inside a B-row-range [r0, r1)
("skip any columns of A outside of this range" — §3.2.2) and *fuses the previous
partial C into the accumulation* ("inserts the existing values of C^1 into its
hashmap accumulators"), i.e. C^t = A_t x B_t + C^{t-1}.
"""

from __future__ import annotations

import collections
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.sparse.csr import CSR, csr_row_of_entry

# Python-side trace counters: each key increments once per (re)trace of the
# corresponding jitted wrapper / scan body (repro.core.chunk_stream), and the
# ESC body bumps the key of the sort-key layout it compiled. Tests assert
# these stay O(1) in the chunk count — the whole point of the single-trace
# executors.
TRACE_COUNTS: collections.Counter = collections.Counter()


@dataclasses.dataclass(frozen=True)
class SpGEMMWorkspace:
    """Output of the symbolic phase: static capacities for the numeric phase."""

    c_nnz: int          # exact nnz of C
    c_pad: int          # padded capacity (>= c_nnz)
    c_max_row_nnz: int  # densest row of C
    flops: int          # 2 * (number of scalar products)


# ---------------------------------------------------------------------------
# symbolic phase (host, NumPy — the paper computes structure ahead of numerics)
# ---------------------------------------------------------------------------


def spgemm_symbolic_host(A: CSR, B: CSR, pad_multiple: int = 64) -> SpGEMMWorkspace:
    """Exact structure of C = A x B on host: nnz, densest row, flops.

    Thin wrapper over the one structural expansion
    (``repro.core.symbolic.spgemm_structure_host``) so the symbolic phase has
    a single implementation to fix/extend."""
    from repro.core.symbolic import spgemm_structure_host

    s = spgemm_structure_host(A, B)
    return SpGEMMWorkspace(
        c_nnz=s.c_nnz,
        c_pad=-(-max(s.c_nnz, 1) // pad_multiple) * pad_multiple,
        c_max_row_nnz=s.c_max_row_nnz,
        flops=s.flops,
    )


# ---------------------------------------------------------------------------
# numeric phase (pure JAX, jit-able)
# ---------------------------------------------------------------------------


def _ell(B: CSR, width: int):
    """B's rows as ELL tables ``[width, n_rows]`` of column indices and values:
    slot j of row r holds B's entry ``indptr[r] + j``, column -1 past the row's end."""
    start = B.indptr[:-1]
    j = jnp.arange(width, dtype=jnp.int32)[:, None]
    ok = j < (B.indptr[1:] - start)[None, :]
    src = jnp.clip(start[None, :] + j, 0, B.nnz_pad - 1)
    return jnp.where(ok, B.indices[src], -1), jnp.where(ok, B.data[src], 0.0)


def _expand_products(A: CSR, B: CSR, r0, r1):
    """Fan every (valid, in-range) A entry out into its products with B's rows.

    Returns (rows, cols, vals) of static length nnzA_pad * B.max_row_nnz; invalid
    slots get row = A.n_rows (sorts to the tail) and val = 0. B's rows come as
    one column gather per A entry from B's ELL tables, so the stream is slot-major.

    ``r0, r1`` bound the *global* column range of A handled by this call; B is the
    CSR of exactly that row range (local row r_global - r0). For the unchunked case
    pass r0=0, r1=A.n_cols with B the full matrix.
    """
    t = jnp.arange(A.nnz_pad, dtype=jnp.int32)
    in_range = (A.indices >= r0) & (A.indices < r1) & (t < A.indptr[-1])
    b_row = jnp.clip(A.indices - r0, 0, B.n_rows - 1)
    ell_cols, ell_vals = _ell(B, max(B.max_row_nnz, 1))
    cols, vals = ell_cols[:, b_row], ell_vals[:, b_row]      # [bmax, nnzA_pad]
    valid = in_range[None, :] & (cols >= 0)
    rows = jnp.where(valid, csr_row_of_entry(A)[None, :], A.n_rows)
    cols = jnp.where(valid, cols, 0)
    vals = jnp.where(valid, A.data[None, :] * vals, 0.0)
    return rows.reshape(-1), cols.reshape(-1), vals.reshape(-1)


def _differs(keys, d: int):
    """Whether element i + d's key differs from element i's, for i < len - d."""
    out = keys[0][d:] != keys[0][:-d]
    for k in keys[1:]:
        out = out | (k[d:] != k[:-d])
    return out


def _run_sums(keys, vals, depth: int):
    """Segmented inclusive sums over runs of equal (sorted) keys: ``depth``
    steps of shift, mask and add, so runs of up to ``2**depth`` elements end
    with their total on their last element."""
    for i in range(depth):
        d = 1 << i
        if d >= vals.shape[0]:
            break
        shifted = jnp.where(_differs(keys, d), 0, vals[:-d])
        vals = vals + jnp.pad(shifted, (d, 0))
    return vals


def key_layout(m: int, n: int) -> str:
    """The sort key the ESC body uses for an ``m x n`` output: ``"packed"``,
    ``row * n + col`` in one uint32, where ``(m + 1) * n < 2**32`` (row ``m``
    keys the padding), else ``"pair"``, the (row, col) pair in a two-key sort."""
    return "packed" if 0 < n and (m + 1) * n < 2**32 else "pair"


def _accumulate(rows, cols, vals, m: int, n: int, c_pad: int, max_terms: int):
    """Sort-based accumulator: coalesce duplicate (row, col) into CSR arrays.

    One sort carries the values, keyed as ``key_layout(m, n)`` says (a static
    shape test): by ``row * n + col`` in one uint32 where it fits, else by
    the (row, col) pair in a two-key sort. A segmented sum of
    ``ceil(log2(max_terms))`` shift-and-add steps leaves each run's total on
    its last element (``max_terms`` bounds the products of one output entry).
    A second sort moves the run ends, in key order, to the front; the first
    ``c_pad`` are C, and ends past them are dropped. Padding (row ``m``)
    keys past every entry. Nothing is gathered or scattered element by element
    over the stream: on the chip, sorting is cheap and random access is not.
    Every constant is a scalar, so the body also traces inside Pallas kernels
    (the ``sparse`` merge), which reject captured array constants.
    Returns (indptr[m+1], indices[c_pad], data[c_pad]).
    """
    layout = key_layout(m, n)
    TRACE_COUNTS["esc_key_" + layout] += 1
    packed = layout == "packed"
    if packed:
        keys = (rows.astype(jnp.uint32) * np.uint32(n) + cols.astype(jnp.uint32),)
        tail = (np.uint32(m * n),)
    else:
        keys, tail = (rows.astype(jnp.int32), cols.astype(jnp.int32)), (m, 0)
    *keys, vals = lax.sort((*keys, vals), num_keys=len(keys), is_stable=True)
    sums = _run_sums(keys, vals, (max_terms - 1).bit_length())
    is_last = jnp.pad(_differs(keys, 1), (0, 1), constant_values=True)
    ends = [jnp.where(is_last, k, t) for k, t in zip(keys, tail)]
    *ends, sums = lax.sort((*ends, sums), num_keys=len(ends))
    short = max(c_pad - sums.shape[0], 0)
    ends = [jnp.pad(k[:c_pad], (0, short), constant_values=t)
            for k, t in zip(ends, tail)]
    sums = jnp.pad(sums[:c_pad], (0, short))
    if packed:
        out_rows = (ends[0] // np.uint32(n)).astype(jnp.int32)
        indices = (ends[0] % np.uint32(n)).astype(jnp.int32)
    else:
        out_rows, indices = ends
    data = jnp.where(out_rows < m, sums, 0.0)
    # rows are sorted ascending over slots -> indptr by binary search
    indptr = jnp.searchsorted(out_rows, jnp.arange(m + 1, dtype=jnp.int32)).astype(
        jnp.int32
    )
    return indptr, indices, data


@partial(jax.jit, static_argnames=("c_pad", "c_max_row_nnz"))
def spgemm(A: CSR, B: CSR, c_pad: int, c_max_row_nnz: int = 0) -> CSR:
    """Numeric phase of C = A x B. ``c_pad`` comes from ``spgemm_symbolic_host``."""
    rows, cols, vals = _expand_products(A, B, 0, A.n_cols)
    indptr, indices, data = _accumulate(rows, cols, vals, A.n_rows, B.n_cols,
                                        c_pad, max(A.max_row_nnz, 1))
    return CSR(indptr, indices, data, (A.n_rows, B.n_cols),
               c_max_row_nnz or c_pad)


def spgemm_ranged_impl(A: CSR, B_chunk: CSR, r0, r1, C_prev: CSR, c_pad: int,
                       c_max_row_nnz: int = 0) -> CSR:
    """Fused multiply-add over a B row-range: C = A[:, r0:r1] x B_chunk + C_prev.

    The previous partial result's entries join the product stream before
    accumulation — the paper's fused-add into the hashmap accumulators. A is NOT
    physically column-partitioned; out-of-range entries are masked ("skipped").

    This is the traceable body; ``spgemm_ranged`` is the jitted entry point. The
    scan executors (repro.core.chunk_stream) inline this body inside a
    ``lax.scan`` so the whole chunk loop compiles as one program.
    """
    rows, cols, vals = _expand_products(A, B_chunk, r0, r1)
    prev_entry = jnp.arange(C_prev.nnz_pad, dtype=jnp.int32)
    prev_valid = prev_entry < C_prev.indptr[-1]
    prev_rows = jnp.where(prev_valid, csr_row_of_entry(C_prev), A.n_rows)
    prev_cols = jnp.where(prev_valid, C_prev.indices, 0)
    prev_vals = jnp.where(prev_valid, C_prev.data, 0.0)
    rows = jnp.concatenate([rows, prev_rows])
    cols = jnp.concatenate([cols, prev_cols])
    vals = jnp.concatenate([vals, prev_vals])
    # an output entry (i, j) gets at most one product per stored A[i, k],
    # plus C_prev's entry
    indptr, indices, data = _accumulate(rows, cols, vals, A.n_rows, B_chunk.n_cols,
                                        c_pad, A.max_row_nnz + 1)
    return CSR(indptr, indices, data, (A.n_rows, B_chunk.n_cols),
               c_max_row_nnz or c_pad)


spgemm_ranged = partial(jax.jit, static_argnames=("c_pad", "c_max_row_nnz"))(
    spgemm_ranged_impl
)


def spgemm_full(A: CSR, B: CSR) -> CSR:
    """Convenience: symbolic + numeric in one call (host symbolic, jitted numeric)."""
    ws = spgemm_symbolic_host(A, B)
    return spgemm(A, B, ws.c_pad, ws.c_max_row_nnz)


# ---------------------------------------------------------------------------
# reference oracle
# ---------------------------------------------------------------------------


def spgemm_dense_oracle(A: CSR, B: CSR) -> jax.Array:
    """Trustworthy dense reference: densify and matmul."""
    from repro.sparse.csr import csr_to_dense

    return csr_to_dense(A) @ csr_to_dense(B)
