"""Pallas CSR-native SpGEMM with a linear-probing hash accumulator in VMEM.

The ESC backend (``kernels/sparse_accum_spgemm.py``) pays for compressed
accumulation with an expand-sort-compress workspace of
``strip_nnz_cap * b_max_row_nnz + c_pad`` slots per step — the term that
erodes its VMEM win as outputs densify (ROADMAP). This kernel is the hash
variant of the same two-phase scheme (Nagasaka & Azad's hash accumulator,
the insight behind Deveci et al.'s kkmem GPU hashmap): each strip row owns a
**power-of-two linear-probing hash table** keyed by column index, sized by
the symbolic phase's ``c_max_row_nnz`` bound
(``repro.core.planner.hash_table_slots``), so the per-step workspace scales
with the densest *output* row — ``strip_rows x T`` key/value pairs — never
with the expand size.

Per grid step the merge (:func:`hash_merge_impl`) walks the in-range
products of ``A[:, r0:r1] x B_chunk`` plus the previous accumulator's
entries and insert-or-accumulates each into its row's table: probe from
``hash(col) & (T - 1)`` until the key or an empty slot is found
(a bounded ``lax.while_loop``), then scatter the value in. Because the
symbolic bound is exact and a partial C row's structure is a subset of the
final row structure, a row never holds more than ``c_max_row_nnz <= T``
distinct keys, so the probe always terminates at a match or a free slot.
Extraction sorts each row's table by key and compacts into the fixed-capacity
CSR scratch — column-sorted rows, same output convention as the ESC merge.

Everything around the merge — the symbolic phase, the fixed-capacity CSR
accumulator blocks, the two-slot DMA streaming of the non-stationary CSR
triple, the scalar-prefetched ranged column skip — is literally
``sparse_accum_spgemm_stream`` with this merge body plugged in
(``merge_fn``): one DMA pattern, three accumulators (dense slab / ESC / hash)
across the three streaming kernels.

``interpret=default_interpret()`` validates the pipeline on CPU. The probe
loops are plain ``lax.while_loop``/``lax.fori_loop`` over scalar gathers and
single-element scatters — no in-kernel argsort over the expand buffer — so
the body both interprets and traces for Mosaic; the per-row extraction sort
runs over the ``[strip_rows, T]`` table only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.planner import hash_table_slots
from repro.kernels.sparse_accum_spgemm import sparse_accum_spgemm_stream
from repro.sparse.csr import CSR

# Python ints, not jnp scalars: Pallas kernels reject captured array
# constants (same constraint the kkmem ESC body documents), and weak-typed
# int literals fold into the int32 arithmetic without promotion
_EMPTY = -1           # table key sentinel (column ids are >= 0)
_KNUTH = -1640531527  # 2654435769 as int32: Knuth's multiplicative hash


def probe_step_bound(table_size: int) -> int:
    """Static step bound of one linear-probe ``while_loop``: a probe visits
    at most every slot once, so ``table_size`` steps make the loop total even
    if a (host-checked) capacity invariant were violated. Named so the static
    DMA/loop checker (``repro.analysis.dma``) can assert the bound baked into
    the traced jaxpr *is* this function of ``planner.hash_table_slots`` —
    the kernel and the verifier derive the literal from one definition."""
    return int(table_size)


def _insert(tables, row, col, val, valid):
    """Insert-or-accumulate one (row, col, val) product into its row table.

    Linear probe from the hashed slot until the key or an empty slot is
    found; the step bound makes the while_loop total even if a (host-checked)
    capacity invariant were violated. Invalid products still probe — cheaper
    than a cond around the loop — and mask their writes.
    """
    keys, vals = tables
    size = keys.shape[1]
    bound = probe_step_bound(size)
    start = (col * _KNUTH) & (size - 1)

    def cond(state):
        slot, steps = state
        k = keys[row, slot]
        return (steps < bound) & (k != col) & (k != _EMPTY)

    def body(state):
        slot, steps = state
        return (slot + 1) & (size - 1), steps + 1

    slot, _ = lax.while_loop(cond, body, (start, jnp.int32(0)))
    keys = keys.at[row, slot].set(jnp.where(valid, col, keys[row, slot]))
    vals = vals.at[row, slot].add(
        jnp.where(valid, val, jnp.zeros((), vals.dtype)))
    return keys, vals


def hash_merge_impl(A: CSR, B_chunk: CSR, r0, r1, C_prev: CSR, c_pad: int,
                    *, table_size: int) -> CSR:
    """Hash-accumulated fused multiply-add: C = A[:, r0:r1] x B_chunk + C_prev.

    Drop-in for ``spgemm_ranged_impl`` as the streaming kernels' merge body:
    same operands, same fixed-capacity CSR output at ``c_pad``, different
    accumulator — per-row linear-probing hash tables of ``table_size``
    (power-of-two, >= the exact symbolic ``c_max_row_nnz``) instead of the
    expand-sort-compress buffer. Products are consumed entry-by-entry
    (``fori_loop`` over A's entry slots x ``b_max_row_nnz``), so no
    expand-size workspace is ever materialized.
    """
    m = A.n_rows
    size = int(table_size)
    bmax = max(B_chunk.max_row_nnz, 1)
    tables = (jnp.full((m, size), _EMPTY, jnp.int32),
              jnp.zeros((m, size), C_prev.data.dtype))

    a_nnz = A.indptr[-1]

    def per_a_entry(e, tables):
        row = jnp.clip(jnp.searchsorted(A.indptr, e, side="right") - 1,
                       0, m - 1).astype(jnp.int32)
        col_a = A.indices[e]
        in_range = (e < a_nnz) & (col_a >= r0) & (col_a < r1)
        b_row = jnp.clip(col_a - r0, 0, B_chunk.n_rows - 1)
        b_start = B_chunk.indptr[b_row]
        b_len = B_chunk.indptr[b_row + 1] - b_start
        a_val = A.data[e]

        def per_product(jj, tables):
            valid = in_range & (jj < b_len)
            src = jnp.clip(b_start + jj, 0, B_chunk.nnz_pad - 1)
            return _insert(tables, row, B_chunk.indices[src],
                           a_val * B_chunk.data[src], valid)

        return lax.fori_loop(0, bmax, per_product, tables)

    tables = lax.fori_loop(0, A.nnz_pad, per_a_entry, tables)

    prev_nnz = C_prev.indptr[-1]

    def per_prev_entry(e, tables):
        row = jnp.clip(jnp.searchsorted(C_prev.indptr, e, side="right") - 1,
                       0, m - 1).astype(jnp.int32)
        return _insert(tables, row, C_prev.indices[e], C_prev.data[e],
                       e < prev_nnz)

    keys, vals = lax.fori_loop(0, C_prev.nnz_pad, per_prev_entry, tables)

    # extraction: per-row sort by key (empties to the tail), compact into the
    # CSR scratch — realized overflow past c_pad lands in the dropped bucket,
    # but the host-side cap check makes that unreachable
    occupied = keys != _EMPTY
    counts = occupied.sum(axis=1).astype(jnp.int32)
    indptr = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts).astype(jnp.int32)])
    tail = jnp.int32(jnp.iinfo(jnp.int32).max)
    sort_keys = jnp.where(occupied, keys, tail)
    order = jnp.argsort(sort_keys, axis=1)
    skeys = jnp.take_along_axis(sort_keys, order, axis=1)
    svals = jnp.take_along_axis(vals, order, axis=1)
    svalid = skeys != tail
    pos = indptr[:-1, None] + jnp.arange(size, dtype=jnp.int32)[None, :]
    slot = jnp.where(svalid, jnp.minimum(pos, c_pad), c_pad)
    indices = jnp.zeros(c_pad + 1, jnp.int32).at[slot.reshape(-1)].max(
        jnp.where(svalid, skeys, 0).reshape(-1))[:c_pad]
    data = jnp.zeros(c_pad + 1, svals.dtype).at[slot.reshape(-1)].add(
        jnp.where(svalid, svals, jnp.zeros((), svals.dtype)).reshape(-1)
    )[:c_pad]
    return CSR(indptr, indices, data, (m, B_chunk.n_cols), c_pad)


def _probe_only(tables, row, col, val, valid):
    """Accumulate ``val`` into ``(row, col)`` only if the key is already
    seeded; never inserts. Same bounded linear probe as :func:`_insert`, but
    the key array is read-only and a miss (empty slot) masks the write —
    this is what pins a masked product's output structure to the mask."""
    keys, vals = tables
    size = keys.shape[1]
    bound = probe_step_bound(size)
    start = (col * _KNUTH) & (size - 1)

    def cond(state):
        slot, steps = state
        k = keys[row, slot]
        return (steps < bound) & (k != col) & (k != _EMPTY)

    def body(state):
        slot, steps = state
        return (slot + 1) & (size - 1), steps + 1

    slot, _ = lax.while_loop(cond, body, (start, jnp.int32(0)))
    hit = keys[row, slot] == col
    vals = vals.at[row, slot].add(
        jnp.where(valid & hit, val, jnp.zeros((), vals.dtype)))
    return keys, vals


def hash_masked_merge_impl(A: CSR, B_chunk: CSR, r0, r1, C_prev: CSR,
                           c_pad: int, m_indptr, m_indices, *,
                           table_size: int) -> CSR:
    """Mask-fused hash multiply-add: C = ((A[:, r0:r1] x B_chunk) + C_prev) ∘ M.

    The masked variant of :func:`hash_merge_impl` — the fused-mask fast path
    for triangle counting (Wolf/Deveci et al.; Azad et al.'s masked SpGEMM).
    The per-row tables are **seeded** from the mask strip's structure
    (``m_indptr``/``m_indices``, value 0 — the only inserts allowed), then
    products and previous-accumulator entries accumulate *probe-only*:
    a product whose column is not a mask key hits an empty slot and its
    write is masked off. Extraction therefore emits exactly the mask
    structure (explicit zeros where no product landed) — the unmasked C is
    never materialized, at any capacity. ``table_size`` must cover the
    densest *mask* row (``hash_table_slots`` of the mask's max row nnz) and
    ``c_pad`` the largest strip's mask nnz
    (``repro.core.symbolic.masked_output_caps``).
    """
    m = A.n_rows
    size = int(table_size)
    bmax = max(B_chunk.max_row_nnz, 1)
    tables = (jnp.full((m, size), _EMPTY, jnp.int32),
              jnp.zeros((m, size), C_prev.data.dtype))

    # seed: every mask key enters its row's table with value 0 — after this,
    # the key set is frozen
    m_nnz = m_indptr[-1]

    def per_mask_entry(e, tables):
        row = jnp.clip(jnp.searchsorted(m_indptr, e, side="right") - 1,
                       0, m - 1).astype(jnp.int32)
        return _insert(tables, row, m_indices[e],
                       jnp.zeros((), tables[1].dtype), e < m_nnz)

    tables = lax.fori_loop(0, m_indices.shape[-1], per_mask_entry, tables)

    a_nnz = A.indptr[-1]

    def per_a_entry(e, tables):
        row = jnp.clip(jnp.searchsorted(A.indptr, e, side="right") - 1,
                       0, m - 1).astype(jnp.int32)
        col_a = A.indices[e]
        in_range = (e < a_nnz) & (col_a >= r0) & (col_a < r1)
        b_row = jnp.clip(col_a - r0, 0, B_chunk.n_rows - 1)
        b_start = B_chunk.indptr[b_row]
        b_len = B_chunk.indptr[b_row + 1] - b_start
        a_val = A.data[e]

        def per_product(jj, tables):
            valid = in_range & (jj < b_len)
            src = jnp.clip(b_start + jj, 0, B_chunk.nnz_pad - 1)
            return _probe_only(tables, row, B_chunk.indices[src],
                               a_val * B_chunk.data[src], valid)

        return lax.fori_loop(0, bmax, per_product, tables)

    tables = lax.fori_loop(0, A.nnz_pad, per_a_entry, tables)

    prev_nnz = C_prev.indptr[-1]

    def per_prev_entry(e, tables):
        # C_prev is a masked partial (or the zero C0): its keys are a subset
        # of the mask keys, so probe-only always hits
        row = jnp.clip(jnp.searchsorted(C_prev.indptr, e, side="right") - 1,
                       0, m - 1).astype(jnp.int32)
        return _probe_only(tables, row, C_prev.indices[e], C_prev.data[e],
                           e < prev_nnz)

    keys, vals = lax.fori_loop(0, C_prev.nnz_pad, per_prev_entry, tables)

    # extraction: identical to the unmasked merge — all *seeded* keys are
    # occupied, so the compacted output structure is the mask structure
    occupied = keys != _EMPTY
    counts = occupied.sum(axis=1).astype(jnp.int32)
    indptr = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts).astype(jnp.int32)])
    tail = jnp.int32(jnp.iinfo(jnp.int32).max)
    sort_keys = jnp.where(occupied, keys, tail)
    order = jnp.argsort(sort_keys, axis=1)
    skeys = jnp.take_along_axis(sort_keys, order, axis=1)
    svals = jnp.take_along_axis(vals, order, axis=1)
    svalid = skeys != tail
    pos = indptr[:-1, None] + jnp.arange(size, dtype=jnp.int32)[None, :]
    slot = jnp.where(svalid, jnp.minimum(pos, c_pad), c_pad)
    indices = jnp.zeros(c_pad + 1, jnp.int32).at[slot.reshape(-1)].max(
        jnp.where(svalid, skeys, 0).reshape(-1))[:c_pad]
    data = jnp.zeros(c_pad + 1, svals.dtype).at[slot.reshape(-1)].add(
        jnp.where(svalid, svals, jnp.zeros((), svals.dtype)).reshape(-1)
    )[:c_pad]
    return CSR(indptr, indices, data, (m, B_chunk.n_cols), c_pad)


def _table_bytes(Ast: CSR, C0st: CSR, table_size: int) -> int:
    """The merge's workspace: one key/value table of ``table_size`` slots
    per strip row (the ``planner.planned_stats_hash`` workspace term)."""
    return Ast.shape[0] * table_size * (4 + C0st.data.dtype.itemsize)


def hash_masked_accum_spgemm_stream(Ast: CSR, Bst: CSR, C0st: CSR,
                                    mask_st: CSR, r0s: jax.Array,
                                    r1s: jax.Array, *, order: str,
                                    table_size: int,
                                    interpret: bool | None = None):
    """Streamed mask-fused hash multiply over stacked CSR strips and chunks.

    :func:`hash_accum_spgemm_stream` with the masked merge plugged in and
    the mask's stacked structure threaded through the streaming kernel's
    extra stationary operands; ``table_size`` sizes tables from the *mask*'s
    densest row (``masked_output_caps(...).c_max_row_nnz``).
    """
    if table_size < 1 or table_size != hash_table_slots(table_size):
        raise ValueError(f"table_size={table_size} must be a power of two "
                         ">= 1 (use planner.hash_table_slots)")
    merge = functools.partial(hash_masked_merge_impl, table_size=table_size)
    return sparse_accum_spgemm_stream(Ast, Bst, C0st, r0s, r1s, order=order,
                                      interpret=interpret, merge_fn=merge,
                                      mask_st=mask_st,
                                      workspace_bytes=_table_bytes(
                                          Ast, C0st, table_size))


def hash_accum_spgemm_stream(Ast: CSR, Bst: CSR, C0st: CSR,
                             r0s: jax.Array, r1s: jax.Array, *, order: str,
                             table_size: int,
                             interpret: bool | None = None):
    """Streamed hash-accumulated multiply over stacked CSR strips and chunks.

    Operand layout, streaming orders and the returned stacked CSR triple are
    exactly :func:`sparse_accum_spgemm_stream` (which this wraps, passing the
    hash merge as ``merge_fn``); ``table_size`` is the per-row hash-table
    slot count — static, from :func:`repro.core.planner.hash_table_slots` of
    the envelope's ``c_max_row_nnz`` cap.
    """
    if table_size < 1 or table_size != hash_table_slots(table_size):
        raise ValueError(f"table_size={table_size} must be a power of two "
                         ">= 1 (use planner.hash_table_slots)")
    merge = functools.partial(hash_merge_impl, table_size=table_size)
    return sparse_accum_spgemm_stream(Ast, Bst, C0st, r0s, r1s, order=order,
                                      interpret=interpret, merge_fn=merge,
                                      workspace_bytes=_table_bytes(
                                          Ast, C0st, table_size))
