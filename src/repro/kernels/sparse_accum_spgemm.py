"""Pallas CSR-native sparse-output SpGEMM: sorted-merge accumulation in VMEM.

The ranged-SpGEMM kernel (``kernels/ranged_spgemm.py``) trades entry sparsity
for MXU tiles: its accumulator is a dense ``[strip_rows, n_cols]`` slab, so
VMEM — not the chunk plan — bounds strip sizing, and a very sparse C pays
dense-C traffic. This kernel is the numeric phase of the two-phase
symbolic/numeric scheme (``repro.core.symbolic`` is the symbolic phase): the
per-strip accumulator is a **fixed-capacity CSR triple**
(``indptr[strip_rows+1]``, ``indices[c_cap]``, ``data[c_cap]``) whose
capacity ``c_cap`` comes from the symbolic phase's exact structure bound, so
the fast-memory footprint scales with ``nnz(C)`` instead of
``strip_rows * n_cols`` — the compressed-accumulator idea of Deveci et al.'s
KKMEM and Nagasaka & Azad's ESC/hash variants, in the streaming-chunk setting.

Per grid step the kernel runs one fused ranged multiply-add
``C = A[:, r0:r1] x B_chunk + C_prev`` entirely against CSR operands: expand
the in-range products, concatenate the previous accumulator entries, two-key
sort, and compress duplicates back into the CSR scratch
(``repro.core.kkmem.spgemm_ranged_impl`` — the same expand-sort-compress
(ESC) accumulator the scan backend scans over, here executed inside the
kernel so the accumulator never leaves VMEM). Because the symbolic caps are
exact upper bounds and a partial C's structure is always a subset of the
final strip structure, the scratch can never overflow mid-stream.

The streaming schedule is the same explicit two-slot DMA pattern as
``ranged_spgemm_stream``: the stationary operand (the A strip in the Chunk1
order, the B chunk in Chunk2) rides a normal blocked ``BlockSpec``; the
streamed operand's CSR triple lives in slow memory (``pl.ANY``) and is
hand-DMA'd — three async copies per element, one per CSR field — through
``[2, ...]`` VMEM scratch buffers, starting element j+1 while element j
multiplies. Scalar-prefetched ``r0s``/``r1s`` realize the ranged column skip.

``interpret=default_interpret()`` validates the whole pipeline (DMA semantics
included) on CPU. On real TPU the ESC body leans on sort/scatter lowerings
inside the kernel — the open item tracked in ROADMAP.md next to the existing
"run the Pallas lanes on real TPU" note; the CSR-native *memory model* (what
the planner sizes against) is backend-independent.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.kkmem import spgemm_ranged_impl
from repro.kernels import dma_schedule, scoped_vmem
# shared with the dense-slab streaming kernel: same interpret heuristic, same
# linear-grid decomposition, same slot schedule (the two kernels are one DMA
# pattern — kernels/dma_schedule — two accumulators)
from repro.kernels.ranged_spgemm import _decompose, default_interpret
from repro.sparse.csr import CSR


def _kernel(r0s_ref, r1s_ref, *refs, order: str, batch: int,
            n_ac: int, n_b: int, strip_rows: int, chunk_rows: int,
            k_cols: int, n_cols: int, a_mrn: int, b_mrn: int, c_cap: int,
            masked: bool, merge_fn):
    """One grid step: DMA-stream a CSR triple, merge into the CSR scratch.

    ``merge_fn(A, B_chunk, r0, r1, C_prev, c_cap) -> CSR`` is the pluggable
    accumulator body: the ESC sorted merge (``spgemm_ranged_impl``, the
    default) or the linear-probing hash merge
    (``repro.kernels.hash_accum_spgemm.hash_merge_impl``). The streaming
    schedule around it is identical. With ``masked`` the positional refs
    carry two extra stationary operands — the fused output mask's strip
    structure (indptr + indices, no data) — and the merge is called with
    them appended: ``merge_fn(A, B_chunk, r0, r1, C_prev, c_cap,
    mask_indptr, mask_indices)``. The unmasked operand list (and therefore
    the traced jaxpr the static auditor pins) is unchanged.

    Grid is (batch, outer, inner); ``order`` fixes which operand streams:
      chunk1: outer = strips, inner = chunks  -> B triples stream through VMEM
      chunk2: outer = chunks, inner = strips  -> A triples stream through VMEM
    """
    if masked:
        (stat_ip, stat_ix, stat_d,
         stream_ip_hbm, stream_ix_hbm, stream_d_hbm,
         c0_ip, c0_ix, c0_d, m_ip, m_ix,
         out_ip, out_ix, out_d, buf_ip, buf_ix, buf_d, sems) = refs
    else:
        (stat_ip, stat_ix, stat_d,
         stream_ip_hbm, stream_ix_hbm, stream_d_hbm,
         c0_ip, c0_ix, c0_d,
         out_ip, out_ix, out_d, buf_ip, buf_ix, buf_d, sems) = refs
        m_ip = m_ix = None
    b = pl.program_id(0)
    outer_ix = pl.program_id(1)
    inner_ix = pl.program_id(2)
    outer, inner = (n_ac, n_b) if order == "chunk1" else (n_b, n_ac)
    total = batch * outer * inner
    lin = (b * outer + outer_ix) * inner + inner_ix

    def dma(slot, step):
        bb, ii = _decompose(step, outer, inner)
        return [
            pltpu.make_async_copy(stream_ip_hbm.at[bb, ii], buf_ip.at[slot],
                                  sems.at[slot, 0]),
            pltpu.make_async_copy(stream_ix_hbm.at[bb, ii], buf_ix.at[slot],
                                  sems.at[slot, 1]),
            pltpu.make_async_copy(stream_d_hbm.at[bb, ii], buf_d.at[slot],
                                  sems.at[slot, 2]),
        ]

    # warm-up: the very first streamed element has no previous step to
    # prefetch it, so stage it synchronously before the overlap steady-state.
    # Slot arithmetic comes from kernels/dma_schedule — the module the static
    # DMA checker (repro.analysis.dma) simulates host-side.
    @pl.when(dma_schedule.is_prime_step(lin))
    def _prime():
        for copy in dma(dma_schedule.prime_slot(), 0):
            copy.start()

    # the explicit copy2Fast overlap: start element lin+1 into the other
    # slot while this step's merge consumes the read slot
    @pl.when(dma_schedule.has_prefetch(lin, total))
    def _prefetch():
        for copy in dma(dma_schedule.prefetch_slot(lin), lin + 1):
            copy.start()

    for copy in dma(dma_schedule.read_slot(lin), lin):
        copy.wait()
    slot = dma_schedule.read_slot(lin)
    s_ip, s_ix, s_d = buf_ip[slot], buf_ix[slot], buf_d[slot]

    if order == "chunk1":
        j, i = inner_ix, outer_ix
        A = CSR(stat_ip[0, 0], stat_ix[0, 0], stat_d[0, 0],
                (strip_rows, k_cols), a_mrn)
        Bc = CSR(s_ip, s_ix, s_d, (chunk_rows, n_cols), b_mrn)
        prev = (c0_ip[0, 0], c0_ix[0, 0], c0_d[0, 0],
                out_ip[0, 0], out_ix[0, 0], out_d[0, 0])
        mask = (m_ip[0, 0], m_ix[0, 0]) if masked else None
    else:
        j, i = outer_ix, inner_ix
        A = CSR(s_ip, s_ix, s_d, (strip_rows, k_cols), a_mrn)
        Bc = CSR(stat_ip[0, 0], stat_ix[0, 0], stat_d[0, 0],
                 (chunk_rows, n_cols), b_mrn)
        prev = (c0_ip[0, i], c0_ix[0, i], c0_d[0, i],
                out_ip[0, i], out_ix[0, i], out_d[0, i])
        mask = (m_ip[0, i], m_ix[0, i]) if masked else None

    # the fused C_prev: the caller's c0 on the first chunk step, the
    # persistent VMEM accumulator afterwards (out_ref is only ever read
    # behind the j > 0 select, so the j == 0 read of the uninitialized
    # block is discarded)
    first = j == 0
    c_prev = CSR(
        jnp.where(first, prev[0], prev[3]),
        jnp.where(first, prev[1], prev[4]),
        jnp.where(first, prev[2], prev[5]),
        (strip_rows, n_cols), c_cap,
    )
    if masked:
        merged = merge_fn(A, Bc, r0s_ref[j], r1s_ref[j], c_prev, c_cap,
                          mask[0], mask[1])
    else:
        merged = merge_fn(A, Bc, r0s_ref[j], r1s_ref[j], c_prev, c_cap)
    if order == "chunk1":
        out_ip[0, 0] = merged.indptr
        out_ix[0, 0] = merged.indices
        out_d[0, 0] = merged.data
    else:
        out_ip[0, i] = merged.indptr
        out_ix[0, i] = merged.indices
        out_d[0, i] = merged.data


def sparse_accum_spgemm_stream(Ast: CSR, Bst: CSR, C0st: CSR,
                               r0s: jax.Array, r1s: jax.Array, *, order: str,
                               interpret: bool | None = None,
                               merge_fn=None, mask_st: CSR | None = None,
                               workspace_bytes: float | None = None):
    """Streamed sparse-output multiply over stacked CSR strips and chunks.

    Args:
      Ast: doubly-stacked A strips — a :class:`CSR` whose array fields carry
        leading ``[batch, n_ac]`` axes (``csr_stack`` of ``csr_stack``), with
        per-element ``shape == (strip_rows, k_cols)``.
      Bst: doubly-stacked B chunks, leading ``[batch, n_b]`` axes,
        per-element ``shape == (chunk_rows, n_cols)``; ``max_row_nnz`` sizes
        the product expansion.
      C0st: the fused ``C_prev`` per strip, leading ``[batch, n_ac]`` axes;
        its entry capacity is the CSR scratch capacity ``c_cap`` (from the
        symbolic phase — must bound every strip's exact output nnz).
      r0s, r1s: i32[n_b] global row range of each B chunk (scalar-prefetched).
      order: "chunk1" (strips outer, B streamed) or "chunk2" (chunks outer,
        A streamed; per-strip accumulators persist in the VMEM out block).
      merge_fn: per-step accumulator body ``(A, B_chunk, r0, r1, C_prev,
        c_cap) -> CSR``; defaults to the ESC sorted merge
        (``spgemm_ranged_impl``). ``repro.kernels.hash_accum_spgemm`` passes
        its linear-probing hash merge through here, reusing this exact
        streaming schedule.
      mask_st: optional fused output mask, stacked like ``C0st`` (leading
        ``[batch, n_ac]`` axes, per-element shape ``(strip_rows, n_cols)``).
        Only its structure (indptr + indices) enters the kernel — as two
        extra stationary operands with the accumulator blocks' index maps —
        and ``merge_fn`` must then accept them appended: ``(A, B_chunk, r0,
        r1, C_prev, c_cap, mask_indptr, mask_indices) -> CSR`` (the masked
        hash merge). ``C0st``'s capacity must bound every strip's mask nnz.
      workspace_bytes: the merge body's largest temporaries, for the
        kernel's VMEM limit; defaults to the ESC expand buffer
        (``a_cap * b_max_row_nnz + c_cap`` slots of row, column, value).

    Returns ``(indptr, indices, data)`` with leading ``[batch, n_ac]`` axes —
    the accumulated C strip CSRs at capacity ``c_cap``.
    """
    if merge_fn is None:
        merge_fn = spgemm_ranged_impl
    if order not in ("chunk1", "chunk2"):
        raise ValueError(f"unknown streaming order {order!r}")
    batch, n_ac = Ast.indptr.shape[0], Ast.indptr.shape[1]
    n_b = Bst.indptr.shape[1]
    strip_rows, k_cols = Ast.shape
    chunk_rows, n_cols = Bst.shape
    a_cap = Ast.indices.shape[-1]
    chunk_cap = Bst.indices.shape[-1]
    c_cap = C0st.indices.shape[-1]
    dtype = C0st.data.dtype
    if Bst.indptr.shape[0] != batch or C0st.indptr.shape[:2] != (batch, n_ac):
        raise ValueError(
            f"inconsistent stack axes: A[{Ast.indptr.shape[:2]}] "
            f"B[{Bst.indptr.shape[:2]}] C0[{C0st.indptr.shape[:2]}]"
        )
    if C0st.shape != (strip_rows, n_cols):
        raise ValueError(f"C0 shape {C0st.shape} != {(strip_rows, n_cols)}")
    masked = mask_st is not None
    if masked:
        if merge_fn is None:
            raise ValueError("mask_st requires an explicit masked merge_fn")
        if mask_st.indptr.shape[:2] != (batch, n_ac):
            raise ValueError(
                f"mask stack axes {mask_st.indptr.shape[:2]} != "
                f"{(batch, n_ac)}")
        if mask_st.shape != (strip_rows, n_cols):
            raise ValueError(
                f"mask shape {mask_st.shape} != {(strip_rows, n_cols)}")
        m_cap = mask_st.indices.shape[-1]
    interpret = default_interpret() if interpret is None else interpret

    def blocked(trail, index_map):
        return pl.BlockSpec((1, 1) + trail, index_map)

    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    if order == "chunk1":
        grid = (batch, n_ac, n_b)
        stat = Ast
        streamed = Bst
        stat_ix_map = lambda b, i, j, r0s, r1s: (b, i, 0)     # noqa: E731
        stat_specs = [blocked((strip_rows + 1,), stat_ix_map),
                      blocked((a_cap,), stat_ix_map),
                      blocked((a_cap,), stat_ix_map)]
        c_map = lambda b, i, j, r0s, r1s: (b, i, 0)           # noqa: E731
        c0_specs = [blocked((strip_rows + 1,), c_map),
                    blocked((c_cap,), c_map), blocked((c_cap,), c_map)]
        out_specs = (blocked((strip_rows + 1,), c_map),
                     blocked((c_cap,), c_map), blocked((c_cap,), c_map))
        mask_specs = ([blocked((strip_rows + 1,), c_map),
                       blocked((m_cap,), c_map)] if masked else [])
        ns = dma_schedule.N_SLOTS
        bufs = [pltpu.VMEM((ns, chunk_rows + 1), jnp.int32),
                pltpu.VMEM((ns, chunk_cap), jnp.int32),
                pltpu.VMEM((ns, chunk_cap), dtype)]
    else:
        grid = (batch, n_b, n_ac)
        stat = Bst
        streamed = Ast
        stat_ix_map = lambda b, j, i, r0s, r1s: (b, j, 0)     # noqa: E731
        stat_specs = [blocked((chunk_rows + 1,), stat_ix_map),
                      blocked((chunk_cap,), stat_ix_map),
                      blocked((chunk_cap,), stat_ix_map)]
        # whole-batch-element C blocks: every (j, i) step addresses the same
        # persistent out block, strips' accumulators never leave VMEM
        c_map = lambda b, j, i, r0s, r1s: (b, 0, 0)           # noqa: E731
        c0_specs = [pl.BlockSpec((1, n_ac, strip_rows + 1), c_map),
                    pl.BlockSpec((1, n_ac, c_cap), c_map),
                    pl.BlockSpec((1, n_ac, c_cap), c_map)]
        out_specs = (pl.BlockSpec((1, n_ac, strip_rows + 1), c_map),
                     pl.BlockSpec((1, n_ac, c_cap), c_map),
                     pl.BlockSpec((1, n_ac, c_cap), c_map))
        mask_specs = ([pl.BlockSpec((1, n_ac, strip_rows + 1), c_map),
                       pl.BlockSpec((1, n_ac, m_cap), c_map)]
                      if masked else [])
        ns = dma_schedule.N_SLOTS
        bufs = [pltpu.VMEM((ns, strip_rows + 1), jnp.int32),
                pltpu.VMEM((ns, a_cap), jnp.int32),
                pltpu.VMEM((ns, a_cap), dtype)]

    if workspace_bytes is None:
        esc_slots = a_cap * max(Bst.max_row_nnz, 1) + c_cap
        workspace_bytes = esc_slots * (8 + jnp.dtype(dtype).itemsize)
    blocked = [(spec.block_shape, op.dtype) for spec, op in zip(
        [*stat_specs, *c0_specs, *out_specs, *mask_specs],
        [stat.indptr, stat.indices, stat.data,
         C0st.indptr, C0st.indices, C0st.data,
         C0st.indptr, C0st.indices, C0st.data,
         *([mask_st.indptr, mask_st.indices] if masked else [])])]
    kernel = functools.partial(
        _kernel, order=order, batch=batch, n_ac=n_ac, n_b=n_b,
        strip_rows=strip_rows, chunk_rows=chunk_rows, k_cols=k_cols,
        n_cols=n_cols, a_mrn=Ast.max_row_nnz, b_mrn=Bst.max_row_nnz,
        c_cap=c_cap, masked=masked, merge_fn=merge_fn,
    )
    out_shape = (
        jax.ShapeDtypeStruct((batch, n_ac, strip_rows + 1), jnp.int32),
        jax.ShapeDtypeStruct((batch, n_ac, c_cap), jnp.int32),
        jax.ShapeDtypeStruct((batch, n_ac, c_cap), dtype),
    )
    operands = [r0s, r1s, stat.indptr, stat.indices, stat.data,
                streamed.indptr, streamed.indices, streamed.data,
                C0st.indptr, C0st.indices, C0st.data]
    if masked:
        operands += [mask_st.indptr, mask_st.indices]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[*stat_specs, any_spec, any_spec, any_spec,
                      *c0_specs, *mask_specs],
            out_specs=out_specs,
            scratch_shapes=[*bufs,
                            pltpu.SemaphoreType.DMA((dma_schedule.N_SLOTS, 3))],
        ),
        out_shape=out_shape,
        compiler_params=scoped_vmem.compiler_params(
            blocked=blocked, scratch=[(b.shape, b.dtype) for b in bufs],
            workspace=workspace_bytes),
        interpret=interpret,
    )(*operands)
