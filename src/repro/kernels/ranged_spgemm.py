"""Pallas ranged-SpGEMM with explicit double-buffered chunk prefetch.

This is the paper's `copy2Fast` overlap made explicit: the chunked algorithms
(Deveci et al. §3.2) stream one operand through fast memory while the other
stays resident, and the central GPU result is that copying chunk j+1 *while*
chunk j multiplies is what auto-caching cannot deliver. The scan executors
(repro.core.chunk_stream) leave that overlap to XLA's scheduler; here it is a
hand-written two-slot VMEM pipeline:

  * the **stationary** operand (the A strip in the Chunk1 order, the B chunk
    in the Chunk2 order) rides a normal blocked ``BlockSpec`` — Pallas stages
    it into VMEM once per outer step;
  * the **streamed** operand lives in slow memory (``pl.ANY``) and is
    hand-DMA'd through a ``[2, ...]`` VMEM scratch buffer: at every grid step
    the kernel starts the async copy of element j+1 into slot ``(j+1) % 2``,
    then waits on slot ``j % 2`` and multiplies — compute and the next
    transfer overlap by construction;
  * the ranged product ``C = A[:, r0:r1] x B_chunk + C_prev`` uses the
    paper's "skip columns of A outside the range" as a scalar-prefetched
    table (SMEM) of window starts indexing a dynamic column slice of the
    resident strip.

Mosaic slices the lane (last) dimension only at multiples of 128, so each
staged B chunk sits inside a lane-aligned window: the slab for a chunk
starting at global row ``r0`` covers rows ``[a0, a0 + span)`` with
``a0 = r0 - r0 % 128``, its rows shifted down by ``r0 % 128`` and zero
elsewhere, and ``span`` (:func:`stage_dims`) is a multiple of 128 large
enough for any offset. The A column slice ``[a0, a0 + span)`` then starts on
a lane boundary, and the zero rows multiply the columns outside the chunk
into nothing.

Like ``kernels/bsr_spgemm.py``, entry-level sparsity inside the staged pieces
is traded for MXU-shaped dense tiles: the staged B chunk becomes a dense
``[chunk_rows, n]`` slab (its padding rows are zero, so columns of A past the
chunk's true range multiply into nothing), the A strip a dense
``[strip_rows, k_pad]`` block. The accumulator is the output block itself,
initialized from ``C_prev`` — the fused add of the paper's modified KKMEM
sub-procedure — and flushed once per strip.

``interpret`` defaults to :func:`default_interpret`: the same pallas_call runs
in the interpreter on the CPU (DMA semantics included) and compiles for the
TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import dma_schedule, scoped_vmem

LANES = 128


def default_interpret() -> bool:
    """Whether Pallas kernels run in the interpreter: on the CPU they do, on
    the TPU they compile through Mosaic. No other platform has a path here,
    so any other raises rather than run somewhere unintended."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels interpret on 'cpu' and compile on 'tpu'; there is "
        f"no path for platform {platform!r}")


def _lane_pad(x: int) -> int:
    return -(-x // LANES) * LANES


def stage_dims(k: int, chunk_rows: int, n: int) -> tuple:
    """``(k_pad, span, n_pad)`` of the staged dense operands: ``span`` rows
    per B slab — a lane-aligned window that holds ``chunk_rows`` rows at any
    offset below 128 — ``k_pad`` A columns, so that the window of the last
    chunk never reads past the strip, and ``n_pad`` output columns, whole
    lane tiles so that each slab copies as one aligned DMA."""
    span = _lane_pad(chunk_rows + LANES - 1)
    return _lane_pad(k) + span, span, _lane_pad(n)


def _decompose(lin, outer: int, inner: int):
    """(b, inner index) of linear grid step ``lin`` over (batch, outer, inner)."""
    per_batch = outer * inner
    return lin // per_batch, (lin % per_batch) % inner


def _kernel(a0s_ref, stationary_ref, streamed_hbm, c0_ref, out_ref,
            stream_buf, sems, *, order: str, batch: int, n_ac: int, n_b: int,
            span: int):
    """One grid step of the streaming multiply.

    Grid is (batch, outer, inner); ``order`` fixes which operand streams:
      chunk1: outer = strips, inner = chunks  -> B slabs stream through VMEM
      chunk2: outer = chunks, inner = strips  -> A blocks stream through VMEM
    """
    b = pl.program_id(0)
    outer_ix = pl.program_id(1)
    inner_ix = pl.program_id(2)
    outer, inner = (n_ac, n_b) if order == "chunk1" else (n_b, n_ac)
    total = batch * outer * inner
    lin = (b * outer + outer_ix) * inner + inner_ix

    def dma(slot, step):
        bb, ii = _decompose(step, outer, inner)
        return pltpu.make_async_copy(
            streamed_hbm.at[bb, ii], stream_buf.at[slot], sems.at[slot]
        )

    # warm-up: the very first streamed element has no previous step to
    # prefetch it, so stage it synchronously before the overlap steady-state.
    # All slot arithmetic comes from kernels/dma_schedule — the module the
    # static DMA checker (repro.analysis.dma) simulates host-side.
    @pl.when(dma_schedule.is_prime_step(lin))
    def _prime():
        dma(dma_schedule.prime_slot(), 0).start()

    # the explicit copy2Fast overlap: start element lin+1 into the other
    # slot while this step's multiply consumes the read slot
    @pl.when(dma_schedule.has_prefetch(lin, total))
    def _prefetch():
        dma(dma_schedule.prefetch_slot(lin), lin + 1).start()

    slot = dma_schedule.read_slot(lin)
    dma(slot, lin).wait()

    j, i = (inner_ix, outer_ix) if order == "chunk1" else (outer_ix, inner_ix)
    window = pl.ds(pl.multiple_of(a0s_ref[j], LANES), span)
    if order == "chunk1":
        a_blk = stationary_ref[0, 0, :, window]
        b_slab = stream_buf[slot]
    else:
        a_blk = stream_buf[slot, :, window]
        b_slab = stationary_ref[0, 0]

    # HIGHEST: on the TPU the default f32 matmul rounds its operands to bf16,
    # which thins the product's structure where terms cancel
    partial = jnp.dot(a_blk, b_slab, preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST)

    if order == "chunk1":
        # out block = this strip; first chunk initializes from C_prev
        @pl.when(j == 0)
        def _init():
            out_ref[0, 0] = c0_ref[0, 0] + partial

        @pl.when(j > 0)
        def _acc():
            out_ref[0, 0] += partial
    else:
        # out block = the whole per-batch result; strips' partials persist in
        # it across outer (chunk) steps — no fast<->slow partial bounce
        @pl.when(j == 0)
        def _init():
            out_ref[0, i] = c0_ref[0, i] + partial

        @pl.when(j > 0)
        def _acc():
            out_ref[0, i] += partial


def _align_slabs(b_chunks: jax.Array, r0s: jax.Array, span: int) -> jax.Array:
    """Zero-pad each staged chunk to ``span`` rows (and whole lane tiles of
    columns) and shift it down by its
    start's offset in its lane-aligned window (``r0 % LANES``): slab row
    ``t`` of chunk ``j`` then holds global B row ``a0_j + t``. The shift
    wraps only the zero padding, since ``span >= chunk_rows + LANES - 1``."""
    pad = [(0, 0), (0, 0), (0, span - b_chunks.shape[2]),
           (0, _lane_pad(b_chunks.shape[3]) - b_chunks.shape[3])]
    slabs = jnp.pad(b_chunks, pad)
    shift = jax.vmap(lambda s, off: jnp.roll(s, off, axis=0))
    return jax.vmap(shift, in_axes=(0, None))(slabs, r0s % LANES)


def _vmem_blocks(order: str, n_ac: int, strip_rows: int, k: int,
                 chunk_rows: int, n: int) -> tuple:
    """``(stationary_block, c_block, buf_shape, workspace)`` of one launch:
    the blocked stationary and C shapes, the streamed operand's two-slot
    scratch, and the body's temporaries in bytes — the windowed A block, the
    partial product, and the bf16 parts that the HIGHEST-precision dot
    splits each operand into."""
    k_pad, span, n_pad = stage_dims(k, chunk_rows, n)
    if order == "chunk1":
        stationary_block = (1, 1, strip_rows, k_pad)
        buf_shape = (dma_schedule.N_SLOTS, span, n_pad)
        c_block = (1, 1, strip_rows, n_pad)
    else:
        stationary_block = (1, 1, span, n_pad)
        buf_shape = (dma_schedule.N_SLOTS, strip_rows, k_pad)
        c_block = (1, n_ac, strip_rows, n_pad)
    f32 = jnp.float32
    workspace = (2 * scoped_vmem.tiled_nbytes((strip_rows, span), f32)
                 + scoped_vmem.tiled_nbytes((span, n_pad), f32)
                 + scoped_vmem.tiled_nbytes((strip_rows, n_pad), f32))
    return stationary_block, c_block, buf_shape, workspace


def _vmem_operands(stationary_block, c_block, buf_shape, workspace) -> dict:
    f32 = jnp.float32
    return dict(blocked=[(stationary_block, f32), (c_block, f32),
                         (c_block, f32)],
                scratch=[(buf_shape, f32)], workspace=workspace)


def vmem_need_bytes(order: str, n_ac: int, strip_rows: int, k: int,
                    chunk_rows: int, n: int) -> int:
    """Scoped VMEM the kernel asks for at this geometry (A strips of
    ``strip_rows x k``, B chunks of ``chunk_rows x n``); a launch whose
    need exceeds ``scoped_vmem.VMEM_CAPACITY_BYTES`` is refused."""
    return scoped_vmem.need_bytes(**_vmem_operands(
        *_vmem_blocks(order, n_ac, strip_rows, k, chunk_rows, n)))


def ranged_spgemm_stream(a_dense: jax.Array, b_chunks: jax.Array,
                         c0: jax.Array, r0s: jax.Array, *, order: str,
                         interpret: bool | None = None) -> jax.Array:
    """Fused streaming multiply ``C[b, i] = sum_j A[b, i][:, r0_j:r1_j] @
    B_chunk[b, j] + C_prev[b, i]`` with explicit double-buffered prefetch.

    Args:
      a_dense:  f32[batch, n_ac, strip_rows, k] — densified A strips.
      b_chunks: f32[batch, n_b, chunk_rows, n] — densified staged B chunks;
        rows past a chunk's true length are zero.
      c0:       f32[batch, n_ac, strip_rows, n] — the fused ``C_prev``.
      r0s:      i32[n_b] — global start row of each B chunk.
      order:    "chunk1" (strips outer, B slabs streamed) or "chunk2"
                (chunks outer, A blocks streamed).

    The operands are staged at :func:`stage_dims`: A zero-padded to
    ``k_pad`` columns, each B chunk placed in its lane-aligned ``span``-row
    window and zero-padded to ``n_pad`` columns, and the windows' starts
    scalar-prefetched; the result is cropped back to ``n`` columns.

    Returns f32[batch, n_ac, strip_rows, n].
    """
    if order not in ("chunk1", "chunk2"):
        raise ValueError(f"unknown streaming order {order!r}")
    batch, n_ac, strip_rows, k = a_dense.shape
    _, n_b, chunk_rows, n = b_chunks.shape
    if c0.shape != (batch, n_ac, strip_rows, n):
        raise ValueError(f"c0 shape {c0.shape} != {(batch, n_ac, strip_rows, n)}")
    interpret = default_interpret() if interpret is None else interpret
    k_pad, span, n_pad = stage_dims(k, chunk_rows, n)
    a_dense = jnp.pad(a_dense.astype(jnp.float32),
                      [(0, 0), (0, 0), (0, 0), (0, k_pad - k)])
    b_slabs = _align_slabs(b_chunks.astype(jnp.float32), r0s, span)
    c0 = jnp.pad(c0.astype(jnp.float32),
                 [(0, 0), (0, 0), (0, 0), (0, n_pad - n)])
    a0s = (r0s - r0s % LANES).astype(jnp.int32)
    f32 = jnp.float32

    stationary_block, c_block, buf_shape, workspace = _vmem_blocks(
        order, n_ac, strip_rows, k, chunk_rows, n)
    if order == "chunk1":
        grid = (batch, n_ac, n_b)
        stationary_map = lambda b, i, j, a0s: (b, i, 0, 0)  # noqa: E731
        streamed, stationary = b_slabs, a_dense
        c_map = lambda b, i, j, a0s: (b, i, 0, 0)           # noqa: E731
    else:
        grid = (batch, n_b, n_ac)
        stationary_map = lambda b, j, i, a0s: (b, j, 0, 0)  # noqa: E731
        streamed, stationary = a_dense, b_slabs
        # one whole-result c0 block per batch element (fetched once, read at
        # j == 0), matching the out block it initializes
        c_map = lambda b, j, i, a0s: (b, 0, 0, 0)           # noqa: E731

    kernel = functools.partial(
        _kernel, order=order, batch=batch, n_ac=n_ac, n_b=n_b, span=span
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec(stationary_block, stationary_map),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(c_block, c_map),
            ],
            out_specs=pl.BlockSpec(c_block, c_map),
            scratch_shapes=[
                pltpu.VMEM(buf_shape, f32),
                pltpu.SemaphoreType.DMA((dma_schedule.N_SLOTS,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((batch, n_ac, strip_rows, n_pad),
                                       f32),
        compiler_params=scoped_vmem.compiler_params(
            **_vmem_operands(stationary_block, c_block, buf_shape, workspace)),
        interpret=interpret,
    )(a0s, stationary, streamed, c0)
    return out[..., :n]
