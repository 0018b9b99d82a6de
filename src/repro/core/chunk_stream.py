"""Streaming chunk executors behind the backend registry.

The paper's three chunk orders (KNL / Chunk1 / Chunk2) admit many numeric
backends — host loop, device scan, hand-DMA'd Pallas pipelines, compressed
accumulators, MXU-blocked tiles. This module implements the executor
*cores* and registers each backend with
``repro.core.backend_registry`` (registrations at the bottom of the file);
every dispatch layer — ``chunked_spgemm``, :func:`chunked_spgemm_batched`,
``SpGEMMService``, the planner's ``backend="auto"`` resolve, the
conformance matrix, the bench lanes — derives its backend set from the
registry rather than naming backends by hand. Adding a backend is a kernel
module plus one ``BackendSpec`` registration (see ``docs/backends.md``).

The registered backends, in registry (= auto tie-break) order:

* ``loop`` — host-driven Python loop (``repro.core.chunking``); every chunk
  boundary is a device round-trip. Retained as the bitwise oracle.
* ``scan`` — the same three algorithms as **one jitted program each**: the
  uniformly-padded B chunks and A/C strips stack host-side into batched
  CSRs (``csr_stack``) and the chunk loop is a ``lax.scan`` with the fused
  ``spgemm_ranged`` body inlined, so the multiply never leaves the device.
  XLA is *free* to double-buffer the slow->fast transfers, but nothing
  forces the overlap.
* ``pallas`` — forces it: ``repro.kernels.ranged_spgemm``'s pallas_call
  hand-DMAs the streamed operand through a two-slot VMEM buffer (copy
  chunk j+1 while chunk j multiplies — the paper's ``copy2Fast`` overlap
  made explicit), accumulating into dense per-strip slabs.
* ``sparse`` — lifts the dense-C memory bound: the same two-slot DMA
  schedule through ``repro.kernels.sparse_accum_spgemm``, accumulating
  into a fixed-capacity **CSR triple in VMEM** sized by the symbolic phase
  (``repro.core.symbolic``) — footprint scales with ``nnz(C)``, not
  ``strip_rows * n_cols``.
* ``hash`` — shrinks the ESC workspace: ``repro.kernels.hash_accum_spgemm``
  merges through per-row linear-probing hash tables sized by the symbolic
  ``c_max_row_nnz`` (densest output row, not the expand size).
* ``bsr`` — trades entry-level sparsity for MXU-shaped tiles: each
  (strip, chunk) pair stages as BSR (``repro.sparse.bsr``) and runs the
  blocked kernel ``repro.kernels.bsr_spgemm``, whose scalar-prefetched
  slot tables schedule one dense ``bs x bs`` MAC per contributing block
  pair (padding slots point at an appended zero-sentinel block). Its
  compile geometry is the envelope's ``bsr_caps`` block bounds
  (``symbolic.bsr_plan_caps``), so the whole *envelope* is the jit key.

``backend="auto"`` is the planner-driven dispatch over the registered
accumulator backends the TPU compiler accepts:
``planner.select_accumulator_backend`` argmins their ``BackendFastModel``
peak-resident byte models — dense slabs, or BSR when the operands are
block-structured (its model prices the ``bs^2``-per-entry padding waste
honestly, and an envelope without block caps prices it at infinity, keeping
block analysis opt-in). ``sparse`` and ``hash`` register a
``mosaic_refusal``: Mosaic does not compile their kernels, so ``auto`` skips
them and asking for them on a TPU raises. The ``accumulator_shootout`` and
``bsr_blocking`` bench lanes measure where the models cross.

Because a traced scan (or Pallas grid) cannot mutate Python-side counters,
ChunkStats for these backends is *computed from the plan*: the uniform padding
makes every staged chunk/strip/partial the same size, so the per-copy event
sequence is reproducible host-side. ``planned_stats`` replays the loop
executors' CSR-staging events (asserted identical in tests);
``planned_stats_pallas`` replays the Pallas pipeline's dense-slab DMA events,
which differ structurally (dense staged sizes; Chunk2's C partials persist in
VMEM instead of bouncing to slow memory).

Each backend's compile accounting is observable through ``TRACE_COUNTS``
under the spec's ``trace_key``/``trace_key_batched`` templates
(``"{alg}"``, ``"{alg}_pallas_batched"``, ...): one bump per (re)trace of
the backend's jitted core, pinned exactly by the conformance suite.

``chunked_spgemm_batched`` runs a backend's batched entry over stacked
problem instances sharing one plan: the many-small-matrices serving
scenario. Batches may mix sparsity structures: every instance is repadded
to a shared ``GeometryEnvelope`` (the batch union, or a caller-provided
bucket envelope) before stacking. ``repro.serve.spgemm_service`` builds
the request-bucketing service on top.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import backend_registry
from repro.core.chunking import (
    ChunkStats, _assemble, a_strips, b_chunks, batch_envelope,
    chunk_gpu1, chunk_gpu2, chunk_knl, instance_envelope,
)
from repro.core.kkmem import TRACE_COUNTS, key_layout, spgemm_ranged_impl
from repro.core.planner import (
    ChunkPlan, check_output_caps, csr_field_nbytes, hash_table_slots,
    planned_stats_bsr, planned_stats_dense_slab, planned_stats_hash,
    planned_stats_sparse, select_accumulator_backend,
)
from repro.core.spans import span
from repro.core.symbolic import masked_output_caps, strip_output_caps
from repro.kernels.bsr_spgemm import bsr_spgemm_blocks, bsr_spgemm_symbolic
from repro.kernels.hash_accum_spgemm import (
    hash_accum_spgemm_stream, hash_masked_accum_spgemm_stream,
)
from repro.kernels.ranged_spgemm import (
    default_interpret, ranged_spgemm_stream, stage_dims,
)
from repro.kernels.sparse_accum_spgemm import sparse_accum_spgemm_stream
from repro.sparse.bsr import bsr_blocks_with_sentinel, bsr_from_dense
from repro.sparse.csr import (
    CSR, GeometryEnvelope, csr_from_dense, csr_pad_to, csr_stack, csr_to_dense,
    csr_unstack,
)

def _empty_c(n_rows: int, n_cols: int, c_pad: int, dtype) -> CSR:
    """Empty C with ``max_row_nnz=c_pad`` so the scan carry has exactly the
    pytree structure ``spgemm_ranged_impl`` returns (aux mismatch would fail
    the carry check)."""
    return CSR(
        indptr=jnp.zeros(n_rows + 1, jnp.int32),
        indices=jnp.zeros(c_pad, jnp.int32),
        data=jnp.zeros(c_pad, dtype),
        shape=(n_rows, n_cols),
        max_row_nnz=c_pad,
    )


def _empty_c_stack(n: int, n_rows: int, n_cols: int, c_pad: int, dtype) -> CSR:
    """Stacked empty partials ([n, ...] leading axis) for the Chunk2 carry."""
    return CSR(
        indptr=jnp.zeros((n, n_rows + 1), jnp.int32),
        indices=jnp.zeros((n, c_pad), jnp.int32),
        data=jnp.zeros((n, c_pad), dtype),
        shape=(n_rows, n_cols),
        max_row_nnz=c_pad,
    )


# ---------------------------------------------------------------------------
# jitted scan cores (one compilation per padded geometry, not per chunk)
# ---------------------------------------------------------------------------


def _knl_scan_impl(A: CSR, Bs: CSR, r0s, r1s, C0: CSR, c_pad: int) -> CSR:
    def body(C, x):
        TRACE_COUNTS["knl_body"] += 1
        Bc, r0, r1 = x
        return spgemm_ranged_impl(A, Bc, r0, r1, C, c_pad), None

    C, _ = lax.scan(body, C0, (Bs, r0s, r1s))
    return C


def _chunk1_scan_impl(As: CSR, Bs: CSR, r0s, r1s, C0: CSR, c_pad: int) -> CSR:
    def outer(carry, Ai):
        def inner(C, x):
            TRACE_COUNTS["chunk1_body"] += 1
            Bc, r0, r1 = x
            return spgemm_ranged_impl(Ai, Bc, r0, r1, C, c_pad), None

        Ci, _ = lax.scan(inner, C0, (Bs, r0s, r1s))
        return carry, Ci

    _, Cs = lax.scan(outer, None, As)
    return Cs


def _chunk2_scan_impl(As: CSR, Bs: CSR, r0s, r1s, C0s: CSR, c_pad: int) -> CSR:
    def outer(Cs, x):
        Bc, r0, r1 = x

        def inner(carry, y):
            TRACE_COUNTS["chunk2_body"] += 1
            Ai, Ci = y
            return carry, spgemm_ranged_impl(Ai, Bc, r0, r1, Ci, c_pad)

        _, Cs2 = lax.scan(inner, None, (As, Cs))
        return Cs2, None

    Cs, _ = lax.scan(outer, C0s, (Bs, r0s, r1s))
    return Cs


@partial(jax.jit, static_argnames=("c_pad",))
def _knl_scan(A: CSR, Bs: CSR, r0s, r1s, C0: CSR, c_pad: int) -> CSR:
    TRACE_COUNTS["knl"] += 1
    return _knl_scan_impl(A, Bs, r0s, r1s, C0, c_pad)


@partial(jax.jit, static_argnames=("c_pad",))
def _chunk1_scan(As: CSR, Bs: CSR, r0s, r1s, C0: CSR, c_pad: int) -> CSR:
    """A/C strips outer (stationary), B chunks inner (streamed). Returns the
    stacked per-strip results ([n_ac] leading axis)."""
    TRACE_COUNTS["chunk1"] += 1
    return _chunk1_scan_impl(As, Bs, r0s, r1s, C0, c_pad)


@partial(jax.jit, static_argnames=("c_pad",))
def _chunk2_scan(As: CSR, Bs: CSR, r0s, r1s, C0s: CSR, c_pad: int) -> CSR:
    """B chunk outer (stationary), A/C strips inner (streamed); all per-strip
    partials ride the scan carry. Returns the stacked per-strip results."""
    TRACE_COUNTS["chunk2"] += 1
    return _chunk2_scan_impl(As, Bs, r0s, r1s, C0s, c_pad)


# Batched (vmapped) cores: one jitted program per (envelope, plan, batch)
# geometry. Each gets its own TRACE_COUNTS key so the serving layer can assert
# "one compile per geometry bucket" directly.


@partial(jax.jit, static_argnames=("c_pad",))
def _knl_scan_batched(Ast: CSR, Bst: CSR, r0s, r1s, C0s: CSR, c_pad: int) -> CSR:
    TRACE_COUNTS["knl_batched"] += 1
    return jax.vmap(
        lambda A, Bs, C0: _knl_scan_impl(A, Bs, r0s, r1s, C0, c_pad)
    )(Ast, Bst, C0s)


@partial(jax.jit, static_argnames=("c_pad",))
def _chunk1_scan_batched(Ast: CSR, Bst: CSR, r0s, r1s, C0: CSR, c_pad: int) -> CSR:
    TRACE_COUNTS["chunk1_batched"] += 1
    return jax.vmap(
        lambda As, Bs: _chunk1_scan_impl(As, Bs, r0s, r1s, C0, c_pad)
    )(Ast, Bst)


@partial(jax.jit, static_argnames=("c_pad",))
def _chunk2_scan_batched(Ast: CSR, Bst: CSR, r0s, r1s, C0s: CSR, c_pad: int) -> CSR:
    TRACE_COUNTS["chunk2_batched"] += 1
    return jax.vmap(
        lambda As, Bs: _chunk2_scan_impl(As, Bs, r0s, r1s, C0s, c_pad)
    )(Ast, Bst)


_SCAN_CORES_BATCHED = {"knl": _knl_scan_batched,
                       "chunk1": _chunk1_scan_batched,
                       "chunk2": _chunk2_scan_batched}


def _make_scan_batched_cores(donate: bool = False) -> dict:
    """A fresh jitted set of the three batched scan cores (same
    ``TRACE_COUNTS`` keys as the module-level set, so compile accounting is
    backend-uniform regardless of which set ran). Module-level cores cache
    compilations in a module-global jit cache for the life of the process;
    a caller that owns a set from this factory (a serving bucket) is the
    sole owner of its executables, so dropping the set really frees them.

    ``donate=True`` donates the knl C-accumulator stack — the one scan core
    whose output aliases its ``C0s`` argument shape-for-shape, letting XLA
    write results into the staged accumulator's buffer. The chunk1/chunk2
    ``C0`` is a shared per-strip template the vmap broadcasts, so its shape
    never matches the stacked output and donation would only warn."""
    knl_jit = partial(jax.jit, static_argnames=("c_pad",),
                      donate_argnums=(4,) if donate else ())

    @knl_jit
    def knl(Ast: CSR, Bst: CSR, r0s, r1s, C0s: CSR, c_pad: int) -> CSR:
        TRACE_COUNTS["knl_batched"] += 1
        return jax.vmap(
            lambda A, Bc, C0: _knl_scan_impl(A, Bc, r0s, r1s, C0, c_pad)
        )(Ast, Bst, C0s)

    @partial(jax.jit, static_argnames=("c_pad",))
    def chunk1(Ast: CSR, Bst: CSR, r0s, r1s, C0: CSR, c_pad: int) -> CSR:
        TRACE_COUNTS["chunk1_batched"] += 1
        return jax.vmap(
            lambda As, Bs: _chunk1_scan_impl(As, Bs, r0s, r1s, C0, c_pad)
        )(Ast, Bst)

    @partial(jax.jit, static_argnames=("c_pad",))
    def chunk2(Ast: CSR, Bst: CSR, r0s, r1s, C0s: CSR, c_pad: int) -> CSR:
        TRACE_COUNTS["chunk2_batched"] += 1
        return jax.vmap(
            lambda As, Bs: _chunk2_scan_impl(As, Bs, r0s, r1s, C0s, c_pad)
        )(Ast, Bst)

    return {"knl": knl, "chunk1": chunk1, "chunk2": chunk2}


# ---------------------------------------------------------------------------
# plan-derived copy accounting (the scan cannot mutate Python stats)
# ---------------------------------------------------------------------------


def planned_stats(plan: ChunkPlan, chunk_nbytes: int, strip_nbytes: int,
                  c_strip_nbytes: int) -> ChunkStats:
    """Replay the loop executors' per-copy event sequence from the plan.

    Uniform padding makes every B chunk / A strip / C partial the same size,
    so the event stream is fully determined by (algorithm, n_ac, n_b) plus the
    three footprints — tests assert event-for-event equality with the loop.
    """
    stats = ChunkStats(plan.algorithm, plan.n_ac, plan.n_b)
    if plan.algorithm == "knl":
        for _ in range(plan.n_b):
            stats.add_in(chunk_nbytes)
        stats.kernel_calls = plan.n_b
        return stats
    if plan.algorithm == "chunk1":
        for a0, a1 in zip(plan.p_ac[:-1], plan.p_ac[1:]):
            stats.add_in(strip_nbytes)
            stats.add_in((a1 - a0 + 1) * 4)
            for _ in range(plan.n_b):
                stats.add_in(chunk_nbytes)
                stats.kernel_calls += 1
            stats.add_out(c_strip_nbytes)
        return stats
    if plan.algorithm == "chunk2":
        for jb in range(plan.n_b):
            stats.add_in(chunk_nbytes)
            for _ in range(plan.n_ac):
                stats.add_in(strip_nbytes)
                if jb > 0:
                    stats.add_in(c_strip_nbytes)
                stats.kernel_calls += 1
                if jb < plan.n_b - 1:
                    stats.add_out(c_strip_nbytes)
            if jb == plan.n_b - 1:
                for _ in range(plan.n_ac):
                    stats.add_out(c_strip_nbytes)
        return stats
    raise ValueError(f"unknown algorithm {plan.algorithm!r}")


def _c_strip_nbytes(strip_rows: int, c_pad: int, dtype) -> int:
    itemsize = jnp.dtype(dtype).itemsize
    return (strip_rows + 1) * 4 + c_pad * (4 + itemsize)


def planned_stats_pallas(plan: ChunkPlan, slab_nbytes: int, a_stage_nbytes: int,
                         c_stage_nbytes: int) -> ChunkStats:
    """Replay the Pallas pipeline's per-copy event sequence from the plan.

    The event model differs from :func:`planned_stats` in three structural
    ways, all of them properties of the kernel rather than modeling choices:

      * staged pieces are **dense** (slab = ``chunk_rows x n`` floats, strip =
        ``strip_rows x k_pad`` floats), not padded CSR triples;
      * the stationary operand is staged by the Pallas pipeline once per outer
        step, and the streamed operand is hand-DMA'd once per grid step — the
        double-buffer *overlaps* those copies with compute but their byte
        volume is unchanged;
      * in the Chunk2 order the per-strip C partials persist in the VMEM
        output block across outer steps, so the ``(n_b - 1)`` per-strip
        out+in partial bounces of the loop/scan model collapse into **one**
        whole-block ``C_prev`` fetch and **one** final writeback: the kernel
        maps all ``n_ac`` partials as a single ``(n_ac, strip_rows, n)``
        block at a constant index, so the pipeline stages it as one copy
        event of ``n_ac * c_stage_nbytes``, not ``n_ac`` per-strip events
        (the traffic-equality audit holds this model to the traced jaxpr
        event-for-event).
    """
    stats = ChunkStats(plan.algorithm, plan.n_ac, plan.n_b)
    if plan.algorithm in ("knl", "chunk1"):
        for _ in range(plan.n_ac):           # knl is the 1-strip special case
            stats.add_in(a_stage_nbytes)     # stationary strip -> VMEM
            stats.add_in(c_stage_nbytes)     # fused C_prev block
            for _ in range(plan.n_b):
                stats.add_in(slab_nbytes)    # double-buffered slab DMA
                stats.kernel_calls += 1
            stats.add_out(c_stage_nbytes)    # strip result writeback
        return stats
    if plan.algorithm == "chunk2":
        for jb in range(plan.n_b):
            stats.add_in(slab_nbytes)        # stationary chunk -> VMEM
            if jb == 0:
                # C_prev: one whole-block fetch (all n_ac partials at once)
                stats.add_in(plan.n_ac * c_stage_nbytes)
            for _ in range(plan.n_ac):
                stats.add_in(a_stage_nbytes)       # streamed strip DMA
                stats.kernel_calls += 1
        # single whole-block final writeback
        stats.add_out(plan.n_ac * c_stage_nbytes)
        return stats
    raise ValueError(f"unknown algorithm {plan.algorithm!r}")


def _pallas_stage_nbytes(strip_rows: int, k: int, chunk_rows: int,
                         n: int) -> tuple:
    """(slab, a_stage, c_stage) dense staged footprints in bytes (f32), at
    the kernel's lane-aligned staging (``ranged_spgemm.stage_dims``)."""
    k_pad, span, n_pad = stage_dims(k, chunk_rows, n)
    return span * n_pad * 4, strip_rows * k_pad * 4, strip_rows * n_pad * 4


# ---------------------------------------------------------------------------
# executors (drop-in signatures of chunk_knl / chunk_gpu1 / chunk_gpu2)
# ---------------------------------------------------------------------------


def _stage(A: CSR | None, B: CSR, plan: ChunkPlan, sp) -> tuple:
    """On the device: the strips of ``A`` (none for ``None``) and the chunks
    of ``B``, each as a list and as a stack, and the plan's B-row ranges."""
    strips = None if A is None else a_strips(A, plan.p_ac)
    chunks = b_chunks(B, plan.p_b)
    r0s, r1s = sp.put(plan.b_ranges())
    As = None if strips is None else csr_stack(strips)
    return strips, chunks, As, csr_stack(chunks), r0s, r1s


def chunk_knl_scan(A: CSR, B: CSR, plan: ChunkPlan, c_pad: int):
    with span("stage") as sp:
        _, chunks, _, Bs, r0s, r1s = _stage(None, B, plan, sp)
        C0 = _empty_c(A.n_rows, B.n_cols, c_pad, A.dtype)
    with span("launch") as sp:
        sp.note(key=key_layout(A.n_rows, B.n_cols))
        C = _knl_scan(A, Bs, r0s, r1s, C0, c_pad)
    stats = planned_stats(plan, chunks[0].nbytes(), 0, 0)
    return C, stats


def chunk_gpu1_scan(A: CSR, B: CSR, plan: ChunkPlan, c_pad: int):
    with span("stage") as sp:
        strips, chunks, As, Bs, r0s, r1s = _stage(A, B, plan, sp)
        strip_rows = strips[0].n_rows
        C0 = _empty_c(strip_rows, B.n_cols, c_pad, A.dtype)
    with span("launch") as sp:
        sp.note(key=key_layout(strip_rows, B.n_cols))
        Cs = _chunk1_scan(As, Bs, r0s, r1s, C0, c_pad)
    stats = planned_stats(plan, chunks[0].nbytes(), strips[0].nbytes(),
                          _c_strip_nbytes(strip_rows, c_pad, A.dtype))
    return _assemble(Cs, plan.p_ac, B.n_cols), stats


def chunk_gpu2_scan(A: CSR, B: CSR, plan: ChunkPlan, c_pad: int):
    with span("stage") as sp:
        strips, chunks, As, Bs, r0s, r1s = _stage(A, B, plan, sp)
        strip_rows = strips[0].n_rows
        C0s = _empty_c_stack(plan.n_ac, strip_rows, B.n_cols, c_pad, A.dtype)
    with span("launch") as sp:
        sp.note(key=key_layout(strip_rows, B.n_cols))
        Cs = _chunk2_scan(As, Bs, r0s, r1s, C0s, c_pad)
    stats = planned_stats(plan, chunks[0].nbytes(), strips[0].nbytes(),
                          _c_strip_nbytes(strip_rows, c_pad, A.dtype))
    return _assemble(Cs, plan.p_ac, B.n_cols), stats


# ---------------------------------------------------------------------------
# Pallas backend: explicit double-buffered prefetch (kernels/ranged_spgemm)
# ---------------------------------------------------------------------------


def _dense_stack(stacked: CSR, levels: int = 1) -> jax.Array:
    """Densify a (possibly doubly) ``csr_stack``-ed CSR: ``levels`` leading
    stack axes become leading dense axes."""
    shape, mrn = stacked.shape, stacked.max_row_nnz

    def densify(ip, ix, d):
        return csr_to_dense(CSR(ip, ix, d, shape, mrn))

    fn = densify
    for _ in range(levels):
        fn = jax.vmap(fn)
    return fn(stacked.indptr, stacked.indices, stacked.data)


def _make_pallas_core(key: str, order: str, *, batched: bool, strips: bool):
    """One jitted staging-and-launch core; the six variants differ only in
    the streaming order, the trace-counter key, and whether A arrives as a
    plain CSR (knl), a strip stack, or a per-instance (doubly) stacked batch.

    Batched cores ride the batch on a leading grid dimension of the same
    kernel (one pallas_call for the whole microbatch — no vmap-of-pallas),
    with their own TRACE_COUNTS keys so the serving layer's compile
    accounting stays exact.
    """
    a_levels = (1 if strips else 0) + (1 if batched else 0)

    @jax.jit
    def core(Ast: CSR, Bst: CSR, r0s) -> jax.Array:
        TRACE_COUNTS[key] += 1
        a = _dense_stack(Ast, levels=a_levels)
        slabs = _dense_stack(Bst, levels=2 if batched else 1)
        if not strips:               # knl: the whole A is the single strip
            a = a[:, None] if batched else a[None]
        if not batched:              # width-1 batch axis
            a, slabs = a[None], slabs[None]
        c0 = jnp.zeros(a.shape[:3] + (Bst.n_cols,), jnp.float32)
        out = ranged_spgemm_stream(a, slabs, c0, r0s, order=order)
        if not batched:
            out = out[0]
        if not strips:
            out = out[:, 0] if batched else out[0]
        return out

    return core


_knl_pallas = _make_pallas_core("knl_pallas", "chunk1",
                                batched=False, strips=False)
_chunk1_pallas = _make_pallas_core("chunk1_pallas", "chunk1",
                                   batched=False, strips=True)
_chunk2_pallas = _make_pallas_core("chunk2_pallas", "chunk2",
                                   batched=False, strips=True)
_knl_pallas_batched = _make_pallas_core("knl_pallas_batched", "chunk1",
                                        batched=True, strips=False)
_chunk1_pallas_batched = _make_pallas_core("chunk1_pallas_batched", "chunk1",
                                           batched=True, strips=True)
_chunk2_pallas_batched = _make_pallas_core("chunk2_pallas_batched", "chunk2",
                                           batched=True, strips=True)

_PALLAS_CORES_BATCHED = {"knl": _knl_pallas_batched,
                         "chunk1": _chunk1_pallas_batched,
                         "chunk2": _chunk2_pallas_batched}


def _make_pallas_batched_cores(donate: bool = False) -> dict:
    """Fresh jitted batched Pallas cores (see ``_make_scan_batched_cores``
    for why a caller-owned set exists). The dense accumulator is allocated
    inside the jit and the staged CSR operands never alias the dense
    outputs, so there is nothing donation could usefully alias here."""
    del donate
    return {
        "knl": _make_pallas_core("knl_pallas_batched", "chunk1",
                                 batched=True, strips=False),
        "chunk1": _make_pallas_core("chunk1_pallas_batched", "chunk1",
                                    batched=True, strips=True),
        "chunk2": _make_pallas_core("chunk2_pallas_batched", "chunk2",
                                    batched=True, strips=True),
    }


def _pallas_assemble(dense, p_ac: tuple, dtype) -> CSR:
    """Crop per-strip dense results (``[n_ac, rows, n]``, or one ``[rows,
    n]`` block) to their true rows, concatenate, and sparsify on the host.
    The Pallas backend's CSR keeps exactly the nonzeros of the dense result,
    so comparisons against the loop oracle are allclose on the densified
    values rather than bitwise on padding structure."""
    with span("wait"):
        jax.block_until_ready(dense)
    with span("fetch") as sp:
        dense = sp.get(dense)
    with span("assemble") as sp:
        if dense.ndim == 2:
            dense = dense[None]
        whole = np.concatenate([
            dense[i][: e - s]
            for i, (s, e) in enumerate(zip(p_ac[:-1], p_ac[1:]))
        ])
        C = csr_from_dense(whole.astype(dtype))
        sp.add(h2d_bytes=C.nbytes)
        return C


def chunk_knl_pallas(A: CSR, B: CSR, plan: ChunkPlan, c_pad: int):
    del c_pad  # capacity is implicit in the dense accumulator
    with span("stage") as sp:
        _, _, _, Bs, r0s, _ = _stage(None, B, plan, sp)
    with span("launch"):
        dense = _knl_pallas(A, Bs, r0s)
    stats = planned_stats_pallas(
        plan, *_pallas_stage_nbytes(A.n_rows, A.n_cols, Bs.n_rows, B.n_cols))
    return _pallas_assemble(dense, (0, A.n_rows), np.dtype(A.dtype)), stats


def chunk_gpu1_pallas(A: CSR, B: CSR, plan: ChunkPlan, c_pad: int):
    del c_pad
    with span("stage") as sp:
        _, _, As, Bs, r0s, _ = _stage(A, B, plan, sp)
    with span("launch"):
        dense = _chunk1_pallas(As, Bs, r0s)
    stats = planned_stats_pallas(
        plan, *_pallas_stage_nbytes(As.n_rows, A.n_cols, Bs.n_rows, B.n_cols))
    return _pallas_assemble(dense, plan.p_ac, np.dtype(A.dtype)), stats


def chunk_gpu2_pallas(A: CSR, B: CSR, plan: ChunkPlan, c_pad: int):
    del c_pad
    with span("stage") as sp:
        _, _, As, Bs, r0s, _ = _stage(A, B, plan, sp)
    with span("launch"):
        dense = _chunk2_pallas(As, Bs, r0s)
    stats = planned_stats_pallas(
        plan, *_pallas_stage_nbytes(As.n_rows, A.n_cols, Bs.n_rows, B.n_cols))
    return _pallas_assemble(dense, plan.p_ac, np.dtype(A.dtype)), stats


# ---------------------------------------------------------------------------
# Sparse-output backend: CSR-native accumulator (kernels/sparse_accum_spgemm)
# ---------------------------------------------------------------------------


def _sparse_c0_stack(batch: int, n_ac: int, strip_rows: int, n_cols: int,
                     c_cap: int, dtype) -> CSR:
    """Empty stacked C_prev strips ([batch, n_ac] leading axes) at the CSR
    scratch capacity ``c_cap`` (the symbolic phase's strip output bound)."""
    return CSR(
        indptr=jnp.zeros((batch, n_ac, strip_rows + 1), jnp.int32),
        indices=jnp.zeros((batch, n_ac, c_cap), jnp.int32),
        data=jnp.zeros((batch, n_ac, c_cap), dtype),
        shape=(strip_rows, n_cols),
        max_row_nnz=c_cap,
    )


def _make_sparse_core(key: str, order: str, donate: bool = False):
    """One jitted launch core for the sparse-output kernel; the six variants
    differ only in the streaming order and the trace-counter key (all staging
    is host-side, so batched cores share the same body — the batch rides the
    kernel's leading grid dimension). ``donate=True`` donates the ``C0st``
    scratch stack, whose (indptr, indices, data) leaves match the kernel
    outputs shape-for-shape — the serving layer allocates it fresh per
    flush, so XLA may write results straight into it."""

    @partial(jax.jit, donate_argnums=(2,) if donate else ())
    def core(Ast: CSR, Bst: CSR, C0st: CSR, r0s, r1s):
        TRACE_COUNTS[key] += 1
        return sparse_accum_spgemm_stream(Ast, Bst, C0st, r0s, r1s,
                                          order=order)

    return core


_knl_sparse = _make_sparse_core("knl_sparse", "chunk1")
_chunk1_sparse = _make_sparse_core("chunk1_sparse", "chunk1")
_chunk2_sparse = _make_sparse_core("chunk2_sparse", "chunk2")
_knl_sparse_batched = _make_sparse_core("knl_sparse_batched", "chunk1")
_chunk1_sparse_batched = _make_sparse_core("chunk1_sparse_batched", "chunk1")
_chunk2_sparse_batched = _make_sparse_core("chunk2_sparse_batched", "chunk2")

_SPARSE_CORES = {"knl": _knl_sparse, "chunk1": _chunk1_sparse,
                 "chunk2": _chunk2_sparse}
_SPARSE_CORES_BATCHED = {"knl": _knl_sparse_batched,
                         "chunk1": _chunk1_sparse_batched,
                         "chunk2": _chunk2_sparse_batched}


def _make_hash_core(key: str, order: str, donate: bool = False):
    """Launch core for the hash-probe kernel; ``table_size`` (the per-row
    hash-table slot count, from the envelope's ``c_max_row_nnz``) is a static
    jit argument, so two geometries differing only in the densest-output-row
    bound compile separate tables — exactly the retrace the envelope's
    ``c_max_row_nnz`` field exists to key. ``donate`` as in
    :func:`_make_sparse_core` (the ``C0st`` scratch aliases the outputs)."""

    @partial(jax.jit, static_argnames=("table_size",),
             donate_argnums=(2,) if donate else ())
    def core(Ast: CSR, Bst: CSR, C0st: CSR, r0s, r1s, table_size: int):
        TRACE_COUNTS[key] += 1
        return hash_accum_spgemm_stream(Ast, Bst, C0st, r0s, r1s,
                                        order=order, table_size=table_size)

    return core


_knl_hash = _make_hash_core("knl_hash", "chunk1")
_chunk1_hash = _make_hash_core("chunk1_hash", "chunk1")
_chunk2_hash = _make_hash_core("chunk2_hash", "chunk2")
_knl_hash_batched = _make_hash_core("knl_hash_batched", "chunk1")
_chunk1_hash_batched = _make_hash_core("chunk1_hash_batched", "chunk1")
_chunk2_hash_batched = _make_hash_core("chunk2_hash_batched", "chunk2")

_HASH_CORES = {"knl": _knl_hash, "chunk1": _chunk1_hash,
               "chunk2": _chunk2_hash}
_HASH_CORES_BATCHED = {"knl": _knl_hash_batched,
                       "chunk1": _chunk1_hash_batched,
                       "chunk2": _chunk2_hash_batched}

_CSR_ACCUM_ORDERS = {"knl": "chunk1", "chunk1": "chunk1", "chunk2": "chunk2"}


def _make_sparse_batched_cores(donate: bool = False) -> dict:
    """Fresh jitted batched ESC cores (caller-owned executables; see
    ``_make_scan_batched_cores``)."""
    return {alg: _make_sparse_core(f"{alg}_sparse_batched", order,
                                   donate=donate)
            for alg, order in _CSR_ACCUM_ORDERS.items()}


def _make_hash_batched_cores(donate: bool = False) -> dict:
    """Fresh jitted batched hash-probe cores (caller-owned executables)."""
    return {alg: _make_hash_core(f"{alg}_hash_batched", order, donate=donate)
            for alg, order in _CSR_ACCUM_ORDERS.items()}


def _sparse_run(A: CSR, B: CSR, plan: ChunkPlan, c_pad: int, backend: str,
                caps=None):
    """Shared body of the unbatched sparse-output executors (ESC and hash):
    stage CSR strips and chunks (knl is the 1-strip special case of the
    chunk1 order), validate the realized output structure against the
    capacities, launch, and assemble the accumulated strip CSRs.

    ``caps`` is the symbolic phase's :class:`StripOutputCaps` when the caller
    (the ``chunked_spgemm`` dispatch) already ran the expansion — the
    symbolic module's amortization contract; recomputed here only for direct
    executor calls.

    The per-copy event model is structurally the Pallas pipeline's
    (:func:`planned_stats_pallas`: stationary operand staged once per outer
    step, streamed triple DMA'd per grid step, C persists in VMEM with one
    final writeback) — only the staged byte sizes differ: padded **CSR**
    footprints instead of dense slabs.
    """
    if caps is None:
        caps = strip_output_caps(A, B, plan.p_ac)
    table = (hash_table_slots(caps.c_max_row_nnz) if backend == "hash"
             else None)
    check_output_caps(caps.strip_nnz, caps.c_max_row_nnz, c_pad, table,
                      backend=backend, a_shape=A.shape, b_shape=B.shape)
    with span("stage") as sp:
        strips, chunks, As, Bs, r0s, r1s = _stage(A, B, plan, sp)
        Ast, Bst = csr_stack([As]), csr_stack([Bs])
        strip_rows = strips[0].n_rows
        C0 = _sparse_c0_stack(1, plan.n_ac, strip_rows, B.n_cols, c_pad,
                              A.dtype)
    with span("launch"):
        if backend == "hash":
            ip, ix, d = _HASH_CORES[plan.algorithm](
                Ast, Bst, C0, r0s, r1s, table_size=table)
        else:
            ip, ix, d = _SPARSE_CORES[plan.algorithm](Ast, Bst, C0, r0s, r1s)
    stats = planned_stats_pallas(
        plan, chunks[0].nbytes(), strips[0].nbytes(),
        _c_strip_nbytes(strip_rows, c_pad, A.dtype))
    out = CSR(ip[0], ix[0], d[0], (strip_rows, B.n_cols), c_pad)
    return _assemble(out, plan.p_ac, B.n_cols), stats


def chunk_sparse(A: CSR, B: CSR, plan: ChunkPlan, c_pad: int, caps=None):
    """ESC sparse-output executor for any plan algorithm (``_sparse_run``
    dispatches the core on ``plan.algorithm``, so unlike the scan/pallas
    backends there is no per-algorithm staging difference to name)."""
    return _sparse_run(A, B, plan, c_pad, "sparse", caps=caps)


def chunk_hash(A: CSR, B: CSR, plan: ChunkPlan, c_pad: int, caps=None):
    """Hash-probe executor for any plan algorithm (see :func:`chunk_sparse`)."""
    return _sparse_run(A, B, plan, c_pad, "hash", caps=caps)


# ---------------------------------------------------------------------------
# masked hash executor: fused output mask (triangle counting's fast path)
# ---------------------------------------------------------------------------


def _make_masked_hash_core(key: str, order: str):
    """Launch core for the mask-fused hash kernel (``_make_hash_core`` with
    the mask's stacked structure as a fourth staged operand and the
    probe-only masked merge plugged in). Own ``TRACE_COUNTS`` keys: a masked
    product is a different program than the unmasked hash product, so its
    compile accounting must not alias the unmasked cores'."""

    @partial(jax.jit, static_argnames=("table_size",))
    def core(Ast: CSR, Bst: CSR, C0st: CSR, Mst: CSR, r0s, r1s,
             table_size: int):
        TRACE_COUNTS[key] += 1
        return hash_masked_accum_spgemm_stream(
            Ast, Bst, C0st, Mst, r0s, r1s, order=order,
            table_size=table_size)

    return core


_HASH_MASKED_CORES = {
    alg: _make_masked_hash_core(f"{alg}_hash_masked", order)
    for alg, order in {"knl": "chunk1", "chunk1": "chunk1",
                       "chunk2": "chunk2"}.items()
}


def chunk_hash_masked(A: CSR, B: CSR, mask: CSR, plan: ChunkPlan,
                      c_pad: int, caps=None):
    """Mask-fused hash executor: ``C = (A x B) ∘ mask``, mask inside the
    kernel.

    The registry's ``run_masked`` capability for the hash backend. C's
    structure is pinned to the mask's (explicit zeros where no product
    lands), so *every* capacity derives from the mask alone
    (``symbolic.masked_output_caps``): the probe tables are sized from the
    densest mask row and the CSR scratch from the largest strip's mask nnz
    — the unmasked product's structure is never expanded, let alone
    materialized. ``caps`` amortizes the (cheap, mask-only) host pass like
    the unmasked executors' ``StripOutputCaps``.
    """
    if mask.shape != (A.n_rows, B.n_cols):
        raise ValueError(
            f"mask shape {mask.shape} != output shape "
            f"{(A.n_rows, B.n_cols)}")
    if caps is None:
        caps = masked_output_caps(mask, plan.p_ac)
    table = hash_table_slots(caps.c_max_row_nnz)
    check_output_caps(caps.strip_nnz, caps.c_max_row_nnz, c_pad, table,
                      backend="hash", a_shape=A.shape, b_shape=B.shape)
    with span("stage") as sp:
        strips, chunks, As, Bs, r0s, r1s = _stage(A, B, plan, sp)
        mstrips = a_strips(mask, plan.p_ac)
        Ast, Bst = csr_stack([As]), csr_stack([Bs])
        Mst = csr_stack([csr_stack(mstrips)])
        strip_rows = strips[0].n_rows
        C0 = _sparse_c0_stack(1, plan.n_ac, strip_rows, B.n_cols, c_pad,
                              A.dtype)
    with span("launch"):
        ip, ix, d = _HASH_MASKED_CORES[plan.algorithm](
            Ast, Bst, C0, Mst, r0s, r1s, table_size=table)
    stats = planned_stats_pallas(
        plan, chunks[0].nbytes(), strips[0].nbytes(),
        _c_strip_nbytes(strip_rows, c_pad, A.dtype))
    # the mask's structure operands (indptr + indices, no data) stage with
    # the fused C_prev block's index maps: once per strip in the chunk1
    # orders, one whole block in chunk2
    m_struct = (strip_rows + 1) * 4 + mstrips[0].indices.shape[-1] * 4
    if plan.algorithm == "chunk2":
        stats.add_in(plan.n_ac * m_struct)
    else:
        for _ in range(plan.n_ac):
            stats.add_in(m_struct)
    out = CSR(ip[0], ix[0], d[0], (strip_rows, B.n_cols), c_pad)
    return _assemble(out, plan.p_ac, B.n_cols), stats


# ---------------------------------------------------------------------------
# BSR backend: MXU-blocked tiles (kernels/bsr_spgemm), envelope-keyed cores
# ---------------------------------------------------------------------------

_BSR_DEFAULT_BLOCK = 8


def _make_bsr_core(key: str, *, batched: bool):
    """One jitted launch core for the blocked kernel. The whole
    :class:`GeometryEnvelope` is the *static* jit key: the kernel geometry
    (``nc_pad``, ``u_max``, ``bs``) comes from its ``bsr_caps``, and keying
    on the envelope — not just the caps — gives the backend the same
    retrace-per-envelope semantics as every other backend (two geometries
    whose block caps happen to quantize equal still account separately).

    Batched cores take width-stacked operands (leading axis) and unroll the
    width inside the jit, so the serving layer's width-ladder compile
    accounting sees one (re)trace per (envelope, width)."""

    @partial(jax.jit, static_argnames=("envelope",))
    def core(a_blocks, b_blocks, a_slots, b_slots,
             envelope: GeometryEnvelope):
        TRACE_COUNTS[key] += 1
        bs, _, _, nc_pad, u_max = envelope.bsr_caps
        interpret = default_interpret()

        def one(ab, bb, asl, bsl):
            return bsr_spgemm_blocks(ab, bb, asl, bsl, nc_pad=nc_pad,
                                     u_max=u_max, bs=bs, interpret=interpret)

        if batched:
            return jnp.stack([
                one(a_blocks[w], b_blocks[w], a_slots[w], b_slots[w])
                for w in range(a_blocks.shape[0])
            ])
        return one(a_blocks, b_blocks, a_slots, b_slots)

    return core


_BSR_CORES = {alg: _make_bsr_core(f"{alg}_bsr", batched=False)
              for alg in ("knl", "chunk1", "chunk2")}
_BSR_CORES_BATCHED = {alg: _make_bsr_core(f"{alg}_bsr_batched", batched=True)
                      for alg in ("knl", "chunk1", "chunk2")}


def _make_bsr_batched_cores(donate: bool = False) -> dict:
    """Fresh jitted batched BSR cores (caller-owned executables). Staging is
    a host loop over (strip, chunk) pairs, so there is no device scratch to
    donate."""
    del donate
    return {alg: _make_bsr_core(f"{alg}_bsr_batched", batched=True)
            for alg in ("knl", "chunk1", "chunk2")}


def _bsr_execute(As, Bs, plan: ChunkPlan, envelope: GeometryEnvelope, *,
                 batched: bool, cores: dict | None = None):
    """Shared body of the BSR executors: stage every (strip, chunk) pair as
    BSR at the envelope's block caps, launch the blocked kernel per pair
    (Chunk2 streams strips under a stationary chunk, the other orders stream
    chunks under a stationary strip), and accumulate the per-pair outputs
    into per-strip dense C.

    Staging is host-side (like the symbolic phase): the pair's A piece is
    the strip's rows with columns outside the chunk zeroed, at full
    contraction width, and the B piece is the chunk's rows at full output
    width — so summing pair products over chunks is exactly the strip
    product. The per-pair block symbolic runs at the envelope's ``nc``/``u``
    floors, which makes every pair one kernel geometry (and fails loudly if
    the envelope does not dominate an instance). Accumulation and staging
    are f32, so comparisons against the loop oracle are allclose on values,
    like the Pallas dense-slab backend."""
    bs, nbl_a_cap, nbl_b_cap, nc_cap, u_cap = envelope.bsr_caps
    width = len(As)
    k, n = Bs[0].shape
    kpad = -(-k // bs) * bs
    npad = -(-n // bs) * bs
    srpad = -(-envelope.strip_rows // bs) * bs
    mbs, nbp = srpad // bs, npad // bs
    np_dtype = np.dtype(As[0].dtype)
    Ads = [np.asarray(csr_to_dense(A), np.float32) for A in As]
    Bds = [np.asarray(csr_to_dense(B), np.float32) for B in Bs]
    strips = list(zip(plan.p_ac[:-1], plan.p_ac[1:]))
    chunks = list(zip(plan.p_b[:-1], plan.p_b[1:]))
    if cores is None:
        cores = _BSR_CORES_BATCHED if batched else _BSR_CORES
    core = cores[plan.algorithm]
    accs = np.zeros((width, len(strips), mbs, nbp, bs, bs), np.float32)
    pairs = ([(ia, jb) for jb in range(len(chunks))
              for ia in range(len(strips))]
             if plan.algorithm == "chunk2" else
             [(ia, jb) for ia in range(len(strips))
              for jb in range(len(chunks))])
    for ia, jb in pairs:
        s, e = strips[ia]
        r0, r1 = chunks[jb]
        a_bl, b_bl, a_sl, b_sl, metas = [], [], [], [], []
        for w in range(width):
            Am = np.zeros((srpad, kpad), np.float32)
            Am[: e - s, r0:r1] = Ads[w][s:e, r0:r1]
            Bm = np.zeros((kpad, npad), np.float32)
            Bm[r0:r1, :n] = Bds[w][r0:r1, :]
            Ab = bsr_from_dense(Am, bs, pad_to=nbl_a_cap)
            Bb = bsr_from_dense(Bm, bs, pad_to=nbl_b_cap)
            meta = bsr_spgemm_symbolic(Ab, Bb, nc_pad=nc_cap, u_max=u_cap)
            metas.append(meta)
            a_bl.append(bsr_blocks_with_sentinel(Ab))
            b_bl.append(bsr_blocks_with_sentinel(Bb))
            a_sl.append(jnp.asarray(meta.a_slots))
            b_sl.append(jnp.asarray(meta.b_slots))
        if batched:
            out = core(jnp.stack(a_bl), jnp.stack(b_bl), jnp.stack(a_sl),
                       jnp.stack(b_sl), envelope=envelope)
        else:
            out = core(a_bl[0], b_bl[0], a_sl[0], b_sl[0],
                       envelope=envelope)[None]
        out_np = np.asarray(out)
        for w, meta in enumerate(metas):
            n_c = meta.n_c_blocks
            if not n_c:
                continue
            # crop to the real blocks: padded rows carry c_indices == 0 and
            # would alias block column 0 of every strip if scattered
            brows = np.repeat(np.arange(mbs),
                              np.diff(np.asarray(meta.c_indptr, np.int64)))
            np.add.at(accs[w, ia], (brows, meta.c_indices[:n_c]),
                      out_np[w, :n_c])
    block_bytes = bs * bs * 4
    slab = (kpad // bs + 1) * 4 + nbl_b_cap * (4 + block_bytes) + block_bytes
    a_stage = (mbs + 1) * 4 + nbl_a_cap * (4 + block_bytes) + block_bytes
    c_stage = (mbs + 1) * 4 + nc_cap * (4 + block_bytes)
    stats = planned_stats_pallas(plan, slab, a_stage, c_stage)
    out_csrs = []
    for w in range(width):
        dense = accs[w].transpose(0, 1, 3, 2, 4).reshape(len(strips), srpad,
                                                         npad)
        whole = np.concatenate([
            dense[i][: e - s, :n] for i, (s, e) in enumerate(strips)
        ])
        out_csrs.append(csr_from_dense(whole.astype(np_dtype)))
    return out_csrs, stats


def chunk_bsr(A: CSR, B: CSR, plan: ChunkPlan, c_pad: int, caps=None,
              block_size: int | None = None):
    """Blocked-tile executor for any plan algorithm (``_bsr_execute``
    orders the pair loop by ``plan.algorithm``). Builds the block-capped
    instance envelope itself when called directly; the dispatch passes
    ``caps`` to amortize the element-level symbolic phase and
    ``block_size`` to override the registered default block edge."""
    env = instance_envelope(A, B, plan, c_pad=c_pad, caps=caps,
                            block_size=block_size or _BSR_DEFAULT_BLOCK)
    out, stats = _bsr_execute([A], [B], plan, env, batched=False)
    return out[0], stats


# ---------------------------------------------------------------------------
# batched entry point: many problem instances, one plan, one compilation
# ---------------------------------------------------------------------------


def _stage_chunks_batched(Bs, plan: ChunkPlan, envelope: GeometryEnvelope):
    """Every instance's B chunks repadded to the envelope and doubly stacked
    ([batch, n_b, ...]); returns the stack and one staged chunk's bytes."""
    chunk_lists = [b_chunks(B, plan.p_b, envelope=envelope) for B in Bs]
    return (csr_stack([csr_stack(cl) for cl in chunk_lists]),
            chunk_lists[0][0].nbytes())


def _stage_strips_batched(As, plan: ChunkPlan, envelope: GeometryEnvelope):
    """Every instance's A strips repadded to the envelope and doubly stacked
    ([batch, n_ac, ...]); returns the stack and one staged strip's bytes."""
    strip_lists = [a_strips(A, plan.p_ac, envelope=envelope) for A in As]
    return (csr_stack([csr_stack(sl) for sl in strip_lists]),
            strip_lists[0][0].nbytes())


def _scan_run_batched(As, Bs, plan: ChunkPlan, envelope: GeometryEnvelope, *,
                      caps_list=None, validate_caps: bool = True,
                      cores: dict | None = None):
    """Batched entry of the scan backend: vmapped lax.scan cores, bitwise-
    identical to the unbatched executors for same-structure batches.
    ``cores`` substitutes a caller-owned core set from
    :func:`_make_scan_batched_cores` for the module-level one."""
    del caps_list, validate_caps  # the ranged merge cannot overflow c_pad
    if cores is None:
        cores = _SCAN_CORES_BATCHED
    c_pad = envelope.c_pad
    r0s, r1s = plan.b_ranges()
    r0s, r1s = jnp.asarray(r0s), jnp.asarray(r1s)
    n_cols = Bs[0].n_cols
    dtype = As[0].dtype
    Bst, chunk_nbytes = _stage_chunks_batched(Bs, plan, envelope)
    if plan.algorithm == "knl":
        Ast = csr_stack([
            csr_pad_to(A, nnz_cap=envelope.a_nnz_cap,
                       max_row_nnz=envelope.a_max_row_nnz)
            for A in As
        ])
        C0s = _empty_c_stack(len(As), envelope.a_shape[0], n_cols, c_pad,
                             dtype)
        Cb = cores["knl"](Ast, Bst, r0s, r1s, C0s, c_pad=c_pad)
        return csr_unstack(Cb), planned_stats(plan, chunk_nbytes, 0, 0)
    Ast, strip_nbytes = _stage_strips_batched(As, plan, envelope)
    strip_rows = envelope.strip_rows
    stats = planned_stats(plan, chunk_nbytes, strip_nbytes,
                          _c_strip_nbytes(strip_rows, c_pad, dtype))
    if plan.algorithm == "chunk1":
        C0 = _empty_c(strip_rows, n_cols, c_pad, dtype)
        Cb = cores["chunk1"](Ast, Bst, r0s, r1s, C0, c_pad=c_pad)
    else:
        C0s = _empty_c_stack(plan.n_ac, strip_rows, n_cols, c_pad, dtype)
        Cb = cores["chunk2"](Ast, Bst, r0s, r1s, C0s, c_pad=c_pad)
    return [_assemble(Ci, plan.p_ac, n_cols) for Ci in csr_unstack(Cb)], stats


def _pallas_run_batched(As, Bs, plan: ChunkPlan, envelope: GeometryEnvelope, *,
                        caps_list=None, validate_caps: bool = True,
                        cores: dict | None = None):
    """Batched entry of the Pallas backend: the whole microbatch through one
    ``ranged_spgemm_stream`` launch whose leading grid dimension is the
    batch (staging and accumulation in f32 — allclose, not bitwise, against
    the loop oracle)."""
    del caps_list, validate_caps  # dense accumulators cannot overflow
    if cores is None:
        cores = _PALLAS_CORES_BATCHED
    r0s = jnp.asarray(plan.b_ranges()[0])
    n_cols = Bs[0].n_cols
    np_dtype = np.dtype(As[0].dtype)
    Bst, _ = _stage_chunks_batched(Bs, plan, envelope)
    if plan.algorithm == "knl":
        Ast = csr_stack([
            csr_pad_to(A, nnz_cap=envelope.a_nnz_cap,
                       max_row_nnz=envelope.a_max_row_nnz)
            for A in As
        ])
        dense = cores["knl"](Ast, Bst, r0s)
        stats = planned_stats_pallas(plan, *_pallas_stage_nbytes(
            envelope.a_shape[0], envelope.a_shape[1], envelope.chunk_rows,
            n_cols))
        return [_pallas_assemble(d, (0, envelope.a_shape[0]), np_dtype)
                for d in dense], stats
    Ast, _ = _stage_strips_batched(As, plan, envelope)
    dense = cores[plan.algorithm](Ast, Bst, r0s)
    stats = planned_stats_pallas(plan, *_pallas_stage_nbytes(
        envelope.strip_rows, envelope.a_shape[1], envelope.chunk_rows,
        n_cols))
    return [_pallas_assemble(d, plan.p_ac, np_dtype) for d in dense], stats


def _csr_accum_run_batched(As, Bs, plan: ChunkPlan,
                           envelope: GeometryEnvelope, kind: str, *,
                           caps_list=None, validate_caps: bool = True,
                           cores: dict | None = None):
    """Shared batched entry of the CSR-scratch accumulators (ESC and hash):
    one batch-on-the-grid kernel launch into fixed-capacity CSR scratch
    sized by the envelope.

    ``validate_caps`` checks every instance's exact realized output
    structure against the envelope capacities and raises a loud
    ``ValueError`` on overflow (the kernels silently drop entries past
    capacity). Callers whose envelopes dominate the instances *by
    construction* — the serving layer, whose bucket envelopes start from
    exact submit-time instance envelopes and only ever grow by
    union/quantization — pass ``False`` to skip the per-call host symbolic
    expansion the check costs; callers that already ran the expansions pass
    them as ``caps_list``."""
    c_pad = envelope.c_pad
    n_cols = Bs[0].n_cols
    dtype = As[0].dtype
    # the table size is a compile key, so it derives from the envelope
    # alone, never from the per-call instances. A zero c_max_row_nnz is
    # exact (empty output, 1-slot tables) when the symbolic phase ran —
    # witnessed by c_nnz_cap, whose rounding floor makes it nonzero
    # whenever computed; only a legacy both-zero envelope falls back to
    # the always-valid n_cols bound.
    table = None
    if kind == "hash":
        table = hash_table_slots(
            envelope.c_max_row_nnz if envelope.c_nnz_cap else n_cols)
    if validate_caps:
        if caps_list is None:
            caps_list = [strip_output_caps(A, B, plan.p_ac)
                         for A, B in zip(As, Bs)]
        for i, (A, caps) in enumerate(zip(As, caps_list)):
            check_output_caps(caps.strip_nnz, caps.c_max_row_nnz, c_pad,
                              table, backend=kind, a_shape=A.shape,
                              b_shape=Bs[i].shape, instance=i)
    r0s, r1s = plan.b_ranges()
    r0s, r1s = jnp.asarray(r0s), jnp.asarray(r1s)
    Bst, chunk_nbytes = _stage_chunks_batched(Bs, plan, envelope)
    # uniform across all three algorithms: knl is the 1-strip special
    # case (p_ac == (0, n_rows)), so every instance stages as strips
    Ast, strip_nbytes = _stage_strips_batched(As, plan, envelope)
    strip_rows = envelope.strip_rows
    C0 = _sparse_c0_stack(len(As), plan.n_ac, strip_rows, n_cols, c_pad,
                          dtype)
    if cores is None:
        cores = _HASH_CORES_BATCHED if kind == "hash" else _SPARSE_CORES_BATCHED
    if kind == "hash":
        ip, ix, d = cores[plan.algorithm](Ast, Bst, C0, r0s, r1s,
                                          table_size=table)
    else:
        ip, ix, d = cores[plan.algorithm](Ast, Bst, C0, r0s, r1s)
    stats = planned_stats_pallas(
        plan, chunk_nbytes, strip_nbytes,
        _c_strip_nbytes(strip_rows, c_pad, dtype))
    return [
        _assemble(CSR(ip[b], ix[b], d[b], (strip_rows, n_cols), c_pad),
                  plan.p_ac, n_cols)
        for b in range(len(As))
    ], stats


def _sparse_run_batched(As, Bs, plan, envelope, *, caps_list=None,
                        validate_caps=True, cores=None):
    return _csr_accum_run_batched(As, Bs, plan, envelope, "sparse",
                                  caps_list=caps_list,
                                  validate_caps=validate_caps, cores=cores)


def _hash_run_batched(As, Bs, plan, envelope, *, caps_list=None,
                      validate_caps=True, cores=None):
    return _csr_accum_run_batched(As, Bs, plan, envelope, "hash",
                                  caps_list=caps_list,
                                  validate_caps=validate_caps, cores=cores)


def _bsr_run_batched(As, Bs, plan, envelope, *, caps_list=None,
                     validate_caps=True, cores=None):
    """Batched entry of the BSR backend. Cap overflow is caught by the
    per-pair block symbolic itself (``bsr_spgemm_symbolic`` raises when the
    envelope's floors do not dominate an instance), so there is no separate
    validation pass to skip."""
    del caps_list, validate_caps
    if not envelope.bsr_caps:
        raise ValueError(
            "backend 'bsr' needs a block-capped envelope; rebuild it with "
            "batch_envelope(..., block_size=...)"
        )
    return _bsr_execute(As, Bs, plan, envelope, batched=True, cores=cores)


def chunked_spgemm_batched(As, Bs, plan: ChunkPlan, c_pad: int | None = None,
                           envelope: GeometryEnvelope | None = None,
                           backend: str = "scan", validate_caps: bool = True,
                           cores: dict | None = None):
    """Run a backend's batched entry over stacked problem instances sharing
    one plan.

    Instances must share shapes and dtype but may differ in sparsity
    *structure* (nnz, nnz capacities, ``max_row_nnz``): every instance's chunks
    and strips are repadded to a shared :class:`GeometryEnvelope` — by default
    the batch's union envelope, or a caller-provided (e.g. bucket-quantized)
    one — before stacking, so one compiled program serves the whole batch.
    Same-structure batches repad to their own geometry (a no-op), keeping the
    scan backend's results bitwise-identical to the unbatched executors.

    ``backend`` names any registered spec with a batched entry
    (``backend_registry.batched_backends()``) or ``"auto"``, which resolves
    to the accumulator whose planner byte model is smallest under the batch
    envelope (``select_accumulator_backend``); the dispatch hands the whole
    batch to the spec's ``run_batched``. Backends with ``needs_block_caps``
    (``"bsr"``) get a block-capped default envelope built at the spec's
    registered ``block_size``; a caller-provided envelope must already carry
    block caps for them. ``validate_caps`` is forwarded to the spec (the
    CSR-scratch accumulators use it to check realized output structure
    against the envelope capacities; see ``_csr_accum_run_batched``).
    ``cores`` substitutes a caller-owned jitted core set (from the spec's
    ``make_batched_cores`` factory) for the module-level cores — the
    serving layer's bounded executable cache passes per-bucket sets so that
    evicting a bucket really frees its compiled programs.

    Returns ``(list_of_C, stats)`` where ``stats`` is the per-instance modeled
    copy accounting at the *envelope-padded* staged sizes (identical across the
    batch by construction).
    """
    As, Bs = list(As), list(Bs)
    if len(As) != len(Bs) or not As:
        raise ValueError("need equal, nonzero numbers of A and B instances")
    if plan.algorithm not in backend_registry.ALGORITHMS:
        raise ValueError(f"unsupported algorithm {plan.algorithm!r}")
    spec = None if backend == "auto" else backend_registry.get(backend)
    if spec is not None and not spec.supports_batched:
        raise ValueError(
            f"backend {backend!r} does not support batched execution")
    for A, B in zip(As, Bs):
        if A.shape != As[0].shape or B.shape != Bs[0].shape:
            raise ValueError(
                "batched instances must share shapes: "
                f"{A.shape}x{B.shape} vs {As[0].shape}x{Bs[0].shape}"
            )
    caps_list = None
    if envelope is None:
        # the per-instance symbolic expansions feeding the union envelope
        # are exactly what cap validation needs — run them once
        caps_list = [strip_output_caps(A, B, plan.p_ac)
                     for A, B in zip(As, Bs)]
        block = (spec.block_size
                 if spec is not None and spec.needs_block_caps else None)
        envelope = batch_envelope(As, Bs, plan, c_pad=c_pad,
                                  caps_list=caps_list, block_size=block)
    elif c_pad is not None and c_pad != envelope.c_pad:
        raise ValueError(
            f"conflicting c_pad={c_pad} vs envelope.c_pad={envelope.c_pad}"
        )
    if envelope.a_shape != As[0].shape or envelope.b_shape != Bs[0].shape:
        raise ValueError(
            f"envelope shapes {envelope.a_shape}x{envelope.b_shape} do not "
            f"match instances {As[0].shape}x{Bs[0].shape}"
        )
    if spec is None:
        spec = backend_registry.get(
            select_accumulator_backend(plan, envelope))
    if spec.needs_block_caps and not envelope.bsr_caps:
        raise ValueError(
            f"backend {spec.name!r} needs a block-capped envelope; rebuild "
            "it with batch_envelope(..., block_size=...)"
        )
    return spec.run_batched(As, Bs, plan, envelope, caps_list=caps_list,
                            validate_caps=validate_caps, cores=cores)


# ---------------------------------------------------------------------------
# registrations: the one place each backend is wired into the stack
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# audit staging: TraceTargets for the static verifier (repro.analysis)
# ---------------------------------------------------------------------------
#
# Each helper stages one instance at an explicit GeometryEnvelope — exactly
# the envelope-driven padding the batched executors perform — and binds the
# statics into the backend's jitted core so `jax.make_jaxpr(fn)(*args)`
# abstract-traces the very program the executors launch. Two same-envelope
# instances must therefore produce byte-identical jaxprs (the retrace-leak
# contract); the traced program is also what the VMEM and DMA audits read.


def _audit_scan(A: CSR, B: CSR, plan: ChunkPlan, c_pad: int,
                envelope: GeometryEnvelope):
    Bst = csr_stack(b_chunks(B, plan.p_b, envelope=envelope))
    r0s, r1s = plan.b_ranges()
    if plan.algorithm == "knl":
        Ast = csr_pad_to(A, nnz_cap=envelope.a_nnz_cap,
                         max_row_nnz=envelope.a_max_row_nnz)
        C0 = _empty_c(A.n_rows, B.n_cols, c_pad, A.dtype)
        core = _knl_scan
    else:
        Ast = csr_stack(a_strips(A, plan.p_ac, envelope=envelope))
        strip_rows = envelope.strip_rows
        if plan.algorithm == "chunk1":
            C0 = _empty_c(strip_rows, B.n_cols, c_pad, A.dtype)
            core = _chunk1_scan
        else:
            C0 = _empty_c_stack(plan.n_ac, strip_rows, B.n_cols, c_pad,
                                A.dtype)
            core = _chunk2_scan
    return backend_registry.TraceTarget(
        fn=partial(core, c_pad=c_pad),
        args=(Ast, Bst, jnp.asarray(r0s), jnp.asarray(r1s), C0))


def _audit_pallas(A: CSR, B: CSR, plan: ChunkPlan, c_pad: int,
                  envelope: GeometryEnvelope):
    del c_pad  # capacity is implicit in the dense accumulator
    Bst = csr_stack(b_chunks(B, plan.p_b, envelope=envelope))
    r0s, _ = plan.b_ranges()
    if plan.algorithm == "knl":
        Ast = csr_pad_to(A, nnz_cap=envelope.a_nnz_cap,
                         max_row_nnz=envelope.a_max_row_nnz)
        core = _knl_pallas
    else:
        Ast = csr_stack(a_strips(A, plan.p_ac, envelope=envelope))
        core = _chunk1_pallas if plan.algorithm == "chunk1" else _chunk2_pallas
    return backend_registry.TraceTarget(
        fn=core, args=(Ast, Bst, jnp.asarray(r0s)),
        meta={"scalar_args": (jnp.asarray(r0s),)})


def _make_audit_csr_accum(kind: str):
    """Audit staging shared by the ESC ("sparse") and hash backends — the
    doubly stacked width-1 staging of ``_sparse_run``, envelope-padded."""

    def audit(A: CSR, B: CSR, plan: ChunkPlan, c_pad: int,
              envelope: GeometryEnvelope):
        Ast = csr_stack([csr_stack(a_strips(A, plan.p_ac,
                                            envelope=envelope))])
        Bst = csr_stack([csr_stack(b_chunks(B, plan.p_b,
                                            envelope=envelope))])
        r0s, r1s = plan.b_ranges()
        C0 = _sparse_c0_stack(1, plan.n_ac, envelope.strip_rows, B.n_cols,
                              c_pad, A.dtype)
        args = (Ast, Bst, C0, jnp.asarray(r0s), jnp.asarray(r1s))
        scalar_args = (jnp.asarray(r0s), jnp.asarray(r1s))
        if kind == "hash":
            # compile key: the table derives from the envelope, exactly as
            # in the batched run (see _csr_accum_run_batched)
            table = hash_table_slots(
                envelope.c_max_row_nnz if envelope.c_nnz_cap else B.n_cols)
            return backend_registry.TraceTarget(
                fn=partial(_HASH_CORES[plan.algorithm], table_size=table),
                args=args,
                meta={"table_size": table, "scalar_args": scalar_args})
        return backend_registry.TraceTarget(
            fn=_SPARSE_CORES[plan.algorithm], args=args,
            meta={"scalar_args": scalar_args})

    return audit


def _audit_bsr(A: CSR, B: CSR, plan: ChunkPlan, c_pad: int,
               envelope: GeometryEnvelope):
    """Stage the first (strip, chunk) pair exactly as ``_bsr_execute`` does;
    every pair launches the same envelope-keyed kernel geometry, so one pair
    is the whole compile surface."""
    del c_pad
    bs, nbl_a_cap, nbl_b_cap, nc_cap, u_cap = envelope.bsr_caps
    k, n = B.shape
    kpad = -(-k // bs) * bs
    npad = -(-n // bs) * bs
    srpad = -(-envelope.strip_rows // bs) * bs
    Ad = np.asarray(csr_to_dense(A), np.float32)
    Bd = np.asarray(csr_to_dense(B), np.float32)
    s, e = plan.p_ac[0], plan.p_ac[1]
    r0, r1 = plan.p_b[0], plan.p_b[1]
    Am = np.zeros((srpad, kpad), np.float32)
    Am[: e - s, r0:r1] = Ad[s:e, r0:r1]
    Bm = np.zeros((kpad, npad), np.float32)
    Bm[r0:r1, :n] = Bd[r0:r1, :]
    Ab = bsr_from_dense(Am, bs, pad_to=nbl_a_cap)
    Bb = bsr_from_dense(Bm, bs, pad_to=nbl_b_cap)
    meta = bsr_spgemm_symbolic(Ab, Bb, nc_pad=nc_cap, u_max=u_cap)
    a_slots, b_slots = jnp.asarray(meta.a_slots), jnp.asarray(meta.b_slots)
    return backend_registry.TraceTarget(
        fn=partial(_BSR_CORES[plan.algorithm], envelope=envelope),
        args=(bsr_blocks_with_sentinel(Ab), bsr_blocks_with_sentinel(Bb),
              a_slots, b_slots),
        meta={"scalar_args": (a_slots, b_slots)})


# ---------------------------------------------------------------------------
# traffic models: the per-copy-event byte flows the traced jaxprs must equal
# ---------------------------------------------------------------------------
#
# Each hook declares, per pallas operand and in spec order, the ordered list
# of copy-event byte sizes the staged launch performs over its whole grid —
# the planner-side half of the flow-equality audit (repro.analysis.traffic),
# which reconstructs the same lists from the traced jaxpr and demands exact
# equality, then ties the merged flows to the ChunkStats the executors log.


def _traffic_pallas(A, B, plan: ChunkPlan, c_pad: int,
                    envelope: GeometryEnvelope, meta):
    """Dense-slab pipeline flows. knl/chunk1 grid is (1, n_ac, n_b): the
    stationary strip and fused C_prev block refetch per strip, the slab
    hand-DMAs every grid step; chunk2 swaps the roles and maps all C
    partials as one constant-index block (one fetch, one writeback)."""
    del A, B, c_pad, meta
    OpFlow = backend_registry.OpFlow
    k, n = envelope.a_shape[1], envelope.b_shape[1]
    strip_rows = (envelope.a_shape[0] if plan.algorithm == "knl"
                  else envelope.strip_rows)
    slab, a_stage, c_stage = (
        float(v) for v in _pallas_stage_nbytes(strip_rows, k,
                                               envelope.chunk_rows, n))
    n_ac, n_b = plan.n_ac, plan.n_b
    if plan.algorithm in ("knl", "chunk1"):
        in_ops = (OpFlow("stationary", (a_stage,) * n_ac),
                  OpFlow("streamed", (slab,) * (n_ac * n_b)),
                  OpFlow("c_prev", (c_stage,) * n_ac))
        out_ops = (OpFlow("c_out", (c_stage,) * n_ac),)
    else:
        in_ops = (OpFlow("stationary", (slab,) * n_b),
                  OpFlow("streamed", (a_stage,) * (n_b * n_ac)),
                  OpFlow("c_prev", (n_ac * c_stage,)))
        out_ops = (OpFlow("c_out", (n_ac * c_stage,)),)
    st = planned_stats_pallas(plan, slab, a_stage, c_stage)
    return backend_registry.ExpectedTraffic(
        in_ops=in_ops, out_ops=out_ops,
        stats_in=tuple(st.per_copy_in), stats_out=tuple(st.per_copy_out))


def _traffic_csr_accum(A, B, plan: ChunkPlan, c_pad: int,
                       envelope: GeometryEnvelope, meta):
    """CSR-accumulator (ESC and hash) flows: every logical operand is three
    field operands (indptr, indices, data) whose per-event bytes sum to the
    staged triple's ``CSR.nbytes()`` — same-key fields merge event-wise into
    the single ChunkStats event the executors log. knl stages as the
    1-strip chunk1 special case (see ``_sparse_run``)."""
    del A, B, meta
    OpFlow = backend_registry.OpFlow
    itemsize = int(np.dtype(envelope.dtype).itemsize)
    strip_f = csr_field_nbytes(envelope.strip_rows, envelope.strip_nnz_cap,
                               itemsize)
    chunk_f = csr_field_nbytes(envelope.chunk_rows, envelope.chunk_nnz_cap,
                               itemsize)
    c_f = csr_field_nbytes(envelope.strip_rows, c_pad, itemsize)
    n_ac, n_b = plan.n_ac, plan.n_b
    if plan.algorithm in ("knl", "chunk1"):
        stat_f, stream_f = strip_f, chunk_f
        n_stat, n_stream = n_ac, n_ac * n_b
        c_in = tuple(OpFlow("c_prev", (f,) * n_ac) for f in c_f)
        c_out = tuple(OpFlow("c_out", (f,) * n_ac) for f in c_f)
    else:
        stat_f, stream_f = chunk_f, strip_f
        n_stat, n_stream = n_b, n_b * n_ac
        c_in = tuple(OpFlow("c_prev", (n_ac * f,)) for f in c_f)
        c_out = tuple(OpFlow("c_out", (n_ac * f,)) for f in c_f)
    in_ops = (
        tuple(OpFlow("stationary", (f,) * n_stat) for f in stat_f)
        + tuple(OpFlow("streamed", (f,) * n_stream) for f in stream_f)
        + c_in
    )
    st = planned_stats_pallas(
        plan, int(sum(chunk_f)), int(sum(strip_f)),
        _c_strip_nbytes(envelope.strip_rows, c_pad, envelope.dtype))
    return backend_registry.ExpectedTraffic(
        in_ops=in_ops, out_ops=c_out,
        stats_in=tuple(st.per_copy_in), stats_out=tuple(st.per_copy_out))


def _traffic_bsr(A, B, plan: ChunkPlan, c_pad: int,
                 envelope: GeometryEnvelope, meta):
    """Blocked-kernel flows, replayed from the audited pair's scalar-prefetch
    slot tables: a ``bs x bs`` tile is fetched whenever the slot value
    changes between consecutive grid steps (the pipeline reuses a resident
    block when the index map lands on the same slot), and each output block
    row writes back once. The ChunkStats tie is exempt: ``_bsr_execute``
    stages every (strip, chunk) pair through a host loop while its stats
    model the idealized BSR pipeline — a documented modeling fiction
    (see ``_bsr_execute``) the flow audit does not re-litigate."""
    del A, B, plan, c_pad
    OpFlow = backend_registry.OpFlow
    bs = envelope.bsr_caps[0]
    block_bytes = float(bs * bs * 4)
    a_slots = np.asarray(meta["scalar_args"][0])
    b_slots = np.asarray(meta["scalar_args"][1])

    def slot_flow(table):
        events, prev = [], None
        for val in table.reshape(-1):      # row-major == grid order (e, u)
            v = int(val)
            if prev is None or v != prev:
                events.append(block_bytes)
            prev = v
        return tuple(events)

    nc_pad = int(a_slots.shape[0])
    return backend_registry.ExpectedTraffic(
        in_ops=(OpFlow("a_blocks", slot_flow(a_slots)),
                OpFlow("b_blocks", slot_flow(b_slots))),
        out_ops=(OpFlow("c_blocks", (block_bytes,) * nc_pad),),
        stats_exempt=(
            "bsr executor stages per (strip, chunk) pair host-side; its "
            "ChunkStats model the idealized BSR pipeline, not the audited "
            "single-pair launch (documented in _bsr_execute)"))


def _register_all() -> None:
    if "scan" in backend_registry._REGISTRY:   # tolerate importlib.reload
        return
    register, Spec = backend_registry.register, backend_registry.BackendSpec
    algs = backend_registry.ALGORITHMS
    register(Spec(
        name="loop",
        executors={"knl": chunk_knl, "chunk1": chunk_gpu1,
                   "chunk2": chunk_gpu2},
    ))
    register(Spec(
        name="scan",
        executors={"knl": chunk_knl_scan, "chunk1": chunk_gpu1_scan,
                   "chunk2": chunk_gpu2_scan},
        run_batched=_scan_run_batched,
        trace_key="{alg}",
        trace_key_batched="{alg}_batched",
        audit_trace=_audit_scan,
        make_batched_cores=_make_scan_batched_cores,
    ))
    register(Spec(
        name="pallas",
        executors={"knl": chunk_knl_pallas, "chunk1": chunk_gpu1_pallas,
                   "chunk2": chunk_gpu2_pallas},
        run_batched=_pallas_run_batched,
        byte_model=planned_stats_dense_slab,
        trace_key="{alg}_pallas",
        trace_key_batched="{alg}_pallas_batched",
        is_accumulator=True,
        audit_trace=_audit_pallas,
        traffic_model=_traffic_pallas,
        make_batched_cores=_make_pallas_batched_cores,
    ))
    register(Spec(
        name="sparse",
        executors=dict.fromkeys(algs, chunk_sparse),
        run_batched=_sparse_run_batched,
        byte_model=planned_stats_sparse,
        trace_key="{alg}_sparse",
        trace_key_batched="{alg}_sparse_batched",
        needs_output_caps=True,
        is_accumulator=True,
        audit_trace=_make_audit_csr_accum("sparse"),
        traffic_model=_traffic_csr_accum,
        make_batched_cores=_make_sparse_batched_cores,
        mosaic_refusal=(
            "the ESC merge's gathers, sorts and scatter have no Mosaic lowering, "
            "and its (1, 1, cap) CSR blocks break the (8, 128) block-shape "
            "rule"),
    ))
    register(Spec(
        name="hash",
        executors=dict.fromkeys(algs, chunk_hash),
        run_batched=_hash_run_batched,
        byte_model=planned_stats_hash,
        trace_key="{alg}_hash",
        trace_key_batched="{alg}_hash_batched",
        needs_output_caps=True,
        is_accumulator=True,
        run_masked=chunk_hash_masked,
        audit_trace=_make_audit_csr_accum("hash"),
        traffic_model=_traffic_csr_accum,
        make_batched_cores=_make_hash_batched_cores,
        mosaic_refusal=(
            "the probe loops' dynamic_slice has no Mosaic lowering, and its "
            "(1, 1, cap) CSR blocks break the (8, 128) block-shape rule"),
    ))
    register(Spec(
        name="bsr",
        executors=dict.fromkeys(algs, chunk_bsr),
        run_batched=_bsr_run_batched,
        byte_model=planned_stats_bsr,
        trace_key="{alg}_bsr",
        trace_key_batched="{alg}_bsr_batched",
        needs_output_caps=True,
        needs_block_caps=True,
        is_accumulator=True,
        block_size=_BSR_DEFAULT_BLOCK,
        audit_trace=_audit_bsr,
        traffic_model=_traffic_bsr,
        make_batched_cores=_make_bsr_batched_cores,
    ))


_register_all()
