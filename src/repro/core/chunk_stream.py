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
  A step whose A strip has no entry in the chunk's rows skips the body
  (:func:`_step`). XLA is *free* to double-buffer the slow->fast
  transfers, but nothing forces the overlap.
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

Staging is written once. :func:`_stage` cuts one instance's A into strips
and B into chunks, pads them (to the instance's largest piece, or to a
``GeometryEnvelope``) and stacks them; each CSR-staged backend builds its
launch from it (``_scan_launch``, ``_pallas_launch``,
``_csr_accum_launch``), and its batched entry stages every instance the
same way and stacks the results once. A backend's ``audit_trace`` is that
launch staged at an explicit envelope, so the static verifier traces the
program the executors run: at the instance envelope it is the executor's
own jit cache entry. The BSR backend cuts (strip, chunk) pairs instead,
through :func:`_bsr_pair`, for its executor and its audit alike.

Because a traced scan (or Pallas grid) cannot mutate Python-side counters,
ChunkStats for these backends is *computed from the plan*: the uniform padding
makes every staged chunk/strip/partial the same size, so the per-copy event
sequence is reproducible host-side. ``planned_stats`` replays the loop
executors' CSR-staging events (asserted identical in tests);
``planned_stats_pallas`` replays the Pallas pipeline's copy events
(:func:`_pipeline_events`, which the traffic models read too), which
differ structurally (one stationary piece per outer step; Chunk2's C
partials persist in VMEM instead of bouncing to slow memory).

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
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import backend_registry
from repro.core.chunking import (
    ChunkStats, _assemble, _fetch, _join, a_strips, b_chunks, batch_envelope,
    chunk_gpu1, chunk_gpu2, chunk_knl, instance_envelope,
)
from repro.core.kkmem import (
    TRACE_COUNTS, ell_tables, key_layout, multiplies_range, spgemm_ranged_impl,
    spgemm_ranged_tables,
)
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

TraceTarget = backend_registry.TraceTarget

# The kernels' streaming order per plan algorithm: knl is the one-strip
# special case of the chunk1 order.
_ORDERS = {"knl": "chunk1", "chunk1": "chunk1", "chunk2": "chunk2"}


def _cores(make, key: str, **kwargs) -> dict:
    """One jitted core per plan algorithm, ``make(key, alg, **kwargs)``,
    with ``key`` the ``"{alg}"`` template of its ``TRACE_COUNTS`` key."""
    return {alg: make(key.format(alg=alg), alg, **kwargs) for alg in _ORDERS}


def _empty_c(lead: tuple, n_rows: int, n_cols: int, c_pad: int, dtype) -> CSR:
    """An empty C at capacity ``c_pad``, or a stack of them with leading
    axes ``lead``. ``max_row_nnz=c_pad`` gives it the pytree structure the
    accumulating cores return (an aux mismatch would fail the scan carry
    check)."""
    return CSR(
        indptr=jnp.zeros((*lead, n_rows + 1), jnp.int32),
        indices=jnp.zeros((*lead, c_pad), jnp.int32),
        data=jnp.zeros((*lead, c_pad), dtype),
        shape=(n_rows, n_cols),
        max_row_nnz=c_pad,
    )


# ---------------------------------------------------------------------------
# jitted scan cores (one compilation per padded geometry, not per chunk)
# ---------------------------------------------------------------------------


def _step(A: CSR, r0, r1, state, run):
    """One (strip, chunk) step of a scan core: ``run(state)``, the ESC body
    with all of the step's work inside it, where some entry of the strip
    ``A`` multiplies a row of B's range [r0, r1)
    (``kkmem.multiplies_range``); else ``state`` as it came, and with it
    whether the body ran. A dead step's body would give back the same C:
    no product joins the stream, and sorting a canonical C returns its
    entries, padding and ``indptr`` (only a total of -0.0 would come back
    as +0.0). Under ``vmap`` (the batched cores) the predicate is batched
    and the cond lowers to a select that runs both branches: the same
    results, and no saving."""
    live = multiplies_range(A, r0, r1)
    return lax.cond(live, run, lambda state: state, state), live


def _knl_scan_impl(A: CSR, Bs: CSR, r0s, r1s, C0: CSR, c_pad: int):
    """C, and per chunk whether its step ran."""
    def body(C, x):
        TRACE_COUNTS["knl_body"] += 1
        Bc, r0, r1 = x
        return _step(A, r0, r1, C,
                     lambda C: spgemm_ranged_impl(A, Bc, r0, r1, C, c_pad))

    return lax.scan(body, C0, (Bs, r0s, r1s))


def _chunk1_scan_impl(As: CSR, Bs: CSR, r0s, r1s, C0: CSR, c_pad: int):
    """The stacked per-strip results, and per (strip, chunk) whether its
    step ran."""
    def outer(carry, Ai):
        def inner(C, x):
            TRACE_COUNTS["chunk1_body"] += 1
            Bc, r0, r1 = x
            return _step(Ai, r0, r1, C,
                         lambda C: spgemm_ranged_impl(Ai, Bc, r0, r1, C, c_pad))

        return carry, lax.scan(inner, C0, (Bs, r0s, r1s))

    return lax.scan(outer, None, As)[1]


def _chunk2_scan_impl(As: CSR, Bs: CSR, r0s, r1s, C0s: CSR, c_pad: int):
    """The stacked per-strip results, and per (chunk, strip) whether its
    step ran."""
    def outer(Cs, x):
        TRACE_COUNTS["chunk2_tables"] += 1
        Bc, r0, r1 = x
        # The chunk's ELL tables, built once here for every strip's step.
        # They are held flat through the strip loop: a two-dimensional table
        # held there may take a padded tiled layout, and each live step's
        # reshape is a copy that dies with the step.
        tables = ell_tables(Bc)
        shape = tables[0].shape
        flat = [t.reshape(-1) for t in tables]

        def inner(Cs, y):
            TRACE_COUNTS["chunk2_body"] += 1
            i, Ai = y

            # The stack of partials is the loop's carry, and a live step
            # writes its strip's in place: a partial returned from the cond
            # would take a buffer of its own before joining the stack.
            def run(Cs):
                Ci = jax.tree.map(
                    lambda s: lax.dynamic_index_in_dim(s, i, keepdims=False), Cs)
                tabs = [t.reshape(shape) for t in flat]
                C = spgemm_ranged_tables(Ai, tabs, r0, r1, Ci, c_pad)
                return jax.tree.map(
                    lambda s, c: lax.dynamic_update_index_in_dim(s, c, i, 0),
                    Cs, C)

            return _step(Ai, r0, r1, Cs, run)

        strips = jnp.arange(As.indptr.shape[0], dtype=jnp.int32)
        return lax.scan(inner, Cs, (strips, As))

    return lax.scan(outer, C0s, (Bs, r0s, r1s))


@partial(jax.jit, static_argnames=("c_pad",))
def _knl_scan(A: CSR, Bs: CSR, r0s, r1s, C0: CSR, c_pad: int):
    TRACE_COUNTS["knl"] += 1
    return _knl_scan_impl(A, Bs, r0s, r1s, C0, c_pad)


@partial(jax.jit, static_argnames=("c_pad",))
def _chunk1_scan(As: CSR, Bs: CSR, r0s, r1s, C0: CSR, c_pad: int):
    """A/C strips outer (stationary), B chunks inner (streamed). Returns the
    stacked per-strip results ([n_ac] leading axis) and the steps' flags
    ([n_ac, n_b])."""
    TRACE_COUNTS["chunk1"] += 1
    return _chunk1_scan_impl(As, Bs, r0s, r1s, C0, c_pad)


@partial(jax.jit, static_argnames=("c_pad",))
def _chunk2_scan(As: CSR, Bs: CSR, r0s, r1s, C0s: CSR, c_pad: int):
    """B chunk outer (stationary), A/C strips inner (streamed); all per-strip
    partials ride the scan carry. Returns the stacked per-strip results and
    the steps' flags ([n_b, n_ac])."""
    TRACE_COUNTS["chunk2"] += 1
    return _chunk2_scan_impl(As, Bs, r0s, r1s, C0s, c_pad)


def _make_scan_batched_cores(donate: bool = False) -> dict:
    """A jitted set of the three batched (vmapped) scan cores: one program
    per (envelope, plan, batch) geometry, each with its own
    ``TRACE_COUNTS`` key so the serving layer can assert "one compile per
    geometry bucket" directly. The module-level set
    (``_SCAN_CORES_BATCHED``) caches compilations for the life of the
    process; a caller that owns a set from this factory (a serving bucket)
    is the sole owner of its executables, so dropping the set really frees
    them.

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
            lambda A, Bc, C0: _knl_scan_impl(A, Bc, r0s, r1s, C0, c_pad)[0]
        )(Ast, Bst, C0s)

    @partial(jax.jit, static_argnames=("c_pad",))
    def chunk1(Ast: CSR, Bst: CSR, r0s, r1s, C0: CSR, c_pad: int) -> CSR:
        TRACE_COUNTS["chunk1_batched"] += 1
        return jax.vmap(
            lambda As, Bs: _chunk1_scan_impl(As, Bs, r0s, r1s, C0, c_pad)[0]
        )(Ast, Bst)

    @partial(jax.jit, static_argnames=("c_pad",))
    def chunk2(Ast: CSR, Bst: CSR, r0s, r1s, C0s: CSR, c_pad: int) -> CSR:
        TRACE_COUNTS["chunk2_batched"] += 1
        return jax.vmap(
            lambda As, Bs: _chunk2_scan_impl(As, Bs, r0s, r1s, C0s, c_pad)[0]
        )(Ast, Bst)

    return {"knl": knl, "chunk1": chunk1, "chunk2": chunk2}


_SCAN_CORES_BATCHED = _make_scan_batched_cores()


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


def _pipeline_events(plan: ChunkPlan) -> list:
    """The Pallas pipeline's copy events in issue order, each ``(operand,
    piece, copies)``: the operand is ``stationary``, ``streamed`` or
    ``c_prev`` (slow->fast) or ``c_out`` (fast->slow), the piece it moves
    is an A strip (``"a"``), a B chunk (``"b"``) or a C strip (``"c"``),
    and one event moves ``copies`` such pieces.

    knl/chunk1 (grid ``(1, n_ac, n_b)``): per strip, the stationary strip
    and the fused C_prev block come in, a chunk is hand-DMA'd every grid
    step, and the strip's C goes out. chunk2 swaps the roles, and maps all
    ``n_ac`` C partials as one ``(n_ac, strip_rows, n)`` block at a constant
    index: one whole-block fetch with the first chunk and one writeback at
    the end, not ``n_ac`` per-strip events."""
    n_ac, n_b = plan.n_ac, plan.n_b
    if plan.algorithm in ("knl", "chunk1"):
        strip = [("stationary", "a", 1), ("c_prev", "c", 1),
                 *[("streamed", "b", 1)] * n_b, ("c_out", "c", 1)]
        return strip * n_ac
    if plan.algorithm == "chunk2":
        events = []
        for jb in range(n_b):
            events.append(("stationary", "b", 1))
            if jb == 0:
                events.append(("c_prev", "c", n_ac))
            events += [("streamed", "a", 1)] * n_ac
        return events + [("c_out", "c", n_ac)]
    raise ValueError(f"unknown algorithm {plan.algorithm!r}")


def planned_stats_pallas(plan: ChunkPlan, slab_nbytes: int, a_stage_nbytes: int,
                         c_stage_nbytes: int) -> ChunkStats:
    """Replay the Pallas pipeline's per-copy event sequence from the plan.

    The event model (:func:`_pipeline_events`) differs from
    :func:`planned_stats` in three structural ways, all of them properties
    of the kernel rather than modeling choices:

      * the dense backend's staged pieces are **dense** (slab = ``chunk_rows
        x n`` floats, strip = ``strip_rows x k_pad`` floats), not padded CSR
        triples;
      * the stationary operand is staged by the Pallas pipeline once per outer
        step, and the streamed operand is hand-DMA'd once per grid step — the
        double-buffer *overlaps* those copies with compute but their byte
        volume is unchanged;
      * in the Chunk2 order the per-strip C partials persist in the VMEM
        output block across outer steps, so the ``(n_b - 1)`` per-strip
        out+in partial bounces of the loop/scan model collapse into **one**
        whole-block ``C_prev`` fetch and **one** final writeback of
        ``n_ac * c_stage_nbytes`` (the traffic-equality audit holds this
        model to the traced jaxpr event-for-event).
    """
    size = {"a": a_stage_nbytes, "b": slab_nbytes, "c": c_stage_nbytes}
    stats = ChunkStats(plan.algorithm, plan.n_ac, plan.n_b)
    for operand, piece, copies in _pipeline_events(plan):
        add = stats.add_out if operand == "c_out" else stats.add_in
        add(copies * size[piece])
    stats.kernel_calls = plan.n_ac * plan.n_b
    return stats


def _pallas_stage_nbytes(strip_rows: int, k: int, chunk_rows: int,
                         n: int) -> tuple:
    """(slab, a_stage, c_stage) dense staged footprints in bytes (f32), at
    the kernel's lane-aligned staging (``ranged_spgemm.stage_dims``)."""
    k_pad, span, n_pad = stage_dims(k, chunk_rows, n)
    return span * n_pad * 4, strip_rows * k_pad * 4, strip_rows * n_pad * 4


# ---------------------------------------------------------------------------
# staging: one instance's strips and chunks, padded and stacked
# ---------------------------------------------------------------------------


class _Staged(NamedTuple):
    """One launch's staged operands: A whole or its strips stacked, B's
    chunks stacked, and the bytes of one staged strip (or A) and chunk."""

    A: CSR
    B: CSR
    a_nbytes: int
    b_nbytes: int


def _stage(A: CSR, B: CSR, plan: ChunkPlan,
           envelope: GeometryEnvelope | None = None, *,
           strips: bool = True) -> _Staged:
    """On the device: the strips of ``A`` (with ``strips=False``, A whole)
    and the chunks of ``B``, each stacked. Without an envelope every piece
    is padded to this instance's largest; with one, to the envelope's caps,
    and a whole A to its ``a_nnz_cap``/``a_max_row_nnz``. The lists of
    pieces die here: only the stacks reach the launch."""
    if strips:
        pieces = a_strips(A, plan.p_ac, envelope=envelope)
        A = csr_stack(pieces)
        a_nbytes = pieces[0].nbytes()
    else:
        if envelope is not None:
            A = csr_pad_to(A, nnz_cap=envelope.a_nnz_cap,
                           max_row_nnz=envelope.a_max_row_nnz)
        a_nbytes = A.nbytes()
    pieces = b_chunks(B, plan.p_b, envelope=envelope)
    return _Staged(A, csr_stack(pieces), a_nbytes, pieces[0].nbytes())


def _stage_batch(As, Bs, plan: ChunkPlan, envelope: GeometryEnvelope | None,
                 *, strips: bool = True) -> _Staged:
    """Every instance staged by :func:`_stage` and the stacks stacked once
    more ([batch, ...] leading axis)."""
    staged = [_stage(A, B, plan, envelope, strips=strips)
              for A, B in zip(As, Bs)]
    return staged[0]._replace(A=csr_stack([s.A for s in staged]),
                              B=csr_stack([s.B for s in staged]))


# ---------------------------------------------------------------------------
# scan backend: one lax.scan program per algorithm
# ---------------------------------------------------------------------------


def _scan_launch(A: CSR, B: CSR, plan: ChunkPlan, c_pad: int,
                 envelope: GeometryEnvelope | None = None,
                 put=jax.device_put) -> TraceTarget:
    """One scan launch staged: the core with ``c_pad`` bound, its operands,
    and its copy events as ``meta["stats"]``. ``put`` puts the plan's B-row
    ranges on the device (the stage span's ``put`` counts them). The
    backend's ``audit_trace``."""
    st = _stage(A, B, plan, envelope, strips=plan.algorithm != "knl")
    rows = st.A.n_rows
    lead = (plan.n_ac,) if plan.algorithm == "chunk2" else ()
    C0 = _empty_c(lead, rows, B.n_cols, c_pad, A.dtype)
    core = {"knl": _knl_scan, "chunk1": _chunk1_scan,
            "chunk2": _chunk2_scan}[plan.algorithm]
    stats = planned_stats(plan, st.b_nbytes, st.a_nbytes,
                          _c_strip_nbytes(rows, c_pad, A.dtype))
    return TraceTarget(fn=partial(core, c_pad=c_pad),
                       args=(st.A, st.B, *put(plan.b_ranges()), C0),
                       meta={"stats": stats})


def _scan_run(A: CSR, B: CSR, plan: ChunkPlan, c_pad: int):
    """The scan executor of every algorithm: the plan's algorithm picks the
    core. knl's C stays whole on the device; the strip orders assemble."""
    with span("stage") as sp:
        launch = _scan_launch(A, B, plan, c_pad, put=sp.put)
    steps, stats = plan.n_ac * plan.n_b, launch.meta["stats"]
    with span("launch") as sp:
        # chunk1 has the strip loop outer: each chunk's tables are built
        # anew for every live strip step
        sp.note(key=key_layout(launch.args[0].n_rows, B.n_cols),
                tables=steps if plan.algorithm == "chunk1" else plan.n_b,
                steps=steps)
        C, live = launch.fn(*launch.args)
        if plan.algorithm == "knl":
            # C stays on the device; reading the flags would wait for it
            return C, stats
        C, live = _fetch((C, live))
        sp.note(live_steps=int(live.sum()))
    return _join(C, plan.p_ac, B.n_cols), stats


chunk_knl_scan = chunk_gpu1_scan = chunk_gpu2_scan = _scan_run


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


def _make_pallas_core(key: str, alg: str, *, batched: bool):
    """One jitted staging-and-launch core; the six variants differ only in
    the streaming order, the trace-counter key, and whether A arrives as a
    plain CSR (knl), a strip stack, or a per-instance (doubly) stacked batch.

    Batched cores ride the batch on a leading grid dimension of the same
    kernel (one pallas_call for the whole microbatch — no vmap-of-pallas),
    with their own TRACE_COUNTS keys so the serving layer's compile
    accounting stays exact.
    """
    strips = alg != "knl"
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
        out = ranged_spgemm_stream(a, slabs, c0, r0s, order=_ORDERS[alg])
        if not batched:
            out = out[0]
        if not strips:
            out = out[:, 0] if batched else out[0]
        return out

    return core


def _make_pallas_batched_cores(donate: bool = False) -> dict:
    """Jitted batched Pallas cores (see ``_make_scan_batched_cores`` for why
    a caller-owned set exists). The dense accumulator is allocated inside
    the jit and the staged CSR operands never alias the dense outputs, so
    there is nothing donation could usefully alias here."""
    del donate
    return _cores(_make_pallas_core, "{alg}_pallas_batched", batched=True)


_PALLAS_CORES = _cores(_make_pallas_core, "{alg}_pallas", batched=False)
_PALLAS_CORES_BATCHED = _make_pallas_batched_cores()


def _pallas_assemble(dense, p_ac: tuple, dtype) -> CSR:
    """Crop per-strip dense results (``[n_ac, rows, n]``, or one ``[rows,
    n]`` block) to their true rows, concatenate, and sparsify on the host.
    The Pallas backend's CSR keeps exactly the nonzeros of the dense result,
    so comparisons against the loop oracle are allclose on the densified
    values rather than bitwise on padding structure."""
    dense = _fetch(dense)
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


def _pallas_launch(A: CSR, B: CSR, plan: ChunkPlan, c_pad: int,
                   envelope: GeometryEnvelope | None = None,
                   put=jax.device_put) -> TraceTarget:
    """One Pallas launch staged (see :func:`_scan_launch`); the chunk
    starts are its scalar operand. The backend's ``audit_trace``."""
    del c_pad  # capacity is implicit in the dense accumulator
    st = _stage(A, B, plan, envelope, strips=plan.algorithm != "knl")
    r0s, _ = put(plan.b_ranges())
    stats = planned_stats_pallas(plan, *_pallas_stage_nbytes(
        st.A.n_rows, A.n_cols, st.B.n_rows, B.n_cols))
    return TraceTarget(fn=_PALLAS_CORES[plan.algorithm],
                       args=(st.A, st.B, r0s),
                       meta={"stats": stats, "scalar_args": (r0s,)})


def _pallas_run(A: CSR, B: CSR, plan: ChunkPlan, c_pad: int):
    """The Pallas executor of every algorithm (see :func:`_scan_run`)."""
    with span("stage") as sp:
        launch = _pallas_launch(A, B, plan, c_pad, put=sp.put)
    with span("launch"):
        dense = launch.fn(*launch.args)
    p_ac = (0, A.n_rows) if plan.algorithm == "knl" else plan.p_ac
    return (_pallas_assemble(dense, p_ac, np.dtype(A.dtype)),
            launch.meta["stats"])


chunk_knl_pallas = chunk_gpu1_pallas = chunk_gpu2_pallas = _pallas_run


# ---------------------------------------------------------------------------
# Sparse-output backend: CSR-native accumulator (kernels/sparse_accum_spgemm)
# ---------------------------------------------------------------------------


def _make_sparse_core(key: str, alg: str, donate: bool = False):
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
                                          order=_ORDERS[alg])

    return core


def _make_hash_core(key: str, alg: str, donate: bool = False):
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
                                        order=_ORDERS[alg],
                                        table_size=table_size)

    return core


def _make_sparse_batched_cores(donate: bool = False) -> dict:
    """Jitted batched ESC cores (caller-owned executables; see
    ``_make_scan_batched_cores``)."""
    return _cores(_make_sparse_core, "{alg}_sparse_batched", donate=donate)


def _make_hash_batched_cores(donate: bool = False) -> dict:
    """Jitted batched hash-probe cores (caller-owned executables)."""
    return _cores(_make_hash_core, "{alg}_hash_batched", donate=donate)


_SPARSE_CORES = _cores(_make_sparse_core, "{alg}_sparse")
_SPARSE_CORES_BATCHED = _make_sparse_batched_cores()
_HASH_CORES = _cores(_make_hash_core, "{alg}_hash")
_HASH_CORES_BATCHED = _make_hash_batched_cores()


def _envelope_table(envelope: GeometryEnvelope, n_cols: int) -> int:
    """The hash tables' slot count. It is a compile key, so it derives from
    the envelope alone, never from the per-call instances. A zero
    ``c_max_row_nnz`` is exact (empty output, 1-slot tables) when the
    symbolic phase ran — witnessed by ``c_nnz_cap``, whose rounding floor
    makes it nonzero whenever computed; only a legacy both-zero envelope
    falls back to the always-valid ``n_cols`` bound."""
    return hash_table_slots(
        envelope.c_max_row_nnz if envelope.c_nnz_cap else n_cols)


def _csr_accum_launch(cores: dict, As, Bs, plan: ChunkPlan, c_pad: int,
                      table: int | None = None,
                      envelope: GeometryEnvelope | None = None,
                      put=jax.device_put) -> TraceTarget:
    """One launch of a CSR-scratch accumulator (ESC, or hash with a
    ``table`` size) staged for the instances ``As``/``Bs``: every
    instance's strips and chunks (knl is the 1-strip special case of the
    chunk1 order) stacked over the batch, the empty C scratch at ``c_pad``,
    and the B-row ranges. An unbatched executor is a width-1 batch.

    The per-copy event model is structurally the Pallas pipeline's
    (:func:`planned_stats_pallas`) — only the staged byte sizes differ:
    padded **CSR** footprints instead of dense slabs."""
    st = _stage_batch(As, Bs, plan, envelope)
    rows, dtype = st.A.n_rows, As[0].dtype
    C0 = _empty_c((len(As), plan.n_ac), rows, Bs[0].n_cols, c_pad, dtype)
    r0s, r1s = put(plan.b_ranges())
    stats = planned_stats_pallas(plan, st.b_nbytes, st.a_nbytes,
                                 _c_strip_nbytes(rows, c_pad, dtype))
    fn, meta = cores[plan.algorithm], {"stats": stats,
                                       "scalar_args": (r0s, r1s)}
    if table is not None:
        fn, meta["table_size"] = partial(fn, table_size=table), table
    return TraceTarget(fn=fn, args=(st.A, st.B, C0, r0s, r1s), meta=meta)


def _audit_sparse(A: CSR, B: CSR, plan: ChunkPlan, c_pad: int,
                  envelope: GeometryEnvelope) -> TraceTarget:
    return _csr_accum_launch(_SPARSE_CORES, [A], [B], plan, c_pad,
                             envelope=envelope)


def _audit_hash(A: CSR, B: CSR, plan: ChunkPlan, c_pad: int,
                envelope: GeometryEnvelope) -> TraceTarget:
    return _csr_accum_launch(_HASH_CORES, [A], [B], plan, c_pad,
                             _envelope_table(envelope, B.n_cols), envelope)


def _sparse_run(A: CSR, B: CSR, plan: ChunkPlan, c_pad: int, backend: str,
                caps=None):
    """Shared body of the unbatched sparse-output executors (ESC and hash):
    validate the realized output structure against the capacities, stage a
    width-1 launch, run it, and assemble the accumulated strip CSRs.

    ``caps`` is the symbolic phase's :class:`StripOutputCaps` when the caller
    (the ``chunked_spgemm`` dispatch) already ran the expansion — the
    symbolic module's amortization contract; recomputed here only for direct
    executor calls.
    """
    if caps is None:
        caps = strip_output_caps(A, B, plan.p_ac)
    table = (hash_table_slots(caps.c_max_row_nnz) if backend == "hash"
             else None)
    check_output_caps(caps.strip_nnz, caps.c_max_row_nnz, c_pad, table,
                      backend=backend, a_shape=A.shape, b_shape=B.shape)
    cores = _HASH_CORES if backend == "hash" else _SPARSE_CORES
    with span("stage") as sp:
        launch = _csr_accum_launch(cores, [A], [B], plan, c_pad, table,
                                   put=sp.put)
    with span("launch"):
        ip, ix, d = launch.fn(*launch.args)
    out = CSR(ip[0], ix[0], d[0], (launch.args[0].n_rows, B.n_cols), c_pad)
    return _assemble(out, plan.p_ac, B.n_cols), launch.meta["stats"]


def chunk_sparse(A: CSR, B: CSR, plan: ChunkPlan, c_pad: int, caps=None):
    """ESC sparse-output executor for any plan algorithm (``_sparse_run``
    dispatches the core on ``plan.algorithm``)."""
    return _sparse_run(A, B, plan, c_pad, "sparse", caps=caps)


def chunk_hash(A: CSR, B: CSR, plan: ChunkPlan, c_pad: int, caps=None):
    """Hash-probe executor for any plan algorithm (see :func:`chunk_sparse`)."""
    return _sparse_run(A, B, plan, c_pad, "hash", caps=caps)


# ---------------------------------------------------------------------------
# masked hash executor: fused output mask (triangle counting's fast path)
# ---------------------------------------------------------------------------


def _make_masked_hash_core(key: str, alg: str):
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
            Ast, Bst, C0st, Mst, r0s, r1s, order=_ORDERS[alg],
            table_size=table_size)

    return core


_HASH_MASKED_CORES = _cores(_make_masked_hash_core, "{alg}_hash_masked")


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
        launch = _csr_accum_launch(_HASH_MASKED_CORES, [A], [B], plan, c_pad,
                                   table, put=sp.put)
        Mst = csr_stack([csr_stack(a_strips(mask, plan.p_ac))])
    Ast, Bst, C0, r0s, r1s = launch.args
    with span("launch"):
        ip, ix, d = launch.fn(Ast, Bst, C0, Mst, r0s, r1s)
    stats = launch.meta["stats"]
    # the mask's structure operands (indptr + indices, no data) stage with
    # the fused C_prev block's index maps: once per strip in the chunk1
    # orders, one whole block in chunk2
    strip_rows = Ast.n_rows
    m_struct = (strip_rows + 1) * 4 + Mst.indices.shape[-1] * 4
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


def _make_bsr_core(key: str, alg: str, *, batched: bool):
    """One jitted launch core for the blocked kernel. The whole
    :class:`GeometryEnvelope` is the *static* jit key: the kernel geometry
    (``nc_pad``, ``u_max``, ``bs``) comes from its ``bsr_caps``, and keying
    on the envelope — not just the caps — gives the backend the same
    retrace-per-envelope semantics as every other backend (two geometries
    whose block caps happen to quantize equal still account separately).

    Batched cores take width-stacked operands (leading axis) and unroll the
    width inside the jit, so the serving layer's width-ladder compile
    accounting sees one (re)trace per (envelope, width)."""
    del alg  # one kernel for every order: _bsr_execute orders the pairs

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


def _make_bsr_batched_cores(donate: bool = False) -> dict:
    """Jitted batched BSR cores (caller-owned executables). Staging is a
    host loop over (strip, chunk) pairs, so there is no device scratch to
    donate."""
    del donate
    return _cores(_make_bsr_core, "{alg}_bsr_batched", batched=True)


_BSR_CORES = _cores(_make_bsr_core, "{alg}_bsr", batched=False)
_BSR_CORES_BATCHED = _make_bsr_batched_cores()


def _bsr_padded(envelope: GeometryEnvelope, k: int, n: int) -> tuple:
    """(k, n, strip rows) rounded up to the envelope's block edge."""
    bs = envelope.bsr_caps[0]
    return tuple(-(-x // bs) * bs for x in (k, n, envelope.strip_rows))


def _bsr_pair(Ad: np.ndarray, Bd: np.ndarray, strip: tuple, chunk: tuple,
              envelope: GeometryEnvelope) -> tuple:
    """One (strip, chunk) pair of dense ``Ad`` and ``Bd`` cut as BSR at the
    envelope's block caps: the strip's rows of A with the columns outside
    the chunk zeroed, at full contraction width, and the chunk's rows of B
    at full output width — so summing pair products over chunks is exactly
    the strip product. The pair's block symbolic runs at the envelope's
    ``nc``/``u`` floors, which makes every pair one kernel geometry (and
    fails loudly if the envelope does not dominate an instance). Returns the
    kernel's operands (blocks with their zero sentinel, then the slot
    tables) and the pair's symbolic result."""
    bs, nbl_a_cap, nbl_b_cap, nc_cap, u_cap = envelope.bsr_caps
    (s, e), (r0, r1) = strip, chunk
    n = Bd.shape[1]
    kpad, npad, srpad = _bsr_padded(envelope, *Bd.shape)
    Am = np.zeros((srpad, kpad), np.float32)
    Am[: e - s, r0:r1] = Ad[s:e, r0:r1]
    Bm = np.zeros((kpad, npad), np.float32)
    Bm[r0:r1, :n] = Bd[r0:r1, :]
    Ab = bsr_from_dense(Am, bs, pad_to=nbl_a_cap)
    Bb = bsr_from_dense(Bm, bs, pad_to=nbl_b_cap)
    meta = bsr_spgemm_symbolic(Ab, Bb, nc_pad=nc_cap, u_max=u_cap)
    return (bsr_blocks_with_sentinel(Ab), bsr_blocks_with_sentinel(Bb),
            jnp.asarray(meta.a_slots), jnp.asarray(meta.b_slots)), meta


def _bsr_execute(As, Bs, plan: ChunkPlan, envelope: GeometryEnvelope, *,
                 batched: bool, cores: dict | None = None):
    """Shared body of the BSR executors: cut every (strip, chunk) pair with
    :func:`_bsr_pair`, launch the blocked kernel per pair (Chunk2 streams
    strips under a stationary chunk, the other orders stream chunks under a
    stationary strip), and accumulate the per-pair outputs into per-strip
    dense C.

    Staging is host-side (like the symbolic phase). Accumulation and staging
    are f32, so comparisons against the loop oracle are allclose on values,
    like the Pallas dense-slab backend."""
    bs, nbl_a_cap, nbl_b_cap, nc_cap, _ = envelope.bsr_caps
    width = len(As)
    k, n = Bs[0].shape
    kpad, npad, srpad = _bsr_padded(envelope, k, n)
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
        cut = [_bsr_pair(Ad, Bd, strips[ia], chunks[jb], envelope)
               for Ad, Bd in zip(Ads, Bds)]
        ops = [c[0] for c in cut]
        if batched:
            out = core(*(jnp.stack(x) for x in zip(*ops)), envelope=envelope)
        else:
            out = core(*ops[0], envelope=envelope)[None]
        out_np = np.asarray(out)
        for w, (_, meta) in enumerate(cut):
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


def _audit_bsr(A: CSR, B: CSR, plan: ChunkPlan, c_pad: int,
               envelope: GeometryEnvelope) -> TraceTarget:
    """The first (strip, chunk) pair, cut as ``_bsr_execute`` cuts every
    pair; every pair launches the same envelope-keyed kernel geometry, so
    one pair is the whole compile surface."""
    del c_pad
    Ad, Bd = (np.asarray(csr_to_dense(M), np.float32) for M in (A, B))
    args, _ = _bsr_pair(Ad, Bd, plan.p_ac[:2], plan.p_b[:2], envelope)
    return TraceTarget(
        fn=partial(_BSR_CORES[plan.algorithm], envelope=envelope),
        args=args, meta={"scalar_args": args[2:]})


# ---------------------------------------------------------------------------
# batched entry point: many problem instances, one plan, one compilation
# ---------------------------------------------------------------------------


def _scan_run_batched(As, Bs, plan: ChunkPlan, envelope: GeometryEnvelope, *,
                      caps_list=None, validate_caps: bool = True,
                      cores: dict | None = None):
    """Batched entry of the scan backend: vmapped lax.scan cores, bitwise-
    identical to the unbatched executors for same-structure batches.
    ``cores`` substitutes a caller-owned core set from
    :func:`_make_scan_batched_cores` for the module-level one."""
    del caps_list, validate_caps  # the ranged merge cannot overflow c_pad
    c_pad, n_cols, dtype = envelope.c_pad, Bs[0].n_cols, As[0].dtype
    st = _stage_batch(As, Bs, plan, envelope,
                      strips=plan.algorithm != "knl")
    rows = st.A.n_rows
    # knl's C is per instance and chunk2's per strip; chunk1's one strip
    # template is broadcast by the vmap
    lead = {"knl": (len(As),), "chunk1": (),
            "chunk2": (plan.n_ac,)}[plan.algorithm]
    C0 = _empty_c(lead, rows, n_cols, c_pad, dtype)
    core = (cores or _SCAN_CORES_BATCHED)[plan.algorithm]
    Cb = core(st.A, st.B, *jax.device_put(plan.b_ranges()), C0, c_pad=c_pad)
    stats = planned_stats(plan, st.b_nbytes, st.a_nbytes,
                          _c_strip_nbytes(rows, c_pad, dtype))
    if plan.algorithm == "knl":
        return csr_unstack(Cb), stats
    return [_assemble(Ci, plan.p_ac, n_cols) for Ci in csr_unstack(Cb)], stats


def _pallas_run_batched(As, Bs, plan: ChunkPlan, envelope: GeometryEnvelope, *,
                        caps_list=None, validate_caps: bool = True,
                        cores: dict | None = None):
    """Batched entry of the Pallas backend: the whole microbatch through one
    ``ranged_spgemm_stream`` launch whose leading grid dimension is the
    batch (staging and accumulation in f32 — allclose, not bitwise, against
    the loop oracle)."""
    del caps_list, validate_caps  # dense accumulators cannot overflow
    knl = plan.algorithm == "knl"
    st = _stage_batch(As, Bs, plan, envelope, strips=not knl)
    r0s, _ = jax.device_put(plan.b_ranges())
    dense = (cores or _PALLAS_CORES_BATCHED)[plan.algorithm](st.A, st.B, r0s)
    rows = st.A.n_rows
    stats = planned_stats_pallas(plan, *_pallas_stage_nbytes(
        rows, envelope.a_shape[1], st.B.n_rows, Bs[0].n_cols))
    p_ac = (0, rows) if knl else plan.p_ac
    return [_pallas_assemble(d, p_ac, np.dtype(As[0].dtype))
            for d in dense], stats


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
    c_pad, n_cols = envelope.c_pad, Bs[0].n_cols
    table = _envelope_table(envelope, n_cols) if kind == "hash" else None
    if validate_caps:
        if caps_list is None:
            caps_list = [strip_output_caps(A, B, plan.p_ac)
                         for A, B in zip(As, Bs)]
        for i, (A, caps) in enumerate(zip(As, caps_list)):
            check_output_caps(caps.strip_nnz, caps.c_max_row_nnz, c_pad,
                              table, backend=kind, a_shape=A.shape,
                              b_shape=Bs[i].shape, instance=i)
    if cores is None:
        cores = _HASH_CORES_BATCHED if kind == "hash" else _SPARSE_CORES_BATCHED
    launch = _csr_accum_launch(cores, As, Bs, plan, c_pad, table, envelope)
    ip, ix, d = launch.fn(*launch.args)
    shape = (launch.args[0].n_rows, n_cols)
    return [
        _assemble(CSR(ip[b], ix[b], d[b], shape, c_pad), plan.p_ac, n_cols)
        for b in range(len(As))
    ], launch.meta["stats"]


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
# traffic models: the per-copy-event byte flows the traced jaxprs must equal
# ---------------------------------------------------------------------------
#
# Each hook declares, per pallas operand and in spec order, the ordered list
# of copy-event byte sizes the staged launch performs over its whole grid —
# the planner-side half of the flow-equality audit (repro.analysis.traffic),
# which reconstructs the same lists from the traced jaxpr and demands exact
# equality, then ties the merged flows to the ChunkStats the audited
# launch's staging logs (``meta["stats"]``, the executors' own staging).


def _pipeline_traffic(plan: ChunkPlan, fields: dict, stats: ChunkStats):
    """The Pallas pipeline's flows (:func:`_pipeline_events`): one per
    staged field of each operand, in the kernel's operand order. ``fields``
    maps each piece (``"a"``, ``"b"``, ``"c"``) to its fields' bytes — one
    for a dense block, ``(indptr, indices, data)`` for a CSR triple, whose
    same-key flows merge into the single ChunkStats event of the triple."""
    OpFlow = backend_registry.OpFlow
    events = _pipeline_events(plan)
    piece = {operand: p for operand, p, _ in events}

    def flows(operands):
        return tuple(
            OpFlow(op, tuple(float(n * f) for o, _, n in events if o == op))
            for op in operands for f in fields[piece[op]])

    return backend_registry.ExpectedTraffic(
        in_ops=flows(("stationary", "streamed", "c_prev")),
        out_ops=flows(("c_out",)),
        stats_in=tuple(stats.per_copy_in), stats_out=tuple(stats.per_copy_out))


def _traffic_pallas(A, B, plan: ChunkPlan, c_pad: int,
                    envelope: GeometryEnvelope, meta):
    """Dense-slab pipeline flows, one dense block per operand."""
    del A, B, c_pad
    strip_rows = (envelope.a_shape[0] if plan.algorithm == "knl"
                  else envelope.strip_rows)
    slab, a_stage, c_stage = _pallas_stage_nbytes(
        strip_rows, envelope.a_shape[1], envelope.chunk_rows,
        envelope.b_shape[1])
    return _pipeline_traffic(
        plan, {"a": (a_stage,), "b": (slab,), "c": (c_stage,)},
        meta["stats"])


def _traffic_csr_accum(A, B, plan: ChunkPlan, c_pad: int,
                       envelope: GeometryEnvelope, meta):
    """CSR-accumulator (ESC and hash) flows: every logical operand is three
    field operands whose per-event bytes sum to the staged triple's
    ``CSR.nbytes()``. knl stages as the 1-strip chunk1 special case."""
    del A, B
    item = int(np.dtype(envelope.dtype).itemsize)
    return _pipeline_traffic(plan, {
        "a": csr_field_nbytes(envelope.strip_rows, envelope.strip_nnz_cap,
                              item),
        "b": csr_field_nbytes(envelope.chunk_rows, envelope.chunk_nnz_cap,
                              item),
        "c": csr_field_nbytes(envelope.strip_rows, c_pad, item),
    }, meta["stats"])


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


# ---------------------------------------------------------------------------
# registrations: the one place each backend is wired into the stack
# ---------------------------------------------------------------------------


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
        executors=dict.fromkeys(algs, _scan_run),
        run_batched=_scan_run_batched,
        trace_key="{alg}",
        trace_key_batched="{alg}_batched",
        audit_trace=_scan_launch,
        make_batched_cores=_make_scan_batched_cores,
    ))
    register(Spec(
        name="pallas",
        executors=dict.fromkeys(algs, _pallas_run),
        run_batched=_pallas_run_batched,
        byte_model=planned_stats_dense_slab,
        trace_key="{alg}_pallas",
        trace_key_batched="{alg}_pallas_batched",
        is_accumulator=True,
        audit_trace=_pallas_launch,
        traffic_model=_traffic_pallas,
        make_batched_cores=_make_pallas_batched_cores,
    ))
    register(Spec(
        name="sparse",
        executors=dict.fromkeys(algs, chunk_sparse),
        run_batched=partial(_csr_accum_run_batched, kind="sparse"),
        byte_model=planned_stats_sparse,
        trace_key="{alg}_sparse",
        trace_key_batched="{alg}_sparse_batched",
        needs_output_caps=True,
        is_accumulator=True,
        audit_trace=_audit_sparse,
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
        run_batched=partial(_csr_accum_run_batched, kind="hash"),
        byte_model=planned_stats_hash,
        trace_key="{alg}_hash",
        trace_key_batched="{alg}_hash_batched",
        needs_output_caps=True,
        is_accumulator=True,
        run_masked=chunk_hash_masked,
        audit_trace=_audit_hash,
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
