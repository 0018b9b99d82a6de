"""Chunked SpGEMM executors: the paper's Algorithms 1 (KNL), 2 (Chunk1), 3 (Chunk2).

All three share the ranged fused-multiply-add kernel (repro.core.kkmem.spgemm_ranged):
a row-partition of B induces a column-partition of A that is realized by *skipping*
(masking) out-of-range A columns, never by physically repartitioning A.

Static-shape discipline: every B chunk is padded to the largest chunk's nnz and every
A/C row-strip to the largest strip, so each algorithm traces the jitted kernel exactly
once regardless of the partition count.

Executors return (C, ChunkStats); ChunkStats carries the *actual* fast<->slow traffic
(what `copy2Fast`/`copy2Slow` would have moved), which tests compare against the
planner's modeled copy cost, and which the benchmarks feed into the memory cost model
to reproduce the paper's figures.

This module holds the host-driven loop executors (the oracle path) and the
dispatcher; the device-resident single-trace scan executors live in
repro.core.chunk_stream and are the default backend of ``chunked_spgemm``.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import jax.numpy as jnp

from repro.core.kkmem import spgemm, spgemm_ranged
from repro.core.planner import ChunkPlan
from repro.core.symbolic import strip_output_caps
from repro.sparse.csr import (
    CSR, GeometryEnvelope, csr_pad_to, csr_select_rows_host,
)


@dataclasses.dataclass
class ChunkStats:
    algorithm: str
    n_ac: int
    n_b: int
    copy_in_bytes: float = 0.0   # slow -> fast
    copy_out_bytes: float = 0.0  # fast -> slow
    kernel_calls: int = 0
    # ordered per-copy event logs (one entry per staged transfer, in issue
    # order). The loop executors append as they go; the scan executors compute
    # the identical sequence from the plan (a traced scan cannot mutate Python
    # state), so loop-vs-scan stats can be compared event-for-event.
    per_copy_in: list = dataclasses.field(default_factory=list)
    per_copy_out: list = dataclasses.field(default_factory=list)

    @property
    def copy_bytes(self) -> float:
        return self.copy_in_bytes + self.copy_out_bytes

    def add_in(self, nbytes: float) -> None:
        self.copy_in_bytes += nbytes
        self.per_copy_in.append(float(nbytes))

    def add_out(self, nbytes: float) -> None:
        self.copy_out_bytes += nbytes
        self.per_copy_out.append(float(nbytes))


def _partition_caps(m: CSR, bounds: tuple) -> tuple:
    """(nnz cap, row cap) of the largest piece of a contiguous row partition."""
    ptr = np.asarray(m.indptr)
    cap = max(int(ptr[e] - ptr[s]) for s, e in zip(bounds[:-1], bounds[1:]))
    rows = max(e - s for s, e in zip(bounds[:-1], bounds[1:]))
    return max(cap, 1), rows


def b_chunks(B: CSR, p_b: tuple, envelope: GeometryEnvelope | None = None):
    """Row chunks of B, uniformly padded (rows and nnz) so jit traces once.

    Without an envelope the caps come from this instance's largest chunk (the
    single-problem case); with one, every chunk is padded to the envelope's
    ``chunk_nnz_cap``/``chunk_rows``/``b_max_row_nnz`` so chunks from
    *different* instances stack into one batch."""
    if envelope is None:
        cap, rows = _partition_caps(B, p_b)
        mrn = B.max_row_nnz
    else:
        cap, rows = envelope.chunk_nnz_cap, envelope.chunk_rows
        mrn = envelope.b_max_row_nnz
    return [
        csr_pad_to(csr_select_rows_host(B, s, e, pad_to=cap),
                   rows=rows, max_row_nnz=mrn)
        for s, e in zip(p_b[:-1], p_b[1:])
    ]


def a_strips(A: CSR, p_ac: tuple, envelope: GeometryEnvelope | None = None):
    """Row strips of A, uniformly padded (rows and nnz); with an envelope the
    caps are the batch-wide ``strip_nnz_cap``/``strip_rows``/``a_max_row_nnz``."""
    if envelope is None:
        cap, rows = _partition_caps(A, p_ac)
        mrn = A.max_row_nnz
    else:
        cap, rows = envelope.strip_nnz_cap, envelope.strip_rows
        mrn = envelope.a_max_row_nnz
    return [
        csr_pad_to(csr_select_rows_host(A, s, e, pad_to=cap),
                   rows=rows, max_row_nnz=mrn)
        for s, e in zip(p_ac[:-1], p_ac[1:])
    ]


def instance_envelope(A: CSR, B: CSR, plan: ChunkPlan,
                      c_pad: int | None = None,
                      caps=None, block_size: int | None = None) -> GeometryEnvelope:
    """The padded geometry one (A, B) instance needs under ``plan``.

    The symbolic phase (repro.core.symbolic) runs once here: its output caps
    (whole-C capacity, densest C row, largest-strip capacity) are folded into
    the envelope so sparse-output executables are compile-keyed on the output
    structure too. ``c_pad`` only overrides the *capacity* field; the
    structural bounds stay exact. This is deliberate even when ``c_pad`` is
    given (which used to skip the symbolic phase entirely): an envelope is a
    compile key, and two instances must get equal envelopes regardless of
    which caller built them — callers that already ran the symbolic phase
    pass its ``StripOutputCaps`` as ``caps`` to avoid the repeat expansion.

    ``block_size`` opts into the *block*-level symbolic phase
    (``repro.core.symbolic.bsr_plan_caps``): the envelope additionally
    carries ``bsr_caps``, making block-structured backends (``"bsr"``)
    dispatchable and priceable by the planner under this envelope. It is
    opt-in because the block analysis is another host pass and block
    backends only ever win on block-structured operands."""
    if caps is None:
        caps = strip_output_caps(A, B, plan.p_ac)
    if c_pad is None:
        c_pad = caps.c_pad
    bsr_caps = ()
    if block_size is not None:
        from repro.core.symbolic import bsr_plan_caps

        bsr_caps = bsr_plan_caps(A, B, plan, block_size).as_tuple()
    chunk_cap, chunk_rows = _partition_caps(B, plan.p_b)
    strip_cap, strip_rows = _partition_caps(A, plan.p_ac)
    return GeometryEnvelope(
        a_shape=A.shape, b_shape=B.shape,
        a_nnz_cap=A.nnz_pad, a_max_row_nnz=A.max_row_nnz,
        b_max_row_nnz=B.max_row_nnz,
        chunk_rows=chunk_rows, chunk_nnz_cap=chunk_cap,
        strip_rows=strip_rows, strip_nnz_cap=strip_cap,
        c_pad=int(c_pad), dtype=str(A.dtype),
        c_nnz_cap=caps.c_nnz_cap, c_max_row_nnz=caps.c_max_row_nnz,
        bsr_caps=bsr_caps,
    )


def batch_envelope(As, Bs, plan: ChunkPlan, c_pad: int | None = None,
                   caps_list=None, block_size: int | None = None) -> GeometryEnvelope:
    """Union of per-instance envelopes: the smallest shared padded geometry a
    heterogeneous batch can be repadded to (``c_pad`` overrides the symbolic
    default for every instance when given). Callers that already ran the
    symbolic phase per instance pass its ``StripOutputCaps`` as ``caps_list``
    to avoid repeating the expansions; ``block_size`` folds block caps into
    every instance envelope (see :func:`instance_envelope`) so the union is
    block-capped too."""
    As, Bs = list(As), list(Bs)
    if caps_list is None:
        caps_list = [None] * len(As)
    return GeometryEnvelope.batch(
        instance_envelope(A, B, plan, c_pad=c_pad, caps=caps,
                          block_size=block_size)
        for (A, B), caps in zip(zip(As, Bs), caps_list)
    )


def _empty_like_c(n_rows: int, n_cols: int, c_pad: int, dtype) -> CSR:
    return CSR(
        indptr=jnp.zeros(n_rows + 1, jnp.int32),
        indices=jnp.zeros(c_pad, jnp.int32),
        data=jnp.zeros(c_pad, dtype),
        shape=(n_rows, n_cols),
        max_row_nnz=0,
    )


def _assemble(strips, p_ac: tuple, n_cols: int) -> CSR:
    """Concatenate per-strip C results (host) into one CSR over all rows."""
    ptrs, idxs, vals = [], [], []
    base = 0
    for (s, e), c in zip(zip(p_ac[:-1], p_ac[1:]), strips):
        ptr = np.asarray(c.indptr)[: e - s + 1]
        nnz = int(ptr[-1])
        ptrs.append(ptr[:-1] + base)
        idxs.append(np.asarray(c.indices)[:nnz])
        vals.append(np.asarray(c.data)[:nnz])
        base += nnz
    indptr = np.concatenate(ptrs + [[base]])
    from repro.sparse.csr import csr_from_scipy_like

    return csr_from_scipy_like(indptr, np.concatenate(idxs), np.concatenate(vals),
                               (p_ac[-1] - p_ac[0], n_cols))


# ---------------------------------------------------------------------------
# Algorithm 1: KNL chunking — A, C in slow memory; stream B chunks through fast
# ---------------------------------------------------------------------------


def chunk_knl(A: CSR, B: CSR, plan: ChunkPlan, c_pad: int):
    stats = ChunkStats("knl", 1, plan.n_b)
    chunks = b_chunks(B, plan.p_b)
    C = _empty_like_c(A.n_rows, B.n_cols, c_pad, A.dtype)
    for (r0, r1), Bc in zip(zip(plan.p_b[:-1], plan.p_b[1:]), chunks):
        stats.add_in(Bc.nbytes())                       # copy2Fast(B, B_rp)
        C = spgemm_ranged(A, Bc, r0, r1, C, c_pad)      # kkmem(A, FastB, C, B_rp)
        stats.kernel_calls += 1
    return C, stats


# ---------------------------------------------------------------------------
# Algorithms 2 & 3: GPU chunking — 2-D partitions, two streaming orders
# ---------------------------------------------------------------------------


def chunk_gpu1(A: CSR, B: CSR, plan: ChunkPlan, c_pad: int):
    """Alg. 2 — A,C strips stationary in fast memory; B chunks streamed (inner)."""
    stats = ChunkStats("chunk1", plan.n_ac, plan.n_b)
    strips = a_strips(A, plan.p_ac)
    chunks = b_chunks(B, plan.p_b)
    out = []
    for (a0, a1), Ai in zip(zip(plan.p_ac[:-1], plan.p_ac[1:]), strips):
        stats.add_in(Ai.nbytes())                        # FA = copy2Fast(A)
        stats.add_in((a1 - a0 + 1) * 4)                  # FC row pointers only
        Ci = _empty_like_c(Ai.n_rows, B.n_cols, c_pad, A.dtype)
        for (r0, r1), Bc in zip(zip(plan.p_b[:-1], plan.p_b[1:]), chunks):
            stats.add_in(Bc.nbytes())                    # FB = copy2Fast(B)
            Ci = spgemm_ranged(Ai, Bc, r0, r1, Ci, c_pad)
            stats.kernel_calls += 1
        stats.add_out(Ci.nbytes())                       # copy2Slow(FC)
        out.append(Ci)
    return _assemble(out, plan.p_ac, B.n_cols), stats


def chunk_gpu2(A: CSR, B: CSR, plan: ChunkPlan, c_pad: int):
    """Alg. 3 — B chunk stationary in fast memory; A,C strips streamed (inner)."""
    stats = ChunkStats("chunk2", plan.n_ac, plan.n_b)
    strips = a_strips(A, plan.p_ac)
    chunks = b_chunks(B, plan.p_b)
    partials = [
        _empty_like_c(s.n_rows, B.n_cols, c_pad, A.dtype) for s in strips
    ]
    n_b = plan.n_b
    for jb, ((r0, r1), Bc) in enumerate(zip(zip(plan.p_b[:-1], plan.p_b[1:]), chunks)):
        stats.add_in(Bc.nbytes())                        # FB = copy2Fast(B)
        for ia, Ai in enumerate(strips):
            stats.add_in(Ai.nbytes())                    # FA = copy2Fast(A)
            if jb > 0:
                stats.add_in(partials[ia].nbytes())            # FC partial back in
            partials[ia] = spgemm_ranged(Ai, Bc, r0, r1, partials[ia], c_pad)
            stats.kernel_calls += 1
            if jb < n_b - 1:
                stats.add_out(partials[ia].nbytes())           # partial out
        if jb == n_b - 1:
            for ia in range(len(strips)):
                stats.add_out(partials[ia].nbytes())           # final copy2Slow
    return _assemble(partials, plan.p_ac, B.n_cols), stats


# ---------------------------------------------------------------------------
# dispatcher
# ---------------------------------------------------------------------------


def default_c_pad(A: CSR, B: CSR, plan: ChunkPlan) -> int:
    """Exact symbolic capacity of the largest row strip (whole C for 1-strip
    plans). One global symbolic expansion (repro.core.symbolic), numerically
    identical to running the symbolic phase per strip."""
    return strip_output_caps(A, B, plan.p_ac).c_pad


def chunked_spgemm(A: CSR, B: CSR, plan: ChunkPlan, c_pad: int | None = None,
                   backend: str = "scan", block_size: int | None = None):
    """Execute a ChunkPlan. ``c_pad`` defaults to the exact symbolic capacity of the
    largest row strip (whole C for 1-strip plans).

    ``backend`` names a registered :class:`repro.core.backend_registry.
    BackendSpec` (``"loop"``, ``"scan"``, ``"pallas"``, ``"sparse"``,
    ``"hash"``, ``"bsr"``, ...) or ``"auto"``, which lets the planner pick
    the accumulator backend whose peak-resident byte model is smallest under
    this instance's envelope (``planner.select_accumulator_backend``), and
    raises when no candidate fits the kernels' fast limit. The dispatch is entirely registry-driven: the spec supplies the
    per-algorithm executor, and its capability flags decide what the
    dispatcher stages — ``needs_output_caps`` backends receive the symbolic
    phase's ``StripOutputCaps`` (one expansion amortized across the default
    ``c_pad``, the auto resolve, and the executor's overflow check).

    ``block_size`` opts the *block* symbolic phase into the envelope: under
    ``backend="auto"`` the planner can then price (and select) block
    backends like ``"bsr"``; under an explicit block backend it overrides
    that backend's default block edge. See ``docs/backends.md``.
    """
    from repro.core import backend_registry

    spec = None if backend == "auto" else backend_registry.get(backend)
    # one symbolic expansion serves the default c_pad, the auto resolve, and
    # the caps-consuming executors' overflow checks (the symbolic module's
    # amortize-the-host-pass contract)
    caps = None
    if c_pad is None or backend == "auto" or (spec is not None
                                              and spec.needs_output_caps):
        caps = strip_output_caps(A, B, plan.p_ac)
    if c_pad is None:
        c_pad = caps.c_pad
    if plan.algorithm == "whole_fast":
        stats = ChunkStats("whole_fast", 1, 1)
        stats.add_in(A.nbytes() + B.nbytes())
        C = spgemm(A, B, c_pad)
        stats.add_out(C.nbytes())
        stats.kernel_calls = 1
        return C, stats
    if backend == "auto":
        from repro.core.planner import select_accumulator_backend

        env = instance_envelope(A, B, plan, c_pad=c_pad, caps=caps,
                                block_size=block_size)
        spec = backend_registry.get(select_accumulator_backend(plan, env))
    fn = spec.executors.get(plan.algorithm)
    if fn is None:
        raise ValueError(f"unknown algorithm {plan.algorithm!r}")
    kwargs = {}
    if spec.needs_output_caps:
        kwargs["caps"] = caps
    if block_size is not None and spec.needs_block_caps:
        kwargs["block_size"] = block_size
    return fn(A, B, plan, c_pad, **kwargs)
