"""Chunk executors (Algs 1-3) + planner (Alg 4): correctness and cost properties."""

import numpy as np
import pytest
from conftest import given, settings, st

from repro.core.kkmem import spgemm_symbolic_host, spgemm_dense_oracle
from repro.core.planner import (
    ChunkPlan, plan_chunks, plan_knl, binary_search_partition, partition_cost,
    row_bytes_csr,
)
from repro.core.chunking import chunked_spgemm, chunk_knl, chunk_gpu1, chunk_gpu2
from repro.core.memory_model import P100
from repro.sparse import multigrid
from repro.sparse.csr import csr_to_dense
from conftest import assert_close


@pytest.fixture(scope="module")
def problem():
    A, R, P = multigrid.problem("brick3d", 5)
    return A, P


@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(1, 500), min_size=1, max_size=60),
       st.floats(1.0, 2000.0))
def test_binary_search_partition_properties(row_bytes, target):
    rb = np.asarray(row_bytes, np.float64)
    bounds = binary_search_partition(rb, target)
    assert bounds[0] == 0 and bounds[-1] == len(row_bytes)
    assert list(bounds) == sorted(set(bounds))
    for s, e in zip(bounds[:-1], bounds[1:]):
        size = rb[s:e].sum()
        # each chunk fits, unless it is a single oversized row
        assert size <= target or (e - s) == 1


def test_knl_chunking_matches_oracle(problem):
    A, P = problem
    ref = np.asarray(spgemm_dense_oracle(A, P))
    for frac in (0.6, 0.34, 0.15):
        plan = plan_knl(A, P, fast_limit_bytes=P.nbytes() * frac)
        assert plan.n_b >= 2
        C, stats = chunked_spgemm(A, P, plan)
        assert_close(csr_to_dense(C), ref, atol=1e-4)
        assert stats.kernel_calls == plan.n_b


@pytest.mark.parametrize("algorithm", ["chunk1", "chunk2"])
def test_gpu_chunking_matches_oracle(problem, algorithm):
    A, P = problem
    ws = spgemm_symbolic_host(A, P)
    ref = np.asarray(spgemm_dense_oracle(A, P))
    crb = np.full(A.n_rows, max(ws.c_nnz / A.n_rows, 1.0) * 12)
    tiny = (A.nbytes() + P.nbytes() + float(crb.sum())) / 5
    plan = plan_chunks(A, P, crb, P100, fast_limit_bytes=tiny)
    plan = type(plan)(algorithm, plan.p_ac, plan.p_b, plan.copy_bytes,
                      plan.fast_bytes_needed)
    fn = chunk_gpu1 if algorithm == "chunk1" else chunk_gpu2
    C, stats = fn(A, P, plan, c_pad=ws.c_pad)
    assert_close(csr_to_dense(C), ref, atol=1e-4)
    assert stats.kernel_calls == plan.n_ac * plan.n_b


def test_both_orders_same_result(problem, rng):
    """Chunk1 and Chunk2 stream in different orders but must agree exactly."""
    A, P = problem
    ws = spgemm_symbolic_host(A, P)
    crb = np.full(A.n_rows, 12.0)
    plan = plan_chunks(A, P, crb, P100,
                       fast_limit_bytes=(A.nbytes() + P.nbytes()) / 4)
    c1, _ = chunk_gpu1(A, P, plan, c_pad=ws.c_pad)
    c2, _ = chunk_gpu2(A, P, plan, c_pad=ws.c_pad)
    assert_close(csr_to_dense(c1), csr_to_dense(c2), atol=1e-5)


def test_planner_whole_fast_when_it_fits(problem):
    A, P = problem
    crb = np.full(A.n_rows, 12.0)
    plan = plan_chunks(A, P, crb, P100, fast_limit_bytes=1e12)
    assert plan.algorithm == "whole_fast"
    assert plan.n_ac == 1 and plan.n_b == 1


def test_planner_prefers_resident_b(problem):
    """Alg 4: when B fits in the big portion, B stays resident (chunk2)."""
    A, P = problem
    crb = np.full(A.n_rows, 12.0)
    # use the planner's own byte convention (row_bytes_csr = 12 B/entry)
    size_a = float(row_bytes_csr(A).sum())
    size_b = float(row_bytes_csr(P).sum())
    fast = size_b / 0.7   # B fits in the 75% portion
    assert size_a + size_b + crb.sum() > fast  # whole problem does not fit
    plan = plan_chunks(A, P, crb, P100, fast_limit_bytes=fast)
    assert plan.algorithm == "chunk2"
    assert plan.n_b == 1


def test_planner_picks_cheaper_order(problem):
    """When 2-D chunking is forced, Alg 4 must choose the order with the lower
    modeled copy cost (in the planner's own byte units)."""
    A, P = problem
    crb = np.full(A.n_rows, 12.0)
    size_a = float(row_bytes_csr(A).sum())
    size_b = float(row_bytes_csr(P).sum())
    size_c = float(crb.sum())
    tiny = (size_a + size_b + size_c) / 6
    plan = plan_chunks(A, P, crb, P100, fast_limit_bytes=tiny)
    c1 = partition_cost(size_a, size_b, size_c, plan.n_ac, plan.n_b, "chunk1")
    c2 = partition_cost(size_a, size_b, size_c, plan.n_ac, plan.n_b, "chunk2")
    assert plan.copy_bytes == min(c1, c2)
    assert plan.algorithm == ("chunk1" if c1 <= c2 else "chunk2")


def test_copy_cost_formulas():
    # paper §3.3.1:  chunk1 = |A|+|C|+|B|*n_ac ; chunk2 = |B|+|A|*n_b+|C|*(n_b-1)
    assert partition_cost(10, 20, 5, 3, 4, "chunk1") == 10 + 5 + 20 * 3
    assert partition_cost(10, 20, 5, 3, 4, "chunk2") == 20 + 10 * 4 + 5 * 3


def test_chunk_stats_track_copies(problem):
    """Actual staged bytes scale with the planned partition counts."""
    A, P = problem
    ws = spgemm_symbolic_host(A, P)
    plan = plan_knl(A, P, fast_limit_bytes=P.nbytes() / 3)
    _, stats = chunk_knl(A, P, plan, ws.c_pad)
    # B is staged exactly once in total (row-chunks are disjoint), up to padding
    assert stats.copy_in_bytes >= P.nbytes() * 0.9
    assert stats.copy_in_bytes <= P.nbytes() * plan.n_b  # padding slack bound


def test_plan_knl_models_padded_staged_footprint():
    """The executors stage uniformly padded chunks (every chunk padded to the
    largest chunk's nnz and rows), so the planned fast footprint must cover
    the *staged* chunk bytes — summing unpadded per-chunk bytes undercounts
    on skewed row distributions."""
    from repro.core.chunking import b_chunks
    from repro.sparse.csr import csr_from_dense

    rng = np.random.default_rng(2)
    # skewed B: one fully dense row among hundreds of near-empty ones, so the
    # padded chunk envelope (dense-row nnz cap x widest row span) far exceeds
    # any single chunk's unpadded bytes
    n_rows = 256
    dense = (rng.random((n_rows, 48)) < 0.01) * rng.standard_normal((n_rows, 48))
    dense[0] = rng.standard_normal(48)             # one fully dense row
    B = csr_from_dense(dense.astype(np.float32))
    A = csr_from_dense(np.eye(n_rows, dtype=np.float32))
    size_b = float(row_bytes_csr(B).sum())
    for frac in (0.5, 0.3, 0.15):
        plan = plan_knl(A, B, fast_limit_bytes=size_b * frac)
        chunks = b_chunks(B, plan.p_b)
        staged = max(c.nbytes() for c in chunks)
        assert plan.fast_bytes_needed >= staged, (
            f"frac={frac}: modeled {plan.fast_bytes_needed} < staged {staged}"
        )
    # the pre-fix model (max unpadded chunk bytes) genuinely undercounts here
    plan = plan_knl(A, B, fast_limit_bytes=size_b * 0.15)
    unpadded = max(
        float(row_bytes_csr(B)[s:e].sum())
        for s, e in zip(plan.p_b[:-1], plan.p_b[1:])
    )
    staged = max(c.nbytes() for c in b_chunks(B, plan.p_b))
    assert unpadded < staged


def _skewed_csr(rng, n_rows, n_cols, density, dense_rows=1):
    d = (rng.random((n_rows, n_cols)) < density) * rng.standard_normal(
        (n_rows, n_cols))
    d[:dense_rows] = rng.standard_normal((dense_rows, n_cols))
    from repro.sparse.csr import csr_from_dense
    return csr_from_dense(d.astype(np.float32))


def test_plan_chunks_models_padded_staged_footprint():
    """Regression for the Alg-4 planner's fast-memory model: all three
    branches must report the *staged* peak footprint the executors allocate
    (resident operands + padded streamed envelopes). The pre-fix model used
    the densest single row for the streamed term (and, in the 2-D branch,
    reported the limit itself), so skewed rows made plans "fit" while their
    padded strips/chunks did not."""
    from repro.core.chunking import a_strips, b_chunks

    rng = np.random.default_rng(4)
    n = 192
    A = _skewed_csr(rng, n, n, 0.05)
    B = _skewed_csr(rng, n, n, 0.15)
    crb = np.full(n, 12.0)
    a_rows, b_rows = row_bytes_csr(A), row_bytes_csr(B)
    size_a, size_b, size_c = (float(a_rows.sum()), float(b_rows.sum()),
                              float(crb.sum()))
    ac_rows = a_rows + crb

    def staged_ab(plan):
        sa = max(s.nbytes() for s in a_strips(A, plan.p_ac))
        sb = max(c.nbytes() for c in b_chunks(B, plan.p_b))
        return sa, sb

    # branch 1: B resident, stream A/C strips (chunk2, n_b == 1): a limit
    # above size_b / 0.75 (B fits the big portion) but below the whole problem
    fast = (size_b / 0.75 + (size_a + size_b + size_c)) / 2
    plan = plan_chunks(A, B, crb, P100, fast_limit_bytes=fast)
    assert plan.algorithm == "chunk2" and plan.n_b == 1
    sa, sb = staged_ab(plan)
    assert plan.fast_bytes_needed >= size_b + sa
    # pre-fix model: resident B + densest single A/C row — undercounts the
    # padded strip the executors actually stage, so it fails the bound above
    assert size_b + float(ac_rows.max()) < size_b + sa

    # branch 2: A,C resident, stream B chunks (chunk1)
    fast = (size_a + size_c) / 0.7
    assert size_b > 0.75 * fast       # B must not fit the big portion
    plan = plan_chunks(A, B, crb, P100, fast_limit_bytes=fast)
    assert plan.algorithm == "chunk1" and plan.n_ac == 1
    sa, sb = staged_ab(plan)
    assert plan.fast_bytes_needed >= size_a + size_c + sb
    assert size_a + size_c + float(b_rows.max()) < size_a + size_c + sb

    # branch 3: 2-D chunking — the pre-fix model reported the limit `fast`
    # itself; the footprint must instead be the staged strip + chunk peak
    fast = (size_a + size_b + size_c) / 6
    plan = plan_chunks(A, B, crb, P100, fast_limit_bytes=fast)
    assert plan.algorithm in ("chunk1", "chunk2")
    assert plan.n_ac >= 2 and plan.n_b >= 2
    sa, sb = staged_ab(plan)
    assert plan.fast_bytes_needed >= sa + sb
    assert plan.fast_bytes_needed != fast


def test_planned_stats_sparse_lifts_dense_slab_bound(rng):
    """Acceptance: on a wide, sparse-output geometry the dense-slab backend
    model blows a fast-memory limit the plan was meant for, while the
    CSR-native sparse model — scaling with the symbolic nnz caps, not with
    n_cols — fits under it. This is the planner-side statement of why
    backend="sparse" admits larger strips when C is sparse."""
    from conftest import random_dense
    from repro.core.chunking import instance_envelope
    from repro.core.planner import (
        ChunkPlan, planned_stats_dense_slab, planned_stats_sparse,
    )
    from repro.sparse.csr import csr_from_dense

    A = csr_from_dense(random_dense(rng, 64, 64, 0.05))
    B = csr_from_dense(random_dense(rng, 64, 512, 0.01))   # wide, very sparse C
    plan = ChunkPlan("chunk1", (0, 32, 64), (0, 22, 43, 64), 0.0, 0.0)
    env = instance_envelope(A, B, plan)

    dense = planned_stats_dense_slab(plan, env)
    sparse = planned_stats_sparse(plan, env)
    fast_limit = 48 * 1024
    assert dense.fast_bytes_needed > fast_limit
    assert sparse.fast_bytes_needed < dense.fast_bytes_needed
    assert sparse.fast_bytes_needed < fast_limit
    # both models are their components' sum (no hidden terms)
    for model in (dense, sparse):
        assert model.fast_bytes_needed == (
            2 * model.streamed_bytes + model.stationary_bytes
            + model.c_accum_bytes + model.workspace_bytes)
    # chunk2 keeps every strip's accumulator resident: n_ac x the C block
    plan2 = ChunkPlan("chunk2", plan.p_ac, plan.p_b, 0.0, 0.0)
    assert (planned_stats_sparse(plan2, env).c_accum_bytes
            == plan.n_ac * sparse.c_accum_bytes)
    # the sparse model is n_cols-independent at fixed caps: widening B only
    # moves the dense model
    import dataclasses
    wide = dataclasses.replace(env, b_shape=(env.b_shape[0], 4096))
    assert (planned_stats_sparse(plan, wide).fast_bytes_needed
            == sparse.fast_bytes_needed)
    assert (planned_stats_dense_slab(plan, wide).fast_bytes_needed
            > dense.fast_bytes_needed)


def test_replan_for_latency_coarsens_streamed_partition():
    """Latency feedback: drop every other interior boundary of p_b — chunk
    count halves (rounding up), row coverage is preserved, and the modeled
    fast-memory footprint grows accordingly."""
    from repro.core.planner import replan_for_latency

    plan = ChunkPlan("chunk1", (0, 8), (0, 2, 4, 6, 8), 10.0, 100.0)
    p1 = replan_for_latency(plan)
    assert p1.p_b == (0, 4, 8) and p1.n_b == 2
    assert p1.algorithm == plan.algorithm and p1.p_ac == plan.p_ac
    assert p1.fast_bytes_needed > plan.fast_bytes_needed
    p2 = replan_for_latency(p1)
    assert p2.p_b == (0, 8) and p2.n_b == 1
    assert replan_for_latency(p2) is p2          # single chunk: fixed point
    # odd chunk counts round up: 5 -> 3
    odd = ChunkPlan("knl", (0, 4), (0, 1, 2, 3, 4, 5), 0.0, 1.0)
    assert replan_for_latency(odd).p_b == (0, 2, 4, 5)


def test_plan_dense_slab_fits_its_kernel_and_runs_under_auto():
    """The dense-slab planner sizes strips and chunks to its backend's own
    byte model and to the scoped VMEM its kernel asks for, and ``auto``
    resolves such a plan to that backend."""
    from repro.core.chunking import instance_envelope
    from repro.core.memory_model import TPU_V5E
    from repro.core.planner import (
        dense_slab_slow_bytes, plan_dense_slab, select_accumulator_backend,
    )
    from repro.kernels.ranged_spgemm import vmem_need_bytes
    from repro.kernels.scoped_vmem import VMEM_CAPACITY_BYTES

    A, _, P = multigrid.problem("brick3d", 12)
    plan = plan_dense_slab(A, P, TPU_V5E)
    assert plan.fast_bytes_needed <= TPU_V5E.fast.capacity_bytes
    strip = max(np.diff(plan.p_ac))
    chunk = max(np.diff(plan.p_b))
    assert vmem_need_bytes("chunk1", plan.n_ac, strip, A.n_cols, chunk,
                           P.n_cols) <= VMEM_CAPACITY_BYTES
    assert (dense_slab_slow_bytes(plan, A.shape, P.shape)
            <= TPU_V5E.slow.capacity_bytes)
    env = instance_envelope(A, P, plan)
    assert select_accumulator_backend(plan, env) == "pallas"
    C, _ = chunked_spgemm(A, P, plan, backend="auto")
    assert_close(csr_to_dense(C), spgemm_dense_oracle(A, P), atol=1e-4)


def test_plan_dense_slab_refuses_staging_beyond_slow_memory():
    from repro.core.memory_model import TPU_V5E
    from repro.core.planner import plan_dense_slab

    A, _, P = multigrid.problem("brick3d", 36)   # dense A alone is 8.7 GB
    with pytest.raises(ValueError, match="of HBM, more than"):
        plan_dense_slab(A, P, TPU_V5E)


@pytest.mark.parametrize("fast_limit,fits", [(2 ** 18, True),
                                             (2 ** 20, False)])
def test_auto_on_row_byte_plan_runs_or_names_the_misfit(fast_limit, fits):
    """``plan_chunks`` sizes pieces by CSR bytes; the dense slab of such a
    plan may or may not fit the kernels' fast limit. ``auto`` runs it when
    it does and raises a planning error, before any staging, when no
    compiled backend fits."""
    from repro.core.memory_model import TPU_V5E
    from repro.core.symbolic import spgemm_pattern_host

    A, _, P = multigrid.problem("brick3d", 16)
    T = spgemm_pattern_host(A, P)
    plan = plan_chunks(A, P, row_bytes_csr(T), TPU_V5E,
                       fast_limit_bytes=fast_limit)
    assert plan.algorithm == "chunk2"
    if fits:
        C, _ = chunked_spgemm(A, P, plan, backend="auto")
        assert_close(csr_to_dense(C), spgemm_dense_oracle(A, P), atol=1e-4)
    else:
        with pytest.raises(ValueError, match="no compiled backend fits"):
            chunked_spgemm(A, P, plan, backend="auto")
