"""Scan and Pallas executors (repro.core.chunk_stream) vs the loop oracle.

The contract: for every algorithm and every plan, the device-resident scan
executor produces the *identical* CSR (structure and values, bit-for-bit) and
the *identical* modeled per-copy byte event sequence as the host-driven loop,
while compiling its chunk loop O(1) times regardless of the chunk count. The
Pallas double-buffered backend accumulates densely (explicit DMA prefetch of
the streamed operand), so its contract is allclose to the oracle at matched
``c_pad`` — same O(1) trace bound, its own per-copy event model
(``planned_stats_pallas``).
"""

import gc
import weakref
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.core.chunk_stream import (
    TRACE_COUNTS, chunk_gpu1_pallas, chunk_gpu1_scan, chunk_gpu2_pallas,
    chunk_gpu2_scan, chunk_knl_pallas, chunk_knl_scan, chunked_spgemm_batched,
    planned_stats_pallas,
)
from repro.core.chunking import (
    batch_envelope, chunk_gpu1, chunk_gpu2, chunk_knl, chunked_spgemm,
    instance_envelope,
)
from repro.core.kkmem import (
    key_layout, spgemm_dense_oracle, spgemm_ranged_impl, spgemm_symbolic_host,
)
from repro.core.planner import ChunkPlan, plan_knl
from repro.sparse import multigrid
from repro.sparse.csr import (
    CSR, csr_from_dense, csr_from_scipy_like, csr_to_dense,
)
from conftest import assert_close, csr_pair_cases, live_pairs, random_csr

LOOP = {"knl": chunk_knl, "chunk1": chunk_gpu1, "chunk2": chunk_gpu2}
SCAN = {"knl": chunk_knl_scan, "chunk1": chunk_gpu1_scan, "chunk2": chunk_gpu2_scan}
PALLAS = {"knl": chunk_knl_pallas, "chunk1": chunk_gpu1_pallas,
          "chunk2": chunk_gpu2_pallas}


def _random_plan(algorithm, A, B, rng):
    """A random-but-valid plan: contiguous row partitions of A/C and B."""
    def cuts(n, max_parts):
        k = int(rng.integers(1, max_parts + 1))
        inner = sorted(set(rng.integers(1, n, size=k - 1).tolist())) if n > 1 else []
        return tuple([0] + inner + [n])

    p_ac = (0, A.n_rows) if algorithm == "knl" else cuts(A.n_rows, 4)
    p_b = cuts(B.n_rows, 4)
    return ChunkPlan(algorithm, p_ac, p_b, copy_bytes=0.0, fast_bytes_needed=0.0)


def _assert_same_csr(Cl, Cs):
    assert Cl.shape == Cs.shape
    np.testing.assert_array_equal(np.asarray(Cl.indptr), np.asarray(Cs.indptr))
    np.testing.assert_array_equal(np.asarray(Cl.indices), np.asarray(Cs.indices))
    np.testing.assert_array_equal(np.asarray(Cl.data), np.asarray(Cs.data))


@pytest.mark.parametrize("algorithm", ["knl", "chunk1", "chunk2"])
def test_scan_matches_loop_random_plans(algorithm):
    """Property: identical CSRs and identical per-copy byte events across
    random matrices x random plans."""
    rng = np.random.default_rng(7)
    for i, (A, B) in enumerate(csr_pair_cases(n_examples=5, max_dim=18, seed=3)):
        plan = _random_plan(algorithm, A, B, rng)
        c_pad = spgemm_symbolic_host(A, B).c_pad
        Cl, sl = LOOP[algorithm](A, B, plan, c_pad)
        Cs, ss = SCAN[algorithm](A, B, plan, c_pad)
        _assert_same_csr(Cl, Cs)
        assert sl.per_copy_in == ss.per_copy_in, f"case {i}"
        assert sl.per_copy_out == ss.per_copy_out, f"case {i}"
        assert sl.copy_in_bytes == ss.copy_in_bytes
        assert sl.copy_out_bytes == ss.copy_out_bytes
        assert sl.kernel_calls == ss.kernel_calls
        assert_close(csr_to_dense(Cs), spgemm_dense_oracle(A, B), atol=1e-3,
                     msg=f"case {i}")


@pytest.mark.parametrize("algorithm", ["chunk1", "chunk2"])
def test_scan_matches_loop_2d_plans(algorithm):
    """Both 2-D streaming orders on a real multigrid problem."""
    A, R, P = multigrid.problem("brick3d", 5)
    ws = spgemm_symbolic_host(A, P)
    n_a, n_b = A.n_rows, P.n_rows
    plan = ChunkPlan(algorithm,
                     (0, n_a // 3, 2 * n_a // 3, n_a),
                     (0, n_b // 4, n_b // 2, n_b),
                     copy_bytes=0.0, fast_bytes_needed=0.0)
    Cl, sl = LOOP[algorithm](A, P, plan, ws.c_pad)
    Cs, ss = SCAN[algorithm](A, P, plan, ws.c_pad)
    _assert_same_csr(Cl, Cs)
    assert sl.per_copy_in == ss.per_copy_in
    assert sl.per_copy_out == ss.per_copy_out
    assert_close(csr_to_dense(Cs), spgemm_dense_oracle(A, P), atol=1e-4)


def test_dispatcher_backends_agree():
    A, R, P = multigrid.problem("laplace3d", 6)
    plan = plan_knl(A, P, fast_limit_bytes=P.nbytes() * 0.3)
    assert plan.n_b >= 2
    Cl, sl = chunked_spgemm(A, P, plan, backend="loop")
    Cs, ss = chunked_spgemm(A, P, plan, backend="scan")
    Cp, sp = chunked_spgemm(A, P, plan, backend="pallas")
    _assert_same_csr(Cl, Cs)
    assert sl.copy_bytes == ss.copy_bytes
    assert_close(csr_to_dense(Cp), csr_to_dense(Cl), atol=1e-4)
    assert sp.kernel_calls == sl.kernel_calls
    with pytest.raises(ValueError):
        chunked_spgemm(A, P, plan, backend="nope")


@pytest.mark.parametrize("algorithm", ["knl", "chunk1", "chunk2"])
def test_three_way_backends_agree_random_plans(algorithm):
    """Loop, scan, and Pallas backends on the same random matrices x random
    plans (seeded-parametrize pattern — runs without hypothesis): scan is
    bitwise-equal to loop, Pallas is allclose at matched c_pad (dense
    accumulation reorders the float adds), all three match the dense oracle."""
    rng = np.random.default_rng(23)
    for i, (A, B) in enumerate(csr_pair_cases(n_examples=4, max_dim=14,
                                              seed=29)):
        plan = _random_plan(algorithm, A, B, rng)
        c_pad = spgemm_symbolic_host(A, B).c_pad
        Cl, sl = LOOP[algorithm](A, B, plan, c_pad)
        Cs, ss = SCAN[algorithm](A, B, plan, c_pad)
        Cp, sp = PALLAS[algorithm](A, B, plan, c_pad)
        _assert_same_csr(Cl, Cs)
        ref = spgemm_dense_oracle(A, B)
        assert_close(csr_to_dense(Cp), csr_to_dense(Cl), atol=1e-3,
                     msg=f"case {i}")
        assert_close(csr_to_dense(Cp), ref, atol=1e-3, msg=f"case {i}")
        # same multiply schedule, pallas' own staging event model
        assert sp.kernel_calls == sl.kernel_calls == ss.kernel_calls
        assert len(sp.per_copy_in) >= plan.n_b       # every chunk staged
        assert sp.copy_in_bytes > 0


@pytest.mark.parametrize("algorithm", ["knl", "chunk1", "chunk2"])
def test_pallas_compiles_once_per_geometry(algorithm):
    """<= 2 traces of the pallas core regardless of chunk count, zero on a
    second run with the same padded geometry."""
    A, R, P = multigrid.problem("brick3d", 5)
    ws = spgemm_symbolic_host(A, P)
    n_a, n_b = A.n_rows, P.n_rows
    p_ac = (0, n_a) if algorithm == "knl" else tuple(
        int(v) for v in np.linspace(0, n_a, 5))
    p_b = tuple(int(v) for v in np.linspace(0, n_b, 7))   # 6 B chunks
    plan = ChunkPlan(algorithm, p_ac, p_b, 0.0, 0.0)
    key = f"{algorithm}_pallas"
    before = TRACE_COUNTS[key]
    C, _ = PALLAS[algorithm](A, P, plan, ws.c_pad)
    assert TRACE_COUNTS[key] - before <= 2
    assert_close(csr_to_dense(C), spgemm_dense_oracle(A, P), atol=1e-4)
    mid = TRACE_COUNTS[key]
    PALLAS[algorithm](A, P, plan, ws.c_pad)   # same geometry: cache hit
    assert TRACE_COUNTS[key] == mid


def test_planned_stats_pallas_event_model():
    """The pallas event model: dense slab per (strip, chunk) pair, stationary
    operand staged once per outer step, C_prev fetched once, and — unlike the
    loop/scan model — Chunk2 partials never bounce to slow memory."""
    plan2 = ChunkPlan("chunk2", (0, 4, 8), (0, 3, 6, 9), 0.0, 0.0)
    st2 = planned_stats_pallas(plan2, slab_nbytes=100, a_stage_nbytes=10,
                               c_stage_nbytes=1)
    assert st2.kernel_calls == 6                  # n_ac * n_b
    assert st2.per_copy_in.count(100.0) == 3      # each chunk staged once
    assert st2.per_copy_in.count(10.0) == 6       # strips streamed per chunk
    assert st2.per_copy_in.count(2.0) == 1        # whole C block, one fetch
    assert st2.per_copy_out == [2.0]              # single final writeback
    plan1 = ChunkPlan("chunk1", (0, 4, 8), (0, 3, 6, 9), 0.0, 0.0)
    st1 = planned_stats_pallas(plan1, 100, 10, 1)
    assert st1.kernel_calls == 6
    assert st1.per_copy_in.count(100.0) == 6      # chunks streamed per strip
    assert st1.per_copy_in.count(10.0) == 2       # each strip staged once
    assert st1.per_copy_out == [1.0, 1.0]
    plank = ChunkPlan("knl", (0, 8), (0, 3, 6, 9), 0.0, 0.0)
    stk = planned_stats_pallas(plank, 100, 10, 1)
    assert stk.kernel_calls == 3
    assert stk.per_copy_in.count(100.0) == 3
    with pytest.raises(ValueError):
        planned_stats_pallas(ChunkPlan("nope", (0, 8), (0, 8), 0.0, 0.0),
                             1, 1, 1)


@pytest.mark.parametrize("algorithm", ["knl", "chunk1", "chunk2"])
def test_scan_compiles_once_per_algorithm(algorithm):
    """<= 2 compilations of the chunk loop regardless of the chunk count, and
    zero recompilation on a second run with the same padded geometry."""
    A, R, P = multigrid.problem("brick3d", 5)
    ws = spgemm_symbolic_host(A, P)
    n_a, n_b = A.n_rows, P.n_rows
    p_ac = (0, n_a) if algorithm == "knl" else tuple(
        int(v) for v in np.linspace(0, n_a, 5))
    p_b = tuple(int(v) for v in np.linspace(0, n_b, 7))   # 6 B chunks
    plan = ChunkPlan(algorithm, p_ac, p_b, 0.0, 0.0)
    before_w = TRACE_COUNTS[algorithm]
    before_b = TRACE_COUNTS[f"{algorithm}_body"]
    SCAN[algorithm](A, P, plan, ws.c_pad)
    assert TRACE_COUNTS[algorithm] - before_w <= 2
    assert TRACE_COUNTS[f"{algorithm}_body"] - before_b <= 2
    mid_w = TRACE_COUNTS[algorithm]
    mid_b = TRACE_COUNTS[f"{algorithm}_body"]
    SCAN[algorithm](A, P, plan, ws.c_pad)   # same geometry: cache hit
    assert TRACE_COUNTS[algorithm] == mid_w
    assert TRACE_COUNTS[f"{algorithm}_body"] == mid_b


@pytest.mark.parametrize("algorithm", ["knl", "chunk1", "chunk2"])
def test_batched_heterogeneous_structures(algorithm):
    """Regression: instances differing in sparsity *structure* (nnz,
    max_row_nnz) used to crash csr_stack with 'uniform padded geometry';
    the batch envelope must repad them into one program whose per-instance
    results are bitwise-identical to the unbatched scan executor."""
    rng = np.random.default_rng(5)
    # the original repro: 32x32 at 10% vs 20% density, 2-chunk plan
    As = [random_csr(rng, 32, 32, d) for d in (0.10, 0.20, 0.05)]
    Bs = [random_csr(rng, 32, 32, d) for d in (0.10, 0.20, 0.30)]
    p_ac = (0, 32) if algorithm == "knl" else (0, 13, 32)
    plan = ChunkPlan(algorithm, p_ac, (0, 16, 32), 0.0, 0.0)
    env = batch_envelope(As, Bs, plan)
    for A, B in zip(As, Bs):
        assert env.dominates(instance_envelope(A, B, plan))
    Cs_list, _ = chunked_spgemm_batched(As, Bs, plan)
    assert len(Cs_list) == 3
    for A, B, Cb in zip(As, Bs, Cs_list):
        Ci, _ = chunked_spgemm(A, B, plan, c_pad=env.c_pad)
        _assert_same_csr(Ci, Cb)
        assert_close(csr_to_dense(Cb), spgemm_dense_oracle(A, B), atol=1e-3)


def test_batched_same_structure_unchanged():
    """Same-structure batches must keep the pre-envelope behavior bitwise:
    the batch envelope degenerates to every instance's own geometry."""
    rng = np.random.default_rng(13)
    base_a = (rng.random((20, 16)) < 0.25) * 1.0
    base_b = (rng.random((16, 18)) < 0.25) * 1.0
    As = [csr_from_dense((base_a * rng.standard_normal(base_a.shape))
                         .astype(np.float32)) for _ in range(3)]
    Bs = [csr_from_dense((base_b * rng.standard_normal(base_b.shape))
                         .astype(np.float32)) for _ in range(3)]
    plan = ChunkPlan("knl", (0, 20), (0, 6, 11, 16), 0.0, 0.0)
    env = batch_envelope(As, Bs, plan)
    assert env == instance_envelope(As[0], Bs[0], plan, c_pad=env.c_pad)
    Cs_list, stats = chunked_spgemm_batched(As, Bs, plan)
    for A, B, Cb in zip(As, Bs, Cs_list):
        Cs, ss = chunk_knl_scan(A, B, plan, env.c_pad)
        _assert_same_csr(Cs, Cb)
        assert ss.per_copy_in == stats.per_copy_in
        assert ss.per_copy_out == stats.per_copy_out


def test_batched_rejects_mismatched_shapes_and_conflicting_c_pad():
    rng = np.random.default_rng(3)
    A1, B1 = random_csr(rng, 8, 8, 0.3), random_csr(rng, 8, 8, 0.3)
    A2, B2 = random_csr(rng, 9, 8, 0.3), random_csr(rng, 8, 8, 0.3)
    plan = ChunkPlan("knl", (0, 8), (0, 4, 8), 0.0, 0.0)
    with pytest.raises(ValueError, match="share shapes"):
        chunked_spgemm_batched([A1, A2], [B1, B2], plan)
    env = batch_envelope([A1], [B1], plan)
    with pytest.raises(ValueError, match="c_pad"):
        chunked_spgemm_batched([A1], [B1], plan, c_pad=env.c_pad + 1,
                               envelope=env)
    # an undersized caller envelope (e.g. stale bucket applied to a denser
    # batch) must fail loudly, never silently truncate
    A3, B3 = random_csr(rng, 8, 8, 0.9), random_csr(rng, 8, 8, 0.9)
    with pytest.raises(ValueError):
        chunked_spgemm_batched([A3], [B3], plan, envelope=env)


@pytest.mark.parametrize("algorithm", ["knl", "chunk1", "chunk2"])
def test_batched_matches_per_instance_loop(algorithm):
    """vmapped scan over instances sharing one structure == per-instance loop."""
    rng = np.random.default_rng(11)
    base_a = (rng.random((20, 16)) < 0.25) * 1.0
    base_b = (rng.random((16, 18)) < 0.25) * 1.0
    As, Bs = [], []
    for _ in range(3):
        As.append(csr_from_dense(
            (base_a * rng.standard_normal(base_a.shape)).astype(np.float32)))
        Bs.append(csr_from_dense(
            (base_b * rng.standard_normal(base_b.shape)).astype(np.float32)))
    c_pad = max(spgemm_symbolic_host(A, B).c_pad for A, B in zip(As, Bs))
    p_ac = (0, 20) if algorithm == "knl" else (0, 7, 20)
    plan = ChunkPlan(algorithm, p_ac, (0, 6, 11, 16), 0.0, 0.0)
    Cs_list, stats = chunked_spgemm_batched(As, Bs, plan, c_pad=c_pad)
    assert len(Cs_list) == 3
    for A, B, Cb in zip(As, Bs, Cs_list):
        Cl, sl = LOOP[algorithm](A, B, plan, c_pad)
        _assert_same_csr(Cl, Cb)
        assert sl.per_copy_in == stats.per_copy_in
        assert sl.per_copy_out == stats.per_copy_out


@pytest.mark.parametrize("algorithm", ["knl", "chunk1", "chunk2"])
def test_batched_pallas_heterogeneous_structures(algorithm):
    """The pallas backend serves heterogeneous-structure batches through one
    kernel launch (batch = leading grid dim): oracle-correct per instance,
    O(1) traces per geometry, zero retrace on a repeat batch."""
    rng = np.random.default_rng(5)
    As = [random_csr(rng, 24, 20, d) for d in (0.10, 0.25, 0.05)]
    Bs = [random_csr(rng, 20, 22, d) for d in (0.10, 0.20, 0.30)]
    p_ac = (0, 24) if algorithm == "knl" else (0, 11, 24)
    plan = ChunkPlan(algorithm, p_ac, (0, 7, 14, 20), 0.0, 0.0)
    key = f"{algorithm}_pallas_batched"
    before = TRACE_COUNTS[key]
    out, stats = chunked_spgemm_batched(As, Bs, plan, backend="pallas")
    assert len(out) == 3
    for A, B, Cb in zip(As, Bs, out):
        assert_close(csr_to_dense(Cb), spgemm_dense_oracle(A, B), atol=1e-3)
    assert TRACE_COUNTS[key] - before <= 2
    assert stats.kernel_calls == plan.n_ac * plan.n_b
    mid = TRACE_COUNTS[key]
    chunked_spgemm_batched(As, Bs, plan, backend="pallas")
    assert TRACE_COUNTS[key] == mid
    with pytest.raises(ValueError, match="backend"):
        chunked_spgemm_batched(As, Bs, plan, backend="vmapped")


@pytest.mark.parametrize("backend", ["scan", "sparse", "hash"])
def test_make_batched_cores_isolated_caches(backend):
    """``BackendSpec.make_batched_cores`` builds a *fresh* jitted core set:
    each set owns its compile cache (two sets retrace independently, repeat
    calls within a set don't), results match the default-core path, and
    ``donate=True`` cores stay oracle-correct (the staged accumulator stacks
    they consume are freshly allocated per call)."""
    from repro.core import backend_registry

    spec = backend_registry.get(backend)
    rng = np.random.default_rng(21)
    As = [random_csr(rng, 16, 16, d) for d in (0.2, 0.3)]
    Bs = [random_csr(rng, 16, 16, d) for d in (0.2, 0.3)]
    plan = ChunkPlan("knl", (0, 16), (0, 8, 16), 0.0, 0.0)
    counter = spec.trace_key_batched.format(alg="knl")

    def run(cores):
        Cs, _ = chunked_spgemm_batched(As, Bs, plan, backend=backend,
                                       cores=cores)
        for A, B, C in zip(As, Bs, Cs):
            assert_close(csr_to_dense(C), spgemm_dense_oracle(A, B), atol=1e-3)

    cores_a = spec.make_batched_cores()
    cores_d = spec.make_batched_cores(donate=True)
    assert set(cores_a) == {"knl", "chunk1", "chunk2"}
    before = TRACE_COUNTS[counter]
    run(cores_a)
    assert TRACE_COUNTS[counter] - before == 1   # set A compiles once
    run(cores_a)
    assert TRACE_COUNTS[counter] - before == 1   # ... and stays warm
    run(cores_d)
    assert TRACE_COUNTS[counter] - before == 2   # fresh set: its own cache
    run(cores_d)
    assert TRACE_COUNTS[counter] - before == 2


# ---------------------------------------------------------------------------
# B's ELL tables: built once per call, in place of the CSR chunks
# ---------------------------------------------------------------------------


def _sparse_csr(rng, m, n, nnz, pad=0, col_range=None):
    """An ``m x n`` program CSR with about ``nnz`` entries at random places,
    rows of unequal lengths and some empty. ``col_range(rows)``, where
    given, bounds each entry's column to ``[lo, hi)`` by its row."""
    import scipy.sparse as sps

    r = rng.integers(0, m, nnz)
    c = rng.integers(*(col_range(r) if col_range else (0, n)), size=nnz)
    M = sps.csr_matrix((rng.standard_normal(nnz).astype(np.float32), (r, c)),
                       shape=(m, n))
    M.sum_duplicates()
    M.data[M.data == 0] = 1.0
    return csr_from_scipy_like(M.indptr, M.indices, M.data, M.shape,
                               pad_to=M.nnz + pad)


# (A rows, inner, C cols, A/C strip bounds, B chunk bounds) per sort key:
# the strips of the pair geometry have (65535 + 1) * 65536 = 2**32 keys.
# The B chunks have 5, 8 and 11 rows, padded to 11, so every chunk's tables
# carry -1 slots past its rows' ends and in its padding rows.
KEYED = {
    "packed": (40, 24, 31, (0, 17, 40), (0, 5, 13, 24)),
    "pair": (2 * 65535, 24, 65536, (0, 65535, 2 * 65535), (0, 5, 13, 24)),
}


def _banded(p_ac, p_b):
    """Columns by row for a banded A: the first strip's rows in B's first
    chunk, the other rows in its last, so no entry multiplies the middle
    chunk and each strip misses the other's chunk."""
    def col_range(rows):
        first = rows < p_ac[1]
        return (np.where(first, p_b[0], p_b[-2]),
                np.where(first, p_b[1], p_b[-1]))
    return col_range


@pytest.mark.parametrize("key,banded", [
    ("packed", False), ("pair", False), ("packed", True), ("pair", True)],
    ids=["packed", "pair", "packed-banded", "pair-banded"])
@pytest.mark.parametrize("algorithm", ["knl", "chunk1", "chunk2"])
def test_scan_tables_match_loop_on_both_sort_keys(algorithm, key, banded):
    """Each order of the scan backend, which reads B from tables built once
    per call, gives the loop oracle's CSR bit for bit, with n_ac >= 2 (but
    knl) and n_b = 3 on each sort key; a same-structure batch does too.
    A scattered A multiplies every chunk in every strip; a banded A leaves
    (strip, chunk) steps with nothing to multiply, which the scan cores
    skip (the batched cores select) and the loop runs."""
    m, k, n, p_ac, p_b = KEYED[key]
    rng = np.random.default_rng(len(key) + len(algorithm))
    band = _banded(p_ac, p_b) if banded else None
    A = _sparse_csr(rng, m, k, 160, pad=3, col_range=band)
    B = _sparse_csr(rng, k, n, 90, pad=2)
    if algorithm == "knl":
        p_ac = (0, m)
    plan = ChunkPlan(algorithm, p_ac, p_b, 0.0, 0.0)
    live = live_pairs(A, p_ac, p_b)
    assert live.all() if band is None else not live.all()
    strip_rows = max(e - s for s, e in zip(p_ac[:-1], p_ac[1:]))
    assert key_layout(strip_rows, n) == key
    c_pad = spgemm_symbolic_host(A, B).c_pad
    Cl, sl = LOOP[algorithm](A, B, plan, c_pad)
    Cs, ss = SCAN[algorithm](A, B, plan, c_pad)
    _assert_same_csr(Cl, Cs)
    assert sl.per_copy_in == ss.per_copy_in
    assert sl.per_copy_out == ss.per_copy_out

    # the same structure with other values, as one batch
    def revalue(M, seed):
        data = np.random.default_rng(seed).standard_normal(M.nnz_pad)
        return CSR(M.indptr, M.indices, jnp.asarray(data, jnp.float32)
                   * (jnp.arange(M.nnz_pad) < M.indptr[-1]), M.shape,
                   M.max_row_nnz)

    As, Bs = [A, revalue(A, 1)], [B, revalue(B, 2)]
    env = batch_envelope(As, Bs, plan, c_pad=c_pad)
    Cb_list, _ = chunked_spgemm_batched(As, Bs, plan, envelope=env)
    for Ai, Bi, Cb in zip(As, Bs, Cb_list):
        _assert_same_csr(LOOP[algorithm](Ai, Bi, plan, c_pad)[0], Cb)


def _chunk2_tabulating_every_step(As, Bs, r0s, r1s, C0s, c_pad):
    """The chunk2 core as it was before its tables left the strip loop:
    every strip step tabulates the chunk again (``spgemm_ranged_impl``)."""
    def outer(Cs, x):
        Bc, r0, r1 = x

        def inner(carry, y):
            Ai, Ci = y
            return carry, spgemm_ranged_impl(Ai, Bc, r0, r1, Ci, c_pad)

        return lax.scan(inner, None, (As, Cs))[1], None

    return lax.scan(outer, C0s, (Bs, r0s, r1s))[0]


def test_chunk2_tables_live_only_inside_the_core(monkeypatch):
    """While the chunk2 core runs, the executor holds B's chunks and A's
    strips only as the stacks the core takes, and no table: no staged
    strip or chunk and no device array of a table's size is alive when the
    core is called, nor a table after it returns. The compiled core needs
    no more temporaries than one that tabulates in every strip step."""
    from repro.core import chunk_stream

    rng = np.random.default_rng(17)
    A, B = random_csr(rng, 30, 26, 0.2), random_csr(rng, 26, 22, 0.25)
    plan = ChunkPlan("chunk2", (0, 9, 20, 30), (0, 8, 26), 0.0, 0.0)
    c_pad = spgemm_symbolic_host(A, B).c_pad
    refs, seen = [], []

    def watched(split):
        def pieces(*args, **kwargs):
            out = split(*args, **kwargs)
            refs.extend(weakref.ref(x) for M in out
                        for x in (M.indptr, M.indices, M.data))
            return out
        return pieces

    core = chunk_stream._chunk2_scan

    def watched_core(As, Bs, r0s, r1s, C0s, c_pad):
        gc.collect()
        seen.append(([r() is None for r in refs], Bs,
                     {x.size for x in jax.live_arrays()}))
        hoisted, per_step = (
            jax.jit(partial(f, c_pad=c_pad)).lower(As, Bs, r0s, r1s, C0s)
            .compile().memory_analysis().temp_size_in_bytes
            for f in (chunk_stream._chunk2_scan_impl,
                      _chunk2_tabulating_every_step))
        assert hoisted <= per_step
        return core(As, Bs, r0s, r1s, C0s, c_pad)

    for name in ("a_strips", "b_chunks"):
        monkeypatch.setattr(chunk_stream, name,
                            watched(getattr(chunk_stream, name)))
    monkeypatch.setattr(chunk_stream, "_chunk2_scan", watched_core)
    C, _ = chunk_gpu2_scan(A, B, plan, c_pad)
    (dropped, Bs, live), = seen
    assert len(dropped) == 3 * (plan.n_ac + plan.n_b) and all(dropped)
    table = Bs.max_row_nnz * Bs.n_rows
    assert table not in {Bs.indices.shape[-1], Bs.indptr.shape[-1]}
    assert table not in live
    del Bs, seen
    gc.collect()
    assert table not in {x.size for x in jax.live_arrays()}
    _assert_same_csr(chunk_gpu2(A, B, plan, c_pad)[0], C)


# ---------------------------------------------------------------------------
# the audit stages what the executor launches
# ---------------------------------------------------------------------------


def _audited_backends():
    from repro.core import backend_registry

    return [s.name for s in backend_registry.specs() if s.supports_audit]


@pytest.mark.parametrize("algorithm", ["knl", "chunk1", "chunk2"])
@pytest.mark.parametrize("backend", _audited_backends())
def test_audit_target_is_the_executors_launch(backend, algorithm):
    """A backend's ``audit_trace`` at the instance envelope stages exactly
    the launch its executor runs: after one executor call, calling the
    audit target hits the core's jit cache (its ``TRACE_COUNTS`` key does
    not move), so the static verifier traces the program that runs. Where
    the target carries its staging's copy events, they are the executor's."""
    from repro.core import backend_registry
    from repro.core.symbolic import strip_output_caps

    spec = backend_registry.get(backend)
    rng = np.random.default_rng(31)
    A = random_csr(rng, 26, 22, 0.2, pad_extra=3)
    B = random_csr(rng, 22, 19, 0.25)
    p_ac = (0, A.n_rows) if algorithm == "knl" else (0, 9, 26)
    plan = ChunkPlan(algorithm, p_ac, (0, 8, 15, 22), 0.0, 0.0)
    c_pad = strip_output_caps(A, B, plan.p_ac).c_pad
    key = spec.trace_key.format(alg=algorithm)

    _, stats = spec.executors[algorithm](A, B, plan, c_pad)
    before = TRACE_COUNTS[key]
    block = spec.block_size if spec.needs_block_caps else None
    env = instance_envelope(A, B, plan, c_pad, block_size=block)
    target = spec.audit_trace(A, B, plan, c_pad, env)
    jax.block_until_ready(target.fn(*target.args))
    assert TRACE_COUNTS[key] == before
    if "stats" in target.meta:
        assert target.meta["stats"].per_copy_in == stats.per_copy_in
        assert target.meta["stats"].per_copy_out == stats.per_copy_out
