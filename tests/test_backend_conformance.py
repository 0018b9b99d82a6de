"""Cross-backend differential conformance suite.

One seeded geometry matrix — empty rows, skewed rows, all-zero chunks,
single-column B, all-zero B, wide-but-sparse outputs — runs through **every**
``chunked_spgemm`` backend and is asserted allclose to the loop oracle at
matched ``c_pad`` (scan additionally bitwise, which ``assert_close`` at tiny
atol effectively witnesses via identical float schedules). The backend lists
are **derived from the registry** (``repro.core.backend_registry``): a new
backend's one registration call enrolls it in the whole matrix — correctness
guarantees come from this suite, not per-backend ad-hoc tests — and the
registry-completeness test pins the expected roster so an accidentally
dropped registration fails here, not in production dispatch.

The trace-count section pins the *exact* ``TRACE_COUNTS`` deltas of every
backend across repeat / same-envelope / new-envelope calls, so a silent
retrace regression (a geometry-dependent Python value smuggled into a jitted
signature, a cache-busting non-hashable static) fails the fast lane instead
of showing up as a serving-latency cliff.

Determinism: every case is seeded and the matrix is pure-parametrize, so two
runs of this file must produce identical reports — CI runs it twice and
diffs (the determinism job in .github/workflows/ci.yml).
"""

import numpy as np
import pytest

from repro.core import backend_registry
from repro.core.chunk_stream import TRACE_COUNTS, chunked_spgemm_batched
from repro.core.chunking import (
    batch_envelope, chunked_spgemm, default_c_pad, instance_envelope,
)
from repro.core.kkmem import spgemm_dense_oracle
from repro.core.planner import ChunkPlan, select_accumulator_backend
from repro.core.symbolic import spgemm_structure_host, strip_output_caps
from repro.sparse.csr import csr_from_dense, csr_to_dense
from repro.serve.spgemm_service import SpGEMMService
from conftest import assert_close, random_csr, random_dense

# registry-derived backend matrix: registering a BackendSpec enrolls the
# backend in every test below; nothing is named by hand
BACKENDS = [*backend_registry.all_backends(), "auto"]
BATCHED_BACKENDS = [*backend_registry.batched_backends(), "auto"]
ALGORITHMS = ["knl", "chunk1", "chunk2"]


def _block_size_for(backend: str) -> int | None:
    """The envelope block edge a backend needs (None for non-block backends
    and for auto, whose resolve under uncapped envelopes never picks one)."""
    if backend == "auto":
        return None
    spec = backend_registry.get(backend)
    return spec.block_size if spec.needs_block_caps else None


def test_registry_completeness():
    """The registration contract: the expected roster in priority order,
    every spec covering every algorithm, batched + trace-keyed except the
    loop oracle, byte models on every accumulator, a block edge on every
    block backend. A dropped or malformed registration fails here, not as a
    cryptic dispatch error."""
    specs = backend_registry.specs()
    assert [s.name for s in specs] == ["loop", "scan", "pallas", "sparse",
                                       "hash", "bsr"]
    for s in specs:
        assert set(backend_registry.ALGORITHMS) <= set(s.executors), s.name
        if s.name == "loop":
            assert not s.supports_batched
        else:
            assert s.supports_batched, s.name
            assert s.trace_key and s.trace_key_batched, s.name
        if s.is_accumulator:
            assert s.byte_model is not None, s.name
        if s.needs_block_caps:
            assert s.block_size, s.name
    assert backend_registry.batched_backends() == ("scan", "pallas", "sparse",
                                                   "hash", "bsr")
    assert tuple(s.name for s in backend_registry.accumulator_specs()) == (
        "pallas", "sparse", "hash", "bsr")
    assert tuple(s.name for s in backend_registry.auto_specs()) == (
        "pallas", "bsr")
    with pytest.raises(ValueError, match="unknown backend"):
        backend_registry.get("nope")


def _thirds(n: int) -> tuple:
    if n < 3:
        return (0, n)
    return (0, n // 3, 2 * n // 3, n)


def _case_empty_rows(rng):
    """A with structurally empty rows at both ends and mid-strip."""
    a = random_dense(rng, 14, 11, 0.4)
    a[0] = a[5] = a[6] = a[13] = 0.0
    return csr_from_dense(a), random_csr(rng, 11, 9, 0.3)


def _case_skewed_rows(rng):
    """One fully dense A row among near-empty ones (skewed staging caps)."""
    a = random_dense(rng, 12, 16, 0.05)
    a[7] = rng.standard_normal(16).astype(np.float32)
    return csr_from_dense(a), random_csr(rng, 16, 10, 0.3)


def _case_all_zero_chunk(rng):
    """The middle B chunk of the thirds partition is structurally empty."""
    b = random_dense(rng, 15, 8, 0.4)
    b[5:10] = 0.0
    return random_csr(rng, 10, 15, 0.3), csr_from_dense(b)


def _case_single_col_b(rng):
    return random_csr(rng, 9, 12, 0.4), random_csr(rng, 12, 1, 0.5)


def _case_all_zero_b(rng):
    """C is structurally empty (every backend must produce an all-zero C)."""
    return random_csr(rng, 8, 10, 0.4), csr_from_dense(np.zeros((10, 6),
                                                                np.float32))


def _case_wide_sparse_output(rng):
    """Wide C at low density — the sparse backend's home turf."""
    return random_csr(rng, 10, 12, 0.12), random_csr(rng, 12, 48, 0.04)


def _case_duplicate_heavy(rng):
    """Every A entry hits one of three hot B rows: duplicate (row, col)
    products pile onto the same hash slots and neighboring keys chain off
    each other — the linear-probe collision stressor. The thirds partition
    of B also leaves chunks 1 and 2 structurally empty."""
    a = np.zeros((12, 9), np.float32)
    a[:, :3] = random_dense(rng, 12, 3, 0.9)
    b = np.zeros((9, 10), np.float32)
    b[:3] = random_dense(rng, 3, 10, 0.8)
    return csr_from_dense(a), csr_from_dense(b)


def _case_dense_row(rng):
    """One fully dense C row: ``c_max_row_nnz == n_cols``, so the hash
    table's occupancy hits its exact capacity bound (the table-full
    boundary — every probe chain in that row terminates only because the
    symbolic bound is exact)."""
    a = random_dense(rng, 10, 8, 0.2)
    a[4] = rng.standard_normal(8).astype(np.float32)     # dense A row
    b = random_dense(rng, 8, 12, 0.3)
    b[0] = rng.standard_normal(12).astype(np.float32)    # dense B row
    A, B = csr_from_dense(a), csr_from_dense(b)
    # the case exists for this boundary; pin it so a seed drift can't
    # silently soften the geometry
    assert spgemm_structure_host(A, B).c_max_row_nnz == B.n_cols
    return A, B


CASES = {
    "empty_rows": (_case_empty_rows, 101),
    "skewed_rows": (_case_skewed_rows, 102),
    "all_zero_chunk": (_case_all_zero_chunk, 103),
    "single_col_b": (_case_single_col_b, 104),
    "all_zero_b": (_case_all_zero_b, 105),
    "wide_sparse_output": (_case_wide_sparse_output, 106),
    "duplicate_heavy": (_case_duplicate_heavy, 107),
    "dense_row": (_case_dense_row, 108),
}


def _plan(algorithm: str, A, B) -> ChunkPlan:
    p_ac = (0, A.n_rows) if algorithm == "knl" else _thirds(A.n_rows)
    return ChunkPlan(algorithm, p_ac, _thirds(B.n_rows), 0.0, 0.0)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("algorithm", ALGORITHMS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_backend_matches_loop_oracle(case, algorithm, backend):
    build, seed = CASES[case]
    A, B = build(np.random.default_rng(seed))
    plan = _plan(algorithm, A, B)
    c_pad = default_c_pad(A, B, plan)
    Cl, sl = chunked_spgemm(A, B, plan, c_pad, backend="loop")
    Cb, sb = chunked_spgemm(A, B, plan, c_pad, backend=backend)
    assert Cb.shape == (A.n_rows, B.n_cols)
    assert_close(csr_to_dense(Cb), csr_to_dense(Cl), atol=1e-4,
                 msg=f"{case}/{algorithm}/{backend} vs loop oracle")
    assert_close(csr_to_dense(Cl), spgemm_dense_oracle(A, B), atol=1e-4)
    # every backend runs the plan's exact multiply schedule
    assert sb.kernel_calls == sl.kernel_calls
    assert len(sb.per_copy_in) > 0 and sb.copy_in_bytes > 0


@pytest.mark.parametrize("backend", BATCHED_BACKENDS)
@pytest.mark.parametrize("algorithm", ALGORITHMS)
def test_batched_hetero_conformance(algorithm, backend):
    """Heterogeneous-structure batches (mixed densities plus one structurally
    empty A instance) through every batched backend, against the
    per-instance loop oracle at the batch envelope's c_pad."""
    rng = np.random.default_rng(207)
    As = [random_csr(rng, 18, 15, d) for d in (0.10, 0.30)]
    As.append(csr_from_dense(np.zeros((18, 15), np.float32)))
    Bs = [random_csr(rng, 15, 13, d) for d in (0.15, 0.25, 0.35)]
    plan = _plan(algorithm, As[0], Bs[0])
    env = batch_envelope(As, Bs, plan)
    out, _ = chunked_spgemm_batched(As, Bs, plan, backend=backend)
    assert len(out) == len(As)
    for A, B, Cb in zip(As, Bs, out):
        Cl, _ = chunked_spgemm(A, B, plan, c_pad=env.c_pad, backend="loop")
        assert_close(csr_to_dense(Cb), csr_to_dense(Cl), atol=1e-4,
                     msg=f"hetero/{algorithm}/{backend}")


@pytest.mark.parametrize("backend", BATCHED_BACKENDS)
def test_service_conformance(backend):
    """The full serving path (bucketing, envelope quantization, microbatch
    padding) stays oracle-correct for every backend."""
    rng = np.random.default_rng(303)
    As = [random_csr(rng, 12, 10, d) for d in (0.1, 0.2, 0.3, 0.15)]
    Bs = [random_csr(rng, 10, 8, d) for d in (0.2, 0.3, 0.1, 0.25)]
    svc = SpGEMMService(fast_limit_bytes=1500.0, backend=backend, max_batch=2)
    ids = [svc.submit(A, B) for A, B in zip(As, Bs)]
    responses = svc.flush()
    assert [r.req_id for r in responses] == ids
    for r, A, B in zip(responses, As, Bs):
        assert_close(csr_to_dense(r.C), spgemm_dense_oracle(A, B), atol=1e-4,
                     msg=f"service/{backend}")


# ---------------------------------------------------------------------------
# trace-count regression: exact deltas per backend
# ---------------------------------------------------------------------------

# TRACE_COUNTS key of each backend's jitted core ({alg} formats in) — pulled
# from the registry, so a registration's trace keys are what gets pinned
TRACE_KEYS = {s.name: s.trace_key for s in backend_registry.specs()
              if s.trace_key}
TRACE_KEYS_BATCHED = {s.name: s.trace_key_batched
                      for s in backend_registry.specs()
                      if s.trace_key_batched}


def _trace_key(backend: str, algorithm: str, plan, env) -> str:
    """The TRACE_COUNTS key a chunked_spgemm call will bump. ``auto`` is
    resolved the way the dispatcher resolves it — through the planner byte
    models — so the pin also witnesses that auto's resolution is the
    deterministic function of (plan, envelope) it claims to be."""
    if backend == "auto":
        backend = select_accumulator_backend(plan, env)
    return TRACE_KEYS[backend].format(alg=algorithm)


def _trace_geometry(rng, m=21, k=19, n=13, da=0.25, db=0.3):
    """Sizes unique to this module so the session-global jit cache cannot
    have seen the padded geometry before the first measured call."""
    return random_csr(rng, m, k, da), random_csr(rng, k, n, db)


@pytest.mark.parametrize("backend", [*TRACE_KEYS, "auto"])
@pytest.mark.parametrize("algorithm", ALGORITHMS)
def test_trace_counts_exact(algorithm, backend):
    """first call = exactly one trace of the backend core; repeat and
    same-envelope (new values, same padded geometry) = exactly zero; a new
    envelope = exactly one more."""
    # deterministic per-combination seed (str hashing is process-salted)
    seed = 1000 + 10 * ALGORITHMS.index(algorithm) + BACKENDS.index(backend)
    rng = np.random.default_rng(seed)
    A1, B1 = _trace_geometry(rng)
    plan = _plan(algorithm, A1, B1)
    c_pad = default_c_pad(A1, B1, plan)
    env1 = instance_envelope(A1, B1, plan, c_pad=c_pad)
    key = _trace_key(backend, algorithm, plan, env1)

    before = TRACE_COUNTS[key]
    chunked_spgemm(A1, B1, plan, c_pad, backend=backend)
    assert TRACE_COUNTS[key] - before == 1, "first call must trace once"

    mid = TRACE_COUNTS[key]
    chunked_spgemm(A1, B1, plan, c_pad, backend=backend)     # repeat
    assert TRACE_COUNTS[key] == mid, "repeat call must not retrace"

    # same envelope, different values: rebuild with the same seed's structure
    A1b = csr_from_dense(np.asarray(csr_to_dense(A1)) * 2.0)
    B1b = csr_from_dense(np.asarray(csr_to_dense(B1)) * 0.5)
    assert instance_envelope(A1b, B1b, plan, c_pad=c_pad) == env1
    chunked_spgemm(A1b, B1b, plan, c_pad, backend=backend)
    assert TRACE_COUNTS[key] == mid, "same-envelope call must not retrace"

    # a genuinely new padded geometry: exactly one more trace (of the core
    # auto resolves to *for that geometry* — the winner may change with it)
    A2, B2 = _trace_geometry(rng, m=23, k=20, n=11, da=0.4, db=0.35)
    plan2 = _plan(algorithm, A2, B2)
    c_pad2 = default_c_pad(A2, B2, plan2)
    key2 = _trace_key(backend, algorithm, plan2,
                      instance_envelope(A2, B2, plan2, c_pad=c_pad2))
    mid2 = TRACE_COUNTS[key2]
    chunked_spgemm(A2, B2, plan2, c_pad2, backend=backend)
    assert TRACE_COUNTS[key2] == mid2 + 1, \
        "new envelope must trace exactly once"


@pytest.mark.parametrize("backend", BATCHED_BACKENDS)
def test_trace_counts_exact_batched(backend):
    """Batched cores: one trace per (envelope, batch width), zero on repeat
    and on new same-envelope instances, one more when the envelope grows."""
    algorithm = "chunk1"
    rng = np.random.default_rng(2000 + BACKENDS.index(backend))
    As = [random_csr(rng, 22, 17, 0.2) for _ in range(2)]
    Bs = [random_csr(rng, 17, 12, 0.25) for _ in range(2)]
    plan = _plan(algorithm, As[0], Bs[0])
    block = _block_size_for(backend)
    env = batch_envelope(As, Bs, plan, block_size=block)
    resolved = (select_accumulator_backend(plan, env) if backend == "auto"
                else backend)
    key = TRACE_KEYS_BATCHED[resolved].format(alg=algorithm)

    before = TRACE_COUNTS[key]
    chunked_spgemm_batched(As, Bs, plan, envelope=env, backend=backend)
    assert TRACE_COUNTS[key] - before == 1

    mid = TRACE_COUNTS[key]
    chunked_spgemm_batched(As, Bs, plan, envelope=env, backend=backend)
    assert TRACE_COUNTS[key] == mid

    # fresh instances, same bucket envelope: a structural *subset* of the
    # originals (every other entry dropped, values rescaled), so domination
    # holds by construction for any seed
    def subset(m):
        d = np.asarray(csr_to_dense(m))
        keep = np.arange(d.size).reshape(d.shape) % 2 == 0
        return csr_from_dense((d * keep * 1.5).astype(d.dtype))

    As2 = [subset(A) for A in As]
    Bs2 = [subset(B) for B in Bs]
    assert env.dominates(batch_envelope(As2, Bs2, plan))
    chunked_spgemm_batched(As2, Bs2, plan, envelope=env, backend=backend)
    assert TRACE_COUNTS[key] == mid

    # grown envelope (denser batch): exactly one more compile, of the core
    # auto resolves to under the grown envelope
    As3 = [random_csr(rng, 22, 17, 0.5) for _ in range(2)]
    Bs3 = [random_csr(rng, 17, 12, 0.5) for _ in range(2)]
    env3 = env.union(batch_envelope(As3, Bs3, plan, block_size=block))
    resolved3 = (select_accumulator_backend(plan, env3) if backend == "auto"
                 else backend)
    key3 = TRACE_KEYS_BATCHED[resolved3].format(alg=algorithm)
    mid3 = TRACE_COUNTS[key3]
    chunked_spgemm_batched(As3, Bs3, plan, envelope=env3, backend=backend)
    assert TRACE_COUNTS[key3] == mid3 + 1


# ---------------------------------------------------------------------------
# capacity-overflow regression: under-capped launches fail loudly
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["sparse", "hash"])
def test_undercapped_c_pad_raises(backend):
    """A c_pad below the realized strip output nnz must be a planner-level
    ValueError naming the geometry — both sparse-output kernels would
    otherwise silently drop the overflow into their bounded scratch (the
    ESC scatter's drop bucket, a full hash table)."""
    rng = np.random.default_rng(401)
    A = random_csr(rng, 12, 10, 0.4)
    B = random_csr(rng, 10, 9, 0.4)
    plan = _plan("chunk1", A, B)
    caps = strip_output_caps(A, B, plan.p_ac)
    bad = max(caps.strip_nnz) - 1
    assert bad > 0
    with pytest.raises(ValueError, match="exceeds the accumulator capacity"):
        chunked_spgemm(A, B, plan, c_pad=bad, backend=backend)
    # the exact symbolic capacity itself (unrounded) must be accepted
    C, _ = chunked_spgemm(A, B, plan, c_pad=max(caps.strip_nnz),
                          backend=backend)
    assert_close(csr_to_dense(C), spgemm_dense_oracle(A, B), atol=1e-4)


@pytest.mark.parametrize("backend", ["sparse", "hash"])
def test_undercapped_batched_envelope_raises(backend):
    """The batched path validates every instance against the shared envelope:
    a caller-built envelope whose c_pad undercuts one instance's realized
    output must raise and name the offending instance."""
    import dataclasses

    rng = np.random.default_rng(402)
    As = [random_csr(rng, 12, 10, d) for d in (0.15, 0.45)]
    Bs = [random_csr(rng, 10, 9, d) for d in (0.2, 0.45)]
    plan = _plan("chunk1", As[0], Bs[0])
    env = batch_envelope(As, Bs, plan)
    caps1 = strip_output_caps(As[1], Bs[1], plan.p_ac)
    bad_env = dataclasses.replace(env, c_pad=max(caps1.strip_nnz) - 1)
    with pytest.raises(ValueError, match="batch instance 1"):
        chunked_spgemm_batched(As, Bs, plan, envelope=bad_env,
                               backend=backend)


def test_undercapped_hash_table_raises():
    """The hash-specific cap: an envelope whose c_max_row_nnz undersizes the
    per-row table relative to an instance's realized densest C row must trip
    the row-cap branch of check_output_caps (only reachable batched — the
    unbatched path sizes the table from the exact caps it checks against)."""
    import dataclasses

    rng = np.random.default_rng(403)
    As = [random_csr(rng, 12, 10, 0.5)]
    Bs = [random_csr(rng, 10, 9, 0.5)]
    plan = _plan("chunk1", As[0], Bs[0])
    env = batch_envelope(As, Bs, plan)
    exact = strip_output_caps(As[0], Bs[0], plan.p_ac).c_max_row_nnz
    assert exact > 2    # dense draw: the densest C row has several entries
    bad_env = dataclasses.replace(env, c_max_row_nnz=2)   # 2-slot tables
    with pytest.raises(ValueError, match="hash-table capacity"):
        chunked_spgemm_batched(As, Bs, plan, envelope=bad_env,
                               backend="hash")


# ---------------------------------------------------------------------------
# auto dispatch: provably the minimum-resident-bytes accumulator
# ---------------------------------------------------------------------------


def _auto_geometries(rng):
    """Three geometries that stress different byte-model terms: dense narrow
    C, wide sparse C with a fat product expansion (hash tables beat the ESC
    workspace), near-diagonal tall operands (tiny ESC expand stream beats
    the row-count-scaled hash tables)."""
    dense_a = csr_from_dense(random_dense(rng, 24, 16, 0.6))
    dense_b = csr_from_dense(random_dense(rng, 16, 12, 0.6))
    dense_plan = ChunkPlan("chunk1", (0, 12, 24), (0, 8, 16), 0.0, 0.0)

    wide_a = csr_from_dense(random_dense(rng, 32, 40, 0.25))
    wide_b = csr_from_dense(random_dense(rng, 40, 512, 0.02))
    wide_plan = ChunkPlan("chunk1", (0, 16, 32), (0, 14, 27, 40), 0.0, 0.0)

    m, k, n = 192, 64, 512
    a = np.zeros((m, k), np.float32)
    a[np.arange(m), np.arange(m) % k] = 1.0
    a[0, :8] = 1.0                      # one denser row: c_max_row_nnz ~ 8
    b = np.zeros((k, n), np.float32)
    b[np.arange(k), (np.arange(k) * 7) % n] = 1.0
    diag_a, diag_b = csr_from_dense(a), csr_from_dense(b)
    diag_plan = ChunkPlan("chunk1", (0, 96, 192), (0, 32, 64), 0.0, 0.0)

    return [("dense_narrow", dense_a, dense_b, dense_plan),
            ("wide_sparse", wide_a, wide_b, wide_plan),
            ("tall_diag", diag_a, diag_b, diag_plan)]


def test_auto_selects_min_resident_bytes_backend():
    """Acceptance: on three geometries, ``backend="auto"`` (i) resolves to
    the argmin of the planner models of the accumulators the TPU compiler
    accepts, (ii) runs exactly that backend's core (trace-counted), and
    (iii) stays oracle-correct. The byte models alone would pick the CSR
    accumulators (``sparse``, ``hash``) on these geometries; their
    ``mosaic_refusal`` is what keeps ``auto`` off them."""
    from repro.core.planner import backend_fast_models

    lowerable = {s.name for s in backend_registry.auto_specs()}
    assert lowerable == {"pallas", "bsr"}
    rng = np.random.default_rng(500)
    winners, byte_winners = {}, {}
    for name, A, B, plan in _auto_geometries(rng):
        c_pad = default_c_pad(A, B, plan)
        env = instance_envelope(A, B, plan, c_pad=c_pad)
        models = backend_fast_models(plan, env)
        pick = select_accumulator_backend(plan, env)
        assert pick in lowerable, name
        assert models[pick].fast_bytes_needed == min(
            models[b].fast_bytes_needed for b in lowerable), name
        byte_winners[name] = min(models,
                                 key=lambda b: models[b].fast_bytes_needed)
        key = TRACE_KEYS[pick].format(alg=plan.algorithm)
        before = TRACE_COUNTS[key]
        C, _ = chunked_spgemm(A, B, plan, c_pad, backend="auto")
        # geometries here are unique to this test, so the resolved core must
        # trace exactly once — auto provably ran the argmin backend
        assert TRACE_COUNTS[key] == before + 1, \
            f"{name}: auto did not run the {pick} core"
        assert_close(csr_to_dense(C), spgemm_dense_oracle(A, B), atol=1e-4,
                     msg=f"auto/{name}")
        winners[name] = pick
    # uncapped envelopes price bsr at infinity, so the dense slab runs
    assert set(winners.values()) == {"pallas"}, winners
    assert set(byte_winners.values()) == {"sparse", "hash"}, byte_winners


# ---------------------------------------------------------------------------
# nightly: larger hash sweep (geometry grid too big for the fast lane)
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("algorithm", ALGORITHMS)
def test_hash_backend_large_sweep(algorithm):
    """Bigger geometries x densities through the hash backend — the probe
    chains and table occupancies the fast-lane cases only sample. Nightly:
    the serial insert loops make these seconds-per-case."""
    rng = np.random.default_rng(600 + ALGORITHMS.index(algorithm))
    for m, k, n, da, db in ((48, 40, 96, 0.15, 0.1), (64, 48, 160, 0.1, 0.05),
                            (40, 56, 64, 0.3, 0.2)):
        A, B = random_csr(rng, m, k, da), random_csr(rng, k, n, db)
        plan = _plan(algorithm, A, B)
        c_pad = default_c_pad(A, B, plan)
        C, _ = chunked_spgemm(A, B, plan, c_pad, backend="hash")
        assert_close(csr_to_dense(C), spgemm_dense_oracle(A, B), atol=1e-4,
                     msg=f"hash sweep {m}x{k}x{n}/{algorithm}")
