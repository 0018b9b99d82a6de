"""Differential + compile-accounting tests for the fused two-hop pipeline.

Three contracts from the pipeline work:

* **Galerkin triple product** — ``C = R x (A x P)`` through the pipeline
  executor equals the dense oracle on all four multigrid ``PROBLEMS``,
  through the scan, sparse (ESC) and hash chunked backends, on the resident
  and the forced-spill paths; and the resident/spill answers agree with
  each other bitwise in structure (the composed symbolic phase is exact, so
  chunking must never change C's pattern).
* **Masked triangle counts** — the fused masked path equals
  ``count_triangles_dense`` on the three bench graph classes.
* **Compile accounting** — one envelope, one compile: a second identical
  pipeline (or masked triangle) run adds zero ``TRACE_COUNTS`` deltas and
  returns bitwise-identical results.
"""

import numpy as np
import pytest
import scipy.sparse as sp

from repro.core.chunk_stream import TRACE_COUNTS
from repro.core.kkmem import spgemm_dense_oracle
from repro.core.memory_model import P100, TPU_V5E
from repro.core.pipeline_spgemm import pipeline_spgemm
from repro.core.planner import plan_knl, plan_pipeline
from repro.core.symbolic import masked_output_caps, pipeline_output_caps
from repro.core.triangle import count_triangles, count_triangles_dense
from repro.sparse import graphs, multigrid
from repro.sparse.csr import csr_from_scipy_like, csr_to_dense

SIZES = {"laplace3d": 4, "bigstar2d": 8, "brick3d": 4, "elasticity": 3}

GRAPHS = {
    "g500_s7": lambda: graphs.rmat(7, 8, seed=1),
    "social_powerlaw": lambda: graphs.powerlaw(256, 8, seed=2),
    "web_like": lambda: graphs.rmat(7, 4, a=0.45, b=0.25, c=0.15, seed=3),
}


def _dense_rap(A, R, P):
    return np.asarray(csr_to_dense(R)) @ np.asarray(spgemm_dense_oracle(A, P))


def _tight_limit(A, P, R, frac):
    return float(A.nbytes() + P.nbytes() + R.nbytes()) * frac


@pytest.mark.parametrize("backend", ["scan", "sparse", "hash"])
@pytest.mark.parametrize("name", multigrid.PROBLEMS)
def test_pipeline_matches_dense_oracle(name, backend):
    """R x (A x P) on every problem, default (ample) fast budget."""
    A, R, P = multigrid.problem(name, SIZES[name])
    C, stats = pipeline_spgemm(A, P, R, system=P100, backend=backend)
    np.testing.assert_allclose(np.asarray(csr_to_dense(C)),
                               _dense_rap(A, R, P), atol=1e-4, rtol=1e-5)
    assert stats.spilled is (not stats.plan.t_resident)


@pytest.mark.parametrize("backend", ["scan", "sparse", "hash"])
def test_pipeline_chunked_regime_matches_dense_oracle(backend):
    """A fast budget tight enough to force chunked hops (and possibly a
    spilled intermediate) must not change the answer."""
    A, R, P = multigrid.problem("laplace3d", SIZES["laplace3d"])
    limit = _tight_limit(A, P, R, 0.25)
    C, stats = pipeline_spgemm(A, P, R, system=P100,
                               fast_limit_bytes=limit, backend=backend)
    assert "whole_fast" not in (stats.plan.plan1.algorithm,
                                stats.plan.plan2.algorithm)
    np.testing.assert_allclose(np.asarray(csr_to_dense(C)),
                               _dense_rap(A, R, P), atol=1e-4, rtol=1e-5)


def test_pipeline_resident_and_spill_same_structure():
    """The composed symbolic phase is exact, so C's pattern is an invariant
    of the geometry — the resident and spill paths must emit bitwise the
    same structure (values agree to accumulation-order tolerance)."""
    A, R, P = multigrid.problem("bigstar2d", SIZES["bigstar2d"])
    C_ample, s_ample = pipeline_spgemm(A, P, R, system=P100,
                                       backend="sparse")
    C_tight, s_tight = pipeline_spgemm(
        A, P, R, system=P100,
        fast_limit_bytes=_tight_limit(A, P, R, 0.25), backend="sparse")
    assert s_ample.plan.t_resident and not s_tight.plan.t_resident
    np.testing.assert_array_equal(np.asarray(C_ample.indptr),
                                  np.asarray(C_tight.indptr))
    nnz = int(np.asarray(C_ample.indptr)[-1])
    np.testing.assert_array_equal(np.asarray(C_ample.indices)[:nnz],
                                  np.asarray(C_tight.indices)[:nnz])
    np.testing.assert_allclose(np.asarray(C_ample.data)[:nnz],
                               np.asarray(C_tight.data)[:nnz],
                               atol=1e-4, rtol=1e-5)


def test_pipeline_spill_path_reports_spill_traffic():
    A, R, P = multigrid.problem("laplace3d", SIZES["laplace3d"])
    _, stats = pipeline_spgemm(A, P, R, system=P100,
                               fast_limit_bytes=_tight_limit(A, P, R, 0.2),
                               backend="sparse")
    if stats.spilled:
        assert stats.spill_bytes > 0
        assert stats.copy_bytes > stats.hop1.copy_bytes + stats.hop2.copy_bytes
    else:
        assert stats.spill_bytes == 0.0


def test_pipeline_requires_plan_or_system():
    A, R, P = multigrid.problem("laplace3d", SIZES["laplace3d"])
    with pytest.raises(ValueError, match="PipelinePlan or"):
        pipeline_spgemm(A, P, R)


@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_masked_triangle_count_matches_dense(name):
    L = graphs.lower_triangular_degree_sorted(GRAPHS[name]())
    assert float(count_triangles(L)) == float(count_triangles_dense(L))


def test_pipeline_compiles_once_per_envelope():
    """Second identical run: zero new traces, bitwise-identical output."""
    A, R, P = multigrid.problem("laplace3d", SIZES["laplace3d"])
    plan = plan_pipeline(A, P, R, P100,
                         fast_limit_bytes=_tight_limit(A, P, R, 0.25))
    assert "whole_fast" not in (plan.plan1.algorithm, plan.plan2.algorithm)
    caps = pipeline_output_caps(A, P, R, plan.plan1.p_ac, plan.plan2.p_ac)
    C1, _ = pipeline_spgemm(A, P, R, plan, backend="sparse", caps=caps)
    before = dict(TRACE_COUNTS)
    C2, _ = pipeline_spgemm(A, P, R, plan, backend="sparse", caps=caps)
    assert dict(TRACE_COUNTS) == before, \
        "second identical pipeline run retraced a core"
    np.testing.assert_array_equal(np.asarray(C1.indptr),
                                  np.asarray(C2.indptr))
    np.testing.assert_array_equal(np.asarray(C1.indices),
                                  np.asarray(C2.indices))
    np.testing.assert_array_equal(np.asarray(C1.data), np.asarray(C2.data))


def test_masked_triangle_compiles_once_per_envelope():
    L = graphs.lower_triangular_degree_sorted(GRAPHS["g500_s7"]())
    plan = plan_knl(L, L, float("inf"))
    caps = masked_output_caps(L, plan.p_ac)
    t1 = float(count_triangles(L, plan=plan, caps=caps))
    before = dict(TRACE_COUNTS)
    t2 = float(count_triangles(L, plan=plan, caps=caps))
    assert dict(TRACE_COUNTS) == before, \
        "second identical masked triangle run retraced a core"
    assert t1 == t2
    assert any(k.endswith("_hash_masked") for k in before), \
        "masked run never hit a masked hash core"


# ---------------------------------------------------------------------------
# the benchmark's HPCG operators on the spilled, chunk2-chunk2 path, and the
# ESC body's two key layouts inside one pipeline
# ---------------------------------------------------------------------------


def _host_csr(C):
    """The program's CSR as a SciPy CSR of its stored entries."""
    ip, ix, d = (np.asarray(x) for x in (C.indptr, C.indices, C.data))
    nnz = int(ip[-1])
    return sp.csr_matrix((d[:nnz], ix[:nnz], ip), shape=C.shape)


def _check_rap(got, A, P, R, tol):
    """``got`` (SciPy CSR) has the structure of |R| (|A| |P|), in which no
    entry cancels, and every value within ``tol`` of the float64 product,
    relative to the same absolute product."""
    A, P, R = (m.astype(np.float64) for m in (A, P, R))
    bound = abs(R) @ (abs(A) @ abs(P))
    bound.sort_indices()
    np.testing.assert_array_equal(got.indptr, bound.indptr)
    np.testing.assert_array_equal(got.indices, bound.indices)
    ref = (R @ (A @ P)).toarray()
    err = np.abs(got.toarray() - ref)
    assert (err <= tol * bound.toarray()).all(), float(err.max())


def _bench_hpcg(grid):
    """The benchmark's HPCG operators (``box27`` and ``full_weighting``) at
    ``grid``, as SciPy CSRs and as the program's CSRs."""
    from bench import operators, program, registry

    config = registry.read_json(registry.ROOT / "bench/configs/hpcg27_104.json")
    ops = operators.build({**config, "grid": grid})
    return ops, [program.to_program(m) for m in (ops.A, ops.P, ops.R)]


def test_bench_hpcg_spills_the_intermediate_and_streams_both_hops():
    """The benchmark's 104^3 cell at grid 12: a fast limit that spills T
    and plans both hops as chunk2, as the v5e's memory plans grid 104. The
    float32 error of each entry is at most about (27 + 27 + 2) roundings of
    2**-24 relative to |R| |A| |P| (two sums of up to 27 terms, the product
    and T's rounding); 1e-5 leaves room above that."""
    ops, (A, P, R) = _bench_hpcg(12)
    plan = plan_pipeline(A, P, R, TPU_V5E, fast_limit_bytes=120_000)
    assert not plan.t_resident
    assert (plan.plan1.algorithm, plan.plan2.algorithm) == ("chunk2", "chunk2")
    assert plan.plan1.n_ac > 1 and plan.plan2.n_ac > 1 and plan.plan2.n_b > 1
    C, stats = pipeline_spgemm(A, P, R, plan)
    assert stats.spilled and stats.spill_bytes > 0
    _check_rap(_host_csr(C), ops.A, ops.P, ops.R, 1e-5)


# hop 1's strips of 32 rows against 2**27 columns key past 2**32; hop 2's
# strips of 4 rows do not
WIDE = 2**27


def _wide_operands():
    """A 64 x 24, P 24 x 2**27 with 12 columns in use, the last among them,
    and R 8 x 64, which is not P^T."""
    rng = np.random.default_rng(15)
    A = sp.random(64, 24, density=0.3, random_state=rng, dtype=np.float32,
                  format="csr")
    cols = np.sort(np.concatenate([rng.choice(WIDE - 1, 11, replace=False),
                                   [WIDE - 1]]))
    Pc = sp.random(24, cols.size, density=0.5, random_state=rng,
                   dtype=np.float32, format="csr")
    P = sp.csr_matrix((Pc.data, cols[Pc.indices], Pc.indptr), shape=(24, WIDE))
    R = sp.random(8, 64, density=0.4, random_state=rng, dtype=np.float32,
                  format="csr")
    for m in (A, P, R):
        m.sort_indices()
    return A, P, R, cols


def test_pair_key_in_hop_1_and_packed_key_in_hop_2(tmp_path):
    """Both key layouts in one pipeline: the launch span of each hop says
    which sort it ran, as the ESC body's trace counters do."""
    import jax

    from bench import program_spans
    from repro.core.kkmem import key_layout
    from repro.core.planner import ChunkPlan, PipelinePlan

    A, P, R, cols = _wide_operands()
    assert (key_layout(32, WIDE), key_layout(4, WIDE)) == ("pair", "packed")
    plan = PipelinePlan(ChunkPlan("chunk2", (0, 32, 64), (0, 24), 0, 0),
                        ChunkPlan("chunk2", (0, 4, 8), (0, 32, 64), 0, 0),
                        False, 0.0, 0.0, 0.0)
    a, p, r = (csr_from_scipy_like(m.indptr, m.indices, m.data, m.shape)
               for m in (A, P, R))
    before = dict(TRACE_COUNTS)
    pipeline_spgemm(a, p, r, plan)
    traced = {k: v - before.get(k, 0) for k, v in TRACE_COUNTS.items()
              if k.startswith("esc_key") and v != before.get(k, 0)}
    # one body per hop: chunk2's inner scan traces it once
    assert traced == {"esc_key_pair": 1, "esc_key_packed": 1}

    jax.profiler.start_trace(str(tmp_path))
    C, stats = pipeline_spgemm(a, p, r, plan)
    jax.profiler.stop_trace()
    assert stats.spilled
    path, = tmp_path.rglob("*.xplane.pb")
    spans = program_spans.load(path)
    hops = [(s, e, st["hop"]) for n, s, e, st, _ in spans if n == "spgemm/hop"]
    keys = {next(h for hs, he, h in hops if hs <= s and e <= he): st["key"]
            for n, s, e, st, _ in spans if n == "spgemm/launch"}
    assert keys == {1: "pair", 2: "packed"}

    # the reference on P's columns in use: C holds no entry elsewhere
    got = _host_csr(C)
    assert got[:, cols].nnz == got.nnz
    _check_rap(got[:, cols], A, P[:, cols], R, 1e-5)
