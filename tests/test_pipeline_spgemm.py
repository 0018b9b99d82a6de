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

from repro.core.chunk_stream import TRACE_COUNTS
from repro.core.kkmem import spgemm_dense_oracle
from repro.core.memory_model import P100
from repro.core.pipeline_spgemm import pipeline_spgemm
from repro.core.planner import plan_knl, plan_pipeline
from repro.core.symbolic import masked_output_caps, pipeline_output_caps
from repro.core.triangle import count_triangles, count_triangles_dense
from repro.sparse import graphs, multigrid
from repro.sparse.csr import csr_to_dense

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
