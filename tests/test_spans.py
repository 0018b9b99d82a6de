"""Host spans of the program (``repro.core.spans``) in a real profiler trace.

Every test that traces runs the product once untraced first, so that the
trace holds no compile, then once under ``jax.profiler`` on the CPU, and
reads the ``spgemm/*`` events back from the ``.xplane.pb``. Byte counts are
worked out here from the operands' structure: on the CPU a device array
keeps no host copy, so every read of one to the host counts.
"""

import pathlib

import jax
import numpy as np
import pytest

from repro.core import spans
from repro.core.chunking import chunked_spgemm
from repro.core.pipeline_spgemm import pipeline_spgemm
from repro.core.planner import ChunkPlan, PipelinePlan
from repro.core.symbolic import pipeline_output_caps, strip_output_caps
from repro.sparse import multigrid
from repro.sparse.csr import (
    csr_from_dense, csr_pad_to, csr_rows_host, csr_select_rows_host,
    csr_to_dense,
)
from conftest import random_csr, random_dense

I32 = 4


def _trace(tmp_path, fn):
    """Run ``fn`` under the profiler; the ``spgemm/*`` events of the trace as
    ``[(phase, start_ns, end_ns, stats)]`` in start order, and ``fn``'s
    result."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = pathlib.Path(tmp_path).rglob("*.xplane.pb")
    events = []
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(spans.PREFIX):
                    events.append((e.name[len(spans.PREFIX):], e.start_ns,
                                   e.end_ns, dict(e.stats)))
    return sorted(events, key=lambda ev: (ev[1], -ev[2])), out


def _hop(ev, events):
    """The ``hop`` of the hop span around ``ev`` (None outside any)."""
    for phase, s, e, stats in events:
        if phase == "hop" and s <= ev[1] and ev[2] <= e:
            return stats["hop"]
    return None


def _bytes(events, phase, key, hop=None):
    return sum(ev[3].get(key, 0) for ev in events
               if ev[0] == phase and (hop is None or _hop(ev, events) == hop))


def _nbytes(M) -> int:
    return sum(np.asarray(x).nbytes for x in (M.indptr, M.indices, M.data))


def _pieces_nbytes(M, bounds) -> int:
    """Bytes of ``M``'s row pieces, each padded to the largest piece's rows
    and entries (at least one entry)."""
    ptr = np.asarray(M.indptr)
    rows = max(e - s for s, e in zip(bounds[:-1], bounds[1:]))
    cap = max(max(int(ptr[e] - ptr[s]) for s, e in zip(bounds[:-1], bounds[1:])), 1)
    item = np.asarray(M.data).dtype.itemsize
    return (len(bounds) - 1) * ((rows + 1) * I32 + cap * (I32 + item))


def _exact_nbytes(n_rows: int, nnz: int, item: int = 4) -> int:
    return (n_rows + 1) * I32 + max(nnz, 1) * (I32 + item)


def _dense_equal(X, Y, exact=True):
    x, y = np.asarray(csr_to_dense(X)), np.asarray(csr_to_dense(Y))
    if exact:
        np.testing.assert_array_equal(x, y)
    else:
        np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# the span itself
# ---------------------------------------------------------------------------


def test_span_writes_phase_args_and_counts(tmp_path):
    def body():
        with spans.span("stage", hop=2, backend="scan") as sp:
            sp.add(d2h_bytes=5, h2d_bytes=lambda: 7)
            sp.add(d2h_bytes=3)
            sp.note(resolved="pallas")
            with spans.span("launch"):
                pass

    events, _ = _trace(tmp_path, body)
    (p0, s0, e0, st0), (p1, s1, e1, st1) = events
    assert (p0, p1) == ("stage", "launch")
    assert s0 <= s1 and e1 <= e0
    assert st0 == {"hop": 2, "backend": "scan", "d2h_bytes": 8, "h2d_bytes": 7,
                   "resolved": "pallas"}
    assert st1 == {}


def test_span_computes_no_count_without_profiler():
    calls = []

    def count():
        calls.append(1)
        return 1

    x = jax.numpy.arange(6, dtype=np.int32)
    with spans.span("stage") as sp:
        sp.add(h2d_bytes=count)
        host = sp.get(x)
        dev = sp.put(np.arange(3, dtype=np.int32))
        assert not sp.on
    assert calls == [] and sp.stats == {}
    np.testing.assert_array_equal(host, np.arange(6))
    assert isinstance(dev, jax.Array)


def test_span_counts_only_what_crosses(tmp_path):
    dev = jax.numpy.ones(10, np.float32)
    host = np.ones(3, np.float64)

    def body():
        with spans.span("fetch") as sp:
            sp.get((dev, host))
        with spans.span("assemble") as sp:
            sp.put((host, dev))

    events, _ = _trace(tmp_path, body)
    assert [ev[3] for ev in events] == [{"d2h_bytes": 40}, {"h2d_bytes": 24}]


def test_span_decorates_symbolic_phase(tmp_path):
    rng = np.random.default_rng(0)
    A, B = random_csr(rng, 12, 9, 0.3), random_csr(rng, 9, 7, 0.3)
    events, caps = _trace(tmp_path, lambda: strip_output_caps(A, B, (0, 5, 12)))
    assert [ev[0] for ev in events] == ["symbolic"]
    assert caps.strip_nnz == strip_output_caps(A, B, (0, 5, 12)).strip_nnz


# ---------------------------------------------------------------------------
# host slicing: the same pieces as the device-side slice and pad
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("r0,r1,cap,rows,mrn", [
    (0, 12, None, None, None), (3, 9, 40, 8, 9), (5, 5, 3, 2, 4),
    (0, 4, None, 6, None)])
def test_csr_rows_host_matches_the_device_slice_and_pad(r0, r1, cap, rows, mrn):
    rng = np.random.default_rng(3)
    d = random_dense(rng, 12, 9, 0.4)
    M = csr_from_dense(d, pad_to=int((d != 0).sum()) + 5)
    got = csr_rows_host(jax.device_get(M), r0, r1, cap, rows, mrn)
    want = csr_pad_to(csr_from_dense(d[r0:r1], pad_to=cap), rows=rows,
                      max_row_nnz=mrn)
    assert isinstance(csr_select_rows_host(M, r0, r1, pad_to=cap).data, jax.Array)
    assert (got.shape, got.max_row_nnz) == (want.shape, want.max_row_nnz)
    for g, w in zip((got.indptr, got.indices, got.data),
                    (want.indptr, want.indices, want.data)):
        assert isinstance(g, np.ndarray)
        np.testing.assert_array_equal(g, np.asarray(w))
        assert g.dtype == w.dtype


@pytest.mark.parametrize("target", [{"nnz_cap": 1}, {"rows": 2},
                                    {"max_row_nnz": 0}])
def test_csr_rows_host_refuses_a_smaller_target(target):
    M = jax.device_get(csr_from_dense(np.ones((6, 4), np.float32)))
    with pytest.raises(ValueError):
        csr_rows_host(M, 1, 5, **target)


# ---------------------------------------------------------------------------
# chunked_spgemm and pipeline_spgemm under the profiler
# ---------------------------------------------------------------------------

CASES = {
    "scan-chunk1": ("scan", ChunkPlan("chunk1", (0, 14, 30, 40), (0, 11, 30), 0, 0)),
    "scan-chunk2": ("scan", ChunkPlan("chunk2", (0, 14, 30, 40), (0, 11, 30), 0, 0)),
    "scan-knl": ("scan", ChunkPlan("knl", (0, 40), (0, 11, 20, 30), 0, 0)),
    "pallas-chunk1": ("pallas", ChunkPlan("chunk1", (0, 16, 40), (0, 11, 30), 0, 0)),
    "pallas-chunk2": ("pallas", ChunkPlan("chunk2", (0, 16, 40), (0, 11, 30), 0, 0)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_chunked_spgemm_spans_and_bytes(tmp_path, case):
    backend, plan = CASES[case]
    rng = np.random.default_rng(11)
    A, B = random_csr(rng, 40, 30, 0.15, pad_extra=3), random_csr(rng, 30, 24, 0.2)
    c_pad = strip_output_caps(A, B, plan.p_ac).c_pad
    run = lambda: chunked_spgemm(A, B, plan, c_pad, backend=backend)[0]  # noqa: E731
    run()
    events, C = _trace(tmp_path, run)
    _dense_equal(C, chunked_spgemm(A, B, plan, c_pad, backend="loop")[0],
                 exact=backend == "scan")

    phases = {ev[0] for ev in events}
    call = [ev for ev in events if ev[0] == "call"]
    assert len(call) == 1
    assert call[0][3] == {"algorithm": plan.algorithm, "n_ac": plan.n_ac,
                          "n_b": plan.n_b, "backend": backend}
    assert all(call[0][1] <= s and e <= call[0][2] for _, s, e, _ in events)

    ranges = 2 * plan.n_b * I32
    stage_d2h = _nbytes(B) + (0 if plan.algorithm == "knl" else _nbytes(A))
    stage_h2d = _pieces_nbytes(B, plan.p_b) + ranges
    if plan.algorithm != "knl":
        stage_h2d += _pieces_nbytes(A, plan.p_ac)
    assert _bytes(events, "stage", "d2h_bytes") == stage_d2h
    assert _bytes(events, "stage", "h2d_bytes") == stage_h2d
    if backend == "scan" and plan.algorithm == "knl":
        # the whole C stays on the device: nothing to wait for or fetch
        assert phases == {"call", "stage", "launch"}
        return
    assert phases == {"call", "stage", "launch", "wait", "fetch", "assemble"}
    strip_rows = max(e - s for s, e in zip(plan.p_ac[:-1], plan.p_ac[1:]))
    if backend == "scan":
        # the strips of C, and one flag per step: whether its body ran
        fetched = plan.n_ac * ((strip_rows + 1) * I32 + c_pad * (I32 + 4)
                               + plan.n_b)
    else:
        fetched = plan.n_ac * strip_rows * B.n_cols * 4
    nnz = int(np.asarray(C.indptr)[-1])
    assert _bytes(events, "fetch", "d2h_bytes") == fetched
    assert _bytes(events, "assemble", "h2d_bytes") == _exact_nbytes(A.n_rows, nnz)


def test_chunked_spgemm_auto_resolves_inside_plan_span(tmp_path):
    backend, plan = CASES["pallas-chunk1"]
    rng = np.random.default_rng(5)
    A, B = random_csr(rng, 40, 30, 0.15), random_csr(rng, 30, 24, 0.2)
    run = lambda: chunked_spgemm(A, B, plan, backend="auto")[0]  # noqa: E731
    run()
    events, _ = _trace(tmp_path, run)
    names = [ev[0] for ev in events]
    assert names[:3] == ["call", "symbolic", "plan"]
    assert events[0][3]["backend"] == backend


@pytest.mark.parametrize("resident", [True, False])
def test_pipeline_spans_carry_hops_and_bytes(tmp_path, resident):
    A, R, P = multigrid.problem("laplace3d", 4)
    p1 = ChunkPlan("chunk2", (0, 20, 45, 64), (0, 30, 64), 0, 0)
    p2 = ChunkPlan("chunk1", (0, 3, 8), (0, 30, 64), 0, 0)
    plan = PipelinePlan(p1, p2, t_resident=resident, t_bytes=0.0,
                        copy_bytes=0.0, fast_bytes_needed=0.0)
    caps = pipeline_output_caps(A, P, R, p1.p_ac, p2.p_ac)
    run = lambda: pipeline_spgemm(A, P, R, plan, caps=caps)[0]  # noqa: E731
    run()
    events, C = _trace(tmp_path, run)
    T_loop = chunked_spgemm(A, P, p1, caps.hop1.c_pad, backend="loop")[0]
    _dense_equal(C, chunked_spgemm(R, T_loop, p2, caps.hop2.c_pad,
                                   backend="loop")[0])

    assert [ev[0] for ev in events][:2] == ["pipeline", "hop"]
    hops = [ev[3] for ev in events if ev[0] == "hop"]
    assert [(h["hop"], h["resident"]) for h in hops] == [(1, resident), (2, resident)]
    for phase in ("stage", "launch", "wait", "fetch", "assemble"):
        assert {_hop(ev, events) for ev in events if ev[0] == phase} == {1, 2}

    t_nnz = int(caps.t_pattern.indptr[-1])
    t_bytes = _exact_nbytes(A.n_rows, t_nnz)
    assert _bytes(events, "stage", "d2h_bytes", hop=1) == _nbytes(A) + _nbytes(P)
    assert _bytes(events, "assemble", "h2d_bytes", hop=1) == t_bytes
    # T crosses back once: read by hop 2's staging, or by the spill before it
    assert _bytes(events, "stage", "d2h_bytes", hop=2) == _nbytes(R) + (
        t_bytes if resident else 0)
    assert _bytes(events, "spill", "d2h_bytes") == (0 if resident else t_bytes)
    assert ("spill" in {ev[0] for ev in events}) is not resident
    assert _bytes(events, "fetch", "d2h_bytes", hop=2) == p2.n_ac * (
        (5 + 1) * I32 + caps.hop2.c_pad * (I32 + 4) + p2.n_b)
    nnz = int(np.asarray(C.indptr)[-1])
    assert _bytes(events, "assemble", "h2d_bytes", hop=2) == _exact_nbytes(R.n_rows, nnz)
