"""The reduction of the program's ``spgemm/*`` spans beside the device's
operations: own time and byte counts per phase, the per-product metrics,
idle gaps named by program span, and the share of idle time they cover."""

import json
from pathlib import Path

import pytest

from bench import program_spans as ps
from bench import trace

T = ("/host:CPU", 0)


def _program():
    """Two products: R(AP) with its two hops, then an A P whose caps are
    worked out inside the call."""
    spans = [
        ("pipeline", 10, 490, {}),
        ("hop", 20, 240, {"hop": 1}),
        ("stage", 20, 60, {"d2h_bytes": 100, "h2d_bytes": 300}),
        ("launch", 60, 70, {}), ("wait", 70, 200, {}),
        ("fetch", 200, 220, {"d2h_bytes": 50}),
        ("assemble", 220, 240, {"h2d_bytes": 40}),
        ("hop", 250, 480, {"hop": 2}),
        ("stage", 250, 300, {"d2h_bytes": 10, "h2d_bytes": 20}),
        ("launch", 300, 310, {}), ("wait", 310, 440, {}),
        ("fetch", 440, 460, {"d2h_bytes": 5}),
        ("assemble", 460, 480, {"h2d_bytes": 4}),
        ("call", 510, 990, {"backend": "pallas"}),
        ("symbolic", 520, 700, {}),
        ("stage", 700, 720, {"h2d_bytes": 1000}),
        ("launch", 720, 730, {}), ("wait", 730, 800, {}),
        ("fetch", 800, 900, {"d2h_bytes": 2000}),
        ("assemble", 900, 980, {"h2d_bytes": 6}),
    ]
    return [(ps.PREFIX + n, s, e, st, T) for n, s, e, st in spans]


def _trace(host=()):
    device = [("fusion.1", 70, 200), ("fusion.1", 310, 440), ("fusion.2", 730, 800)]
    spans = [("bench/window", 0, 1000), ("bench/product 1", 0, 500),
             ("bench/product 2", 500, 1000)]
    return trace.Trace({"/device:TPU:0": device}, spans, list(host))


def test_own_time_and_counts_per_phase():
    ph = ps.phases(_program(), 0, 1000)
    own = {k: v["self_s"] * 1e9 for k, v in ph.items()}
    assert own == pytest.approx({"pipeline": 30, "hop": 0, "stage": 110,
                                 "launch": 30, "wait": 330, "fetch": 140,
                                 "assemble": 120, "call": 20, "symbolic": 180})
    assert sum(own.values()) == pytest.approx(480 + 480)
    assert (ph["stage"]["d2h_bytes"], ph["stage"]["h2d_bytes"]) == (110, 1320)
    assert (ph["fetch"]["d2h_bytes"], ph["assemble"]["h2d_bytes"]) == (2055, 50)


def test_own_time_is_clipped_to_the_window_and_kept_per_hop():
    own = ps.own_ns(_program(), 200, 310)
    assert own == {"spgemm/fetch hop 1": 20, "spgemm/assemble hop 1": 20,
                   "spgemm/pipeline": 10, "spgemm/stage hop 2": 50,
                   "spgemm/launch hop 2": 10}


def test_threads_nest_apart():
    """A span on another thread is no child of one it overlaps in time."""
    other = [("spgemm/stage", 100, 150, {}, ("/host:CPU", 1))]
    own = ps.own_ns(_program() + other, 0, 1000)
    assert own["spgemm/stage"] == 20 + 50
    assert own["spgemm/wait hop 1"] == 130


def test_metrics_per_product():
    m = ps.metrics(_trace(), _program())
    assert m["window_symbolic_s"]["value"] == pytest.approx(90e-9)
    assert m["stage_s"]["value"] == pytest.approx(55e-9)
    assert m["assemble_s"]["value"] == pytest.approx(130e-9)
    mb = m["host_device_mb"]
    assert (mb["d2h_mb"], mb["h2d_mb"]) == pytest.approx((2165 / 2e6, 1370 / 2e6))
    assert mb["value"] == pytest.approx(mb["d2h_mb"] + mb["h2d_mb"])


def test_metrics_read_zero_without_program_spans():
    m = ps.metrics(_trace(), [])
    assert {k: v["value"] for k, v in m.items()} == {
        "window_symbolic_s": 0, "stage_s": 0, "assemble_s": 0, "host_device_mb": 0}


def test_idle_gaps_are_named_by_program_span():
    s = ps.summarize(_trace(), _program())
    assert s["idle_gaps"] == [
        ["product 2: spgemm/symbolic", pytest.approx(290e-9)],
        ["product 2: spgemm/fetch", pytest.approx(200e-9)],
        ["product 1: spgemm/stage hop 2", pytest.approx(110e-9)],
        ["product 1: spgemm/stage hop 1", pytest.approx(70e-9)]]
    assert s["idle_covered_share"] == pytest.approx(630 / 670)


def test_a_gap_without_program_span_keeps_its_python_label():
    host = [("call", 190, 320), ("a_strips", 195, 300)]
    t = _trace(host)
    assert ps.label((200, 310), t.spans, []) is None
    s = ps.summarize(t, [])
    assert [g[0] for g in s["idle_gaps"]] == [
        trace._label(g, t.spans, host) for g in [(440, 730), (800, 1000), (200, 310),
                                                 (0, 70)]]
    assert s["idle_gaps"][2][0] == "product 1: call > a_strips"
    assert s["idle_covered_share"] == 0


FIXTURE = Path(__file__).parent / "fixtures" / "hpcg27_ap_grid24.xplane.pb"


def test_recorded_trace_without_program_spans(capsys):
    """The recorded window predates the program's spans: every phase reads 0
    and the gaps keep ``trace.py``'s labels."""
    assert ps.load(FIXTURE) == []
    assert ps.main([str(FIXTURE)]) == 0
    out = json.loads(capsys.readouterr().out)
    assert {k: v["value"] for k, v in out["metrics"].items()} == {
        "window_symbolic_s": 0, "stage_s": 0, "assemble_s": 0, "host_device_mb": 0}
    assert out["idle_covered_share"] == 0
    assert [g[0] for g in out["idle_gaps"]] == [
        g[0] for g in trace.summarize(trace.load(FIXTURE)).idle_gaps]


def test_a_program_trace_on_the_cpu(tmp_path):
    """A real trace of the program's pipeline: phases, hops and counts."""
    import jax

    from repro.core.pipeline_spgemm import pipeline_spgemm
    from repro.core.planner import ChunkPlan, PipelinePlan
    from repro.sparse import multigrid

    A, R, P = multigrid.problem("laplace3d", 4)
    plan = PipelinePlan(ChunkPlan("chunk2", (0, 30, 64), (0, 40, 64), 0, 0),
                        ChunkPlan("chunk1", (0, 8), (0, 30, 64), 0, 0),
                        True, 0.0, 0.0, 0.0)
    pipeline_spgemm(A, P, R, plan)
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench/window"):
        pipeline_spgemm(A, P, R, plan)
    jax.profiler.stop_trace()
    path, = tmp_path.rglob("*.xplane.pb")
    program = ps.load(path)
    lo = min(s for _, s, _, _, _ in program)
    hi = max(e for _, _, e, _, _ in program)
    ph = ps.phases(program, lo, hi)
    assert {"pipeline", "symbolic", "hop", "stage", "launch", "wait", "fetch",
            "assemble"} <= set(ph)
    assert sum(p["self_s"] for p in ph.values()) == pytest.approx((hi - lo) / 1e9)
    assert ph["stage"]["d2h_bytes"] > 0 and ph["assemble"]["h2d_bytes"] > 0
    labels = set(ps.own_ns(program, lo, hi))
    assert {"spgemm/hop 1", "spgemm/hop 2", "spgemm/fetch hop 1",
            "spgemm/stage hop 2"} <= labels
