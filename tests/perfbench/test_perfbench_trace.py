"""The reduction from a profiler trace to busy time, idle share, top
operations and labelled idle gaps."""

import types
from pathlib import Path

import numpy as np
import pytest

from bench import run, trace


def _brute_busy(ops, lo, hi, step=1):
    t = np.arange(lo, hi, step) + step / 2
    busy = np.zeros(t.size, bool)
    for _, s, e in ops:
        busy |= (t >= s) & (t < e)
    return busy.sum() * step


@pytest.mark.parametrize("seed", range(4))
def test_busy_union_matches_brute_force(seed):
    rng = np.random.default_rng(seed)
    starts = rng.integers(0, 900, 40)
    ops = [(f"op{i % 5}", int(s), int(s + rng.integers(1, 60))) for i, s in enumerate(starts)]
    lo, hi = 100, 800
    assert trace.busy_ns(ops, lo, hi) == _brute_busy(ops, lo, hi)
    idle = sum(e - s for s, e in trace.gaps(ops, lo, hi))
    assert idle + trace.busy_ns(ops, lo, hi) == hi - lo


def _synthetic():
    device = [("while", 100, 400), ("fusion.1", 120, 300), ("sort.2", 310, 390),
              ("fusion.1", 700, 750), ("copy.3", 20, 60)]
    spans = [("bench/window", 50, 1050), ("bench/product 1", 50, 600),
             ("bench/product 2", 600, 1050)]
    host = [("call", 390, 720), ("a_strips", 420, 690), ("asarray", 430, 440),
            ("_assemble", 760, 1040)]
    return trace.Trace({"/device:TPU:0": device}, spans, host)


def test_summary_of_a_synthetic_trace():
    s = trace.summarize(_synthetic())
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx((400 - 100 + 50 + 10) * 1e-9)
    # own time: the while holds 300 - 180 - 80; fusion.1 180 + 50; copy.3 is
    # clipped to the window (50-60); together they are the busy time
    own = dict(s.device_ops)
    assert own == pytest.approx({"fusion.1": 230e-9, "sort.2": 80e-9,
                                 "while": 40e-9, "copy.3": 10e-9})
    assert sum(own.values()) == pytest.approx(s.busy_s)
    # gaps: 60-100 (40), 400-700 (300), 750-1050 (300); 50-60 inside copy
    lengths = [g[1] for g in s.idle_gaps]
    assert lengths == pytest.approx([300e-9, 300e-9, 40e-9])
    # 400-700 lies in product 1 and inside "call", most of it in a_strips;
    # no host call spans all of 750-1050
    assert [g[0] for g in s.idle_gaps] == ["product 1: call > a_strips",
                                           "product 2", "product 1"]
    assert s.busy_s / s.window_s < 1


def test_a_window_with_no_device_work_is_refused():
    t = _synthetic()
    t.device_ops = {"/device:TPU:0": [("late", 2000, 3000)]}
    with pytest.raises(ValueError):
        trace.summarize(t)


FIXTURE = Path(__file__).parent / "fixtures" / "hpcg27_ap_grid24.xplane.pb"


def test_recorded_trace_reduces_consistently():
    """A window recorded on a TPU v5 lite by the harness (hpcg27.ap at grid
    24, one product): every interval is read, the own times of all
    operations add up to the busy time, and busy plus idle is the window."""
    t = trace.load(FIXTURE)
    assert list(t.device_ops) == ["/device:TPU:0"]
    assert len(t.device_ops["/device:TPU:0"]) == 352
    assert sorted({n for n, _, _ in t.spans}) == ["bench/product 1", "bench/window"]
    lo, hi = t.window()
    s = trace.summarize(t, top=1000)
    assert s.window_s == pytest.approx((hi - lo) / 1e9) == pytest.approx(0.305083941)
    assert s.busy_s == pytest.approx(0.301406242)
    assert sum(x for _, x in s.device_ops) == pytest.approx(s.busy_s)
    idle = sum(e - b for b, e in trace.gaps(t.device_ops["/device:TPU:0"], lo, hi))
    assert idle / 1e9 + s.busy_s == pytest.approx(s.window_s)
    assert all(name.startswith("jit_") for name, _ in s.device_ops)


def test_trace_metrics_on_the_recorded_window():
    s = trace.summarize(trace.load(FIXTURE))
    run_ = types.SimpleNamespace(
        trace=s, products=[(1, 0.0, 1.0)],
        work={"flops": 2 * 10**6, "bytes": 10**7},
        cell=types.SimpleNamespace(peaks=run.peaks_for("TPU v5 lite")))
    idle = run.reader("device_idle_pct")(run_)
    assert idle["value"] == pytest.approx(100 * (1 - 0.301406242 / 0.305083941))
    roof = run.reader("spgemm_roofline_pct")(run_)
    assert roof["bound"] == "memory"
    assert roof["value"] == pytest.approx(100 * (1e7 / 819e9) / 0.301406242)
    assert 0 < roof["value"] < 100
