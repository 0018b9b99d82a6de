"""Bench-driver registry hygiene: every lane listed exactly once, JSON lanes
wired through the driver, and the CI-parsed lanes present under the names the
workflow invokes — the drift this guards against is a renamed lane leaving a
stale SUITES entry (double-run) or none (silently dropped from full runs).
"""

import collections
import pathlib

from benchmarks.chunking_bench import JSON_LANES as CHUNKING_LANES
from benchmarks.run import SUITES, _resolve
from benchmarks.triangle_counting import JSON_LANES as TRIANGLE_LANES

JSON_LANES = {**CHUNKING_LANES, **TRIANGLE_LANES}


def test_json_lane_names_globally_unique():
    """`--lane` names double as bench-artifact filenames, so two modules
    must never register the same lane name."""
    overlap = set(CHUNKING_LANES) & set(TRIANGLE_LANES)
    assert not overlap, f"lane names registered by two modules: {overlap}"


def test_suites_list_every_lane_exactly_once():
    """Lane names are unique by dict construction; the drift that can happen
    is two names pointing at the same module:function (one lane run twice
    per full sweep)."""
    specs = collections.Counter(SUITES.values())
    dupes = {spec: n for spec, n in specs.items() if n > 1}
    assert not dupes, f"lanes registered more than once: {dupes}"


def test_every_suite_spec_resolves():
    for name, spec in SUITES.items():
        fn = _resolve(spec)
        assert callable(fn), f"{name}: {spec} did not resolve to a callable"


def test_json_lanes_have_driver_entries():
    """Each JSON lane (what `--lane` and the CI smoke parse run) also runs
    under a full `python -m benchmarks.run` via a CSV wrapper."""
    for lane in JSON_LANES:
        assert lane in SUITES, f"JSON lane {lane!r} missing from run.SUITES"
    assert "accumulator_shootout" in JSON_LANES
    assert "bsr_blocking" in JSON_LANES
    assert "triangle_counting" in JSON_LANES
    assert "dense_vs_sparse_accum" not in SUITES, \
        "stale pre-shootout lane name still registered"
    assert "fig11" not in SUITES, \
        "stale pre-JSON-lane triangle suite name still registered"


def test_ci_smokes_every_json_lane():
    """Registry-driven CI gating: the workflow must carry a smoke step (and
    artifact capture) for every registered JSON lane, so registering a lane
    without wiring its CI gate fails the fast test lane — the same
    add-one-registration contract the backend registry gives executors."""
    ci = (pathlib.Path(__file__).resolve().parents[1]
          / ".github" / "workflows" / "ci.yml").read_text()
    for lane in JSON_LANES:
        if lane == "scan_vs_pallas":
            continue                     # the workflow's default (laneless) run
        assert f"--lane {lane}" in ci, \
            f"JSON lane {lane!r} has no smoke step in .github/workflows/ci.yml"
    assert "upload-artifact" in ci, "bench artifacts are not uploaded by CI"


def test_triangle_speedup_is_lane_level_scalar():
    """The chunked-vs-kkmem speedup is one lane-level float beside the rows,
    which the CI smoke step asserts on."""
    from benchmarks.triangle_counting import run_triangle_counting

    report = run_triangle_counting(smoke=True)
    assert report["bench"] == "triangle_counting"
    assert isinstance(report["chunked_vs_kkmem_speedup"], float)
    assert report["chunked_vs_kkmem_speedup"] > 0
    assert report["rows"], "smoke lane emitted no rows"
