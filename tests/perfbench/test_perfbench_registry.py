"""BENCHMARK.json against the files the harness finds by name, the
contract's limits on its entries, and that a new cell, configuration,
traffic mix, stencil, product kind or metric needs only new files and new
entries."""

import dataclasses
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import pytest

from bench import reference, registry, run
from repro.core.memory_model import TPU_V5E

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys_and_paths():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    for path in SPEC["paths"]:
        assert (ROOT / path).is_dir() and not path.startswith("/") and ".." not in path
    for word in SPEC["command"][1:]:
        assert any(word.startswith(p + "/") for p in SPEC["paths"])


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves_to_its_files(cell):
    c = run.resolve(cell)
    assert c.workload["chips"] in (1, 4)
    assert callable(registry.load("products", c.traffic["product"]).prepare)
    assert callable(registry.load("stencils", c.config["stencil"]).matrix)
    assert callable(registry.load("restrictions", c.config["restriction"]).matrix)
    assert set(c.limits) == set(reference.NUMBERS)
    assert c.limits["structure_diff"] == 0
    assert c.limits["left_out_err"] < c.limits["value_err"] / 10
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    assert len(c.end_to_end) >= 2 and c.per_layer


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
def test_every_metric_has_a_reader(metric):
    assert callable(run.reader(metric))


def test_names_units_and_layers():
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    names = [e["name"] for e in SPEC["configs"] + SPEC["workloads"] + metrics]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert all(UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
               for m in metrics)
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert all(m["moves"] in e2e for m in SPEC["per_layer"])
    assert all(0.01 <= m["bound"] <= 0.25 for m in SPEC["end_to_end"])
    for c in SPEC["configs"]:
        config = json.loads((ROOT / c["file"]).read_text())
        assert all(k in config for k in c["reduced"])


def test_no_tpu_means_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "hpcg27.ap", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout == ""


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for path in SPEC["paths"]:
        shutil.copytree(ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "hpcg27.ap", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout == ""


def test_unknown_device_kind_is_an_error():
    with pytest.raises(run.NoDevice):
        run.peaks_for("TPU v9 imaginary")


def _digest(root):
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


STAR7 = """\
import numpy as np

from bench import operators


def matrix(config):
    g = int(config["grid"])
    offsets = np.array([[0, 0, 0], [-1, 0, 0], [1, 0, 0], [0, -1, 0],
                        [0, 1, 0], [0, 0, -1], [0, 0, 1]])
    weights = [config["center"]] + [config["off"]] * 6
    rows, cols, vals = operators.grid_coo(operators.grid_points(g), g,
                                          offsets, weights)
    return operators.csr(rows, cols, vals, (g ** 3, g ** 3))
"""

AP_FRESH = """\
from bench import program


def prepare(traffic, ops, system, spans):
    from repro.core import planner, symbolic
    from repro.core.chunking import chunked_spgemm

    A, P = program.to_program(ops.A), program.to_program(ops.P)

    def call(data):
        a = program.with_values(A, data)
        T = symbolic.spgemm_pattern_host(a, P)
        plan = planner.plan_chunks(a, P, planner.row_bytes_csr(T), system)
        return chunked_spgemm(a, P, plan)[0]

    return program.Product(call, ops, ("A", "P"), "planned on every call")
"""


def test_new_cell_needs_only_new_files(tmp_path):
    """A Laplace3D configuration on a new stencil, a product kind that plans
    on every call, its traffic, limits and a new metric: new files and new
    entries only, and a run of the new cell on the CPU at a small size."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = tmp_path / "bench"
    before = _digest(bench)
    (bench / "stencils" / "star7.py").write_text(STAR7)
    (bench / "products" / "ap_fresh.py").write_text(AP_FRESH)
    (bench / "configs" / "laplace3d.json").write_text(json.dumps(
        {"stencil": "star7", "restriction": "full_weighting", "grid": 8,
         "dof": 1, "center": 6.0, "off": -1.0, "dtype": "float32"}))
    (bench / "traffic" / "ap_fresh.json").write_text(json.dumps(
        {"product": "ap_fresh", "backend": None, "a_value_scale": [0.5, 1.5]}))
    (bench / "limits" / "laplace3d.ap_fresh.json").write_text(json.dumps(
        {"structure_diff": 0, "left_out_err": 1e-6, "value_err": 5e-5}))
    (bench / "metrics" / "products_done.py").write_text(
        "def read(run):\n    return {'value': len(run.products)}\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "laplace3d", "source": "https://example.org",
                            "file": "bench/configs/laplace3d.json", "reduced": [],
                            "why": "a test"})
    spec["workloads"].append({"name": "laplace3d.ap_fresh", "config": "laplace3d",
                              "traffic": "ap_fresh", "chips": 1, "why": "a test"})
    spec["end_to_end"].append({"name": "products_done", "unit": "count",
                               "better": "higher", "bound": 0.01,
                               "source": "host_clock"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    after = _digest(bench)
    assert all(after[p] == d for p, d in before.items())   # nothing edited

    cell = run.resolve("laplace3d.ap_fresh", root=tmp_path)
    cell.peaks = run.peaks_for("TPU v5 lite", root=tmp_path)
    system = dataclasses.replace(
        TPU_V5E, fast=dataclasses.replace(TPU_V5E.fast, capacity_bytes=20_000))
    lines = []
    result = run.measure(cell, 2**31 + 5, 0.05, False, jax.devices()[:1], system,
                         time.perf_counter(), log=lines.append)
    assert result["correct"] and result["attempted"] >= 1
    assert result["metrics"]["products_done"]["value"] == result["attempted"]
    assert "planned on every call" in lines[0]
