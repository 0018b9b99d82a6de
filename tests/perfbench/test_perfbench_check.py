"""The check that decides ``correct``: the reference comparison on its own,
then whole runs of each cell at a small size on the CPU, sound, with the
control in the program's place, and with faults planted in the timed path.
"""

import dataclasses
import time

import jax
import numpy as np
import pytest
import scipy.sparse as sp

from bench import control, operators, reference, run
from repro.core.memory_model import TPU_V5E

HPCG_SMALL = {"stencil": "box27", "restriction": "full_weighting", "grid": 8,
              "dof": 1, "coupling": [[1.0]], "center": 26.0, "off": -1.0,
              "dtype": "float32"}


def _seeded(ops, seed):
    rng = np.random.default_rng(seed)
    a = ops.A.copy()
    a.data = (a.data * rng.uniform(0.5, 1.5, a.nnz)).astype(np.float32)
    return a


def _f32_product(factors):
    out = factors[-1]
    for f in reversed(factors[:-1]):
        out = f @ out
    out.sort_indices()
    return out.indptr, out.indices, out.data


@pytest.mark.parametrize("two_hop", [False, True])
def test_reference_accepts_a_float32_product(two_hop):
    ops = operators.build(HPCG_SMALL)
    a = _seeded(ops, 1)
    factors = [ops.R, a, ops.P] if two_hop else [a, ops.P]
    got = reference.compare(_f32_product(factors), factors)
    assert got["structure_diff"] == 0
    assert got["value_err"] < 1e-6


def test_reference_rejects_a_perturbed_product():
    ops = operators.build(HPCG_SMALL)
    factors = [_seeded(ops, 2), ops.P]
    indptr, indices, data = _f32_product(factors)
    data = data.copy()
    i = int(np.argmax(np.abs(data)))
    data[i] *= 1.001
    got = reference.compare((indptr, indices, data), factors)
    assert got["structure_diff"] == 0
    assert got["value_err"] > 1e-4


def test_reference_judges_left_out_entries_by_value():
    ops = operators.build(HPCG_SMALL)
    factors = [_seeded(ops, 3), ops.P]
    indptr, indices, data = _f32_product(factors)
    dropped = sp.csr_matrix((data.copy(), indices, indptr),
                            shape=(ops.A.shape[0], ops.P.shape[1]))
    dropped.data[5] = 0.0
    dropped.eliminate_zeros()
    got = reference.compare((dropped.indptr, dropped.indices, dropped.data), factors)
    assert got["structure_diff"] == 0
    assert got["left_out_err"] > 1e-3
    assert got["value_err"] > 1e-3


def _one_row(second):
    """c = [1 * 1 + second * 1, 2]: its first entry cancels when ``second``
    is -1, and a product that stores only nonzeros may leave it out."""
    x = sp.csr_matrix(np.array([[1.0, second]], np.float64))
    y = sp.csr_matrix(np.array([[1.0, 2.0], [1.0, 0.0]], np.float32))
    return reference.compare((np.array([0, 1]), np.array([1]), np.array([2.0])), [x, y])


def test_reference_accepts_a_left_out_entry_that_cancels():
    assert _one_row(-1.0) == {"structure_diff": 0, "left_out_err": 0.0,
                              "value_err": 0.0}
    # within float32 rounding of its terms' magnitudes: passes
    within = _one_row(-1.0 + 2.0 ** -23)
    assert 0 < within["left_out_err"] < run.resolve("hpcg27.ap").limits["left_out_err"]


def test_reference_rejects_a_left_out_entry_above_rounding():
    limits = run.resolve("hpcg27.ap").limits
    got = _one_row(-1.0 + 1e-5)
    # left out at 5e-6 of its magnitude sum: inside value_err's limit, and
    # caught by the separate, far tighter limit on entries left out
    assert got["value_err"] < limits["value_err"]
    assert got["left_out_err"] > limits["left_out_err"]
    assert not reference.verdict(got, limits)


def test_reference_counts_extra_and_repeated_entries():
    ops = operators.build(HPCG_SMALL)
    factors = [_seeded(ops, 3), ops.P]
    indptr, indices, data = _f32_product(factors)
    repeated = indices.copy()
    repeated[1] = repeated[0]
    got = reference.compare((indptr, repeated, data), factors)
    assert got["structure_diff"] >= 1
    x = sp.csr_matrix(np.array([[1.0, 0.0]], np.float32))
    y = sp.csr_matrix(np.array([[1.0, 0.0], [0.0, 1.0]], np.float32))
    got = reference.compare((np.array([0, 2]), np.array([0, 1]), np.array([1.0, 0.0])),
                            [x, y])
    assert got["structure_diff"] == 1


@pytest.mark.parametrize("two_hop", [False, True])
def test_reference_rejects_a_bfloat16_product(two_hop):
    ops = operators.build(HPCG_SMALL)
    a = _seeded(ops, 4)
    factors = [ops.R, a, ops.P] if two_hop else [a, ops.P]
    got = reference.compare(reference.control_product(factors), factors)
    limits = run.resolve("hpcg27.rap" if two_hop else "hpcg27.ap").limits
    assert not reference.verdict(got, limits)
    assert got["value_err"] > 10 * limits["value_err"]


# whole runs at a small size: a fast limit small enough that every cell's
# plan still chunks, as it does at the timed size
SMALL = {"hpcg27.rap": (8, 200_000), "hpcg27.ap": (8, 100_000),
         "elasticity3d.ap_slab": (6, TPU_V5E.fast.capacity_bytes)}


def _small_run(name, wrap=None, seed=2**31 + 11):
    grid, fast = SMALL[name]
    cell = run.resolve(name)
    cell.config = {**cell.config, "grid": grid}
    cell.peaks = run.peaks_for("TPU v5 lite")
    system = dataclasses.replace(
        TPU_V5E, fast=dataclasses.replace(TPU_V5E.fast, capacity_bytes=fast))
    lines = []
    result = run.measure(cell, seed, 0.05, False, jax.devices()[:1], system,
                         time.perf_counter(), log=lines.append, wrap=wrap)
    return result, lines


@pytest.mark.parametrize("name", sorted(SMALL))
def test_small_run_of_each_cell_is_correct(name):
    result, lines = _small_run(name)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 1
    assert list(result)[-1] == "check"
    assert set(result["metrics"]) == {"spgemm_gflops", "setup_s"}
    assert any(line.startswith("plan: ") and "whole_fast" not in line.split(",")[0]
               for line in lines)
    assert lines[-len(reference.NUMBERS):] == [
        f"check {n} {v['value']!r} limit {v['limit']!r}"
        for n, v in result["check"].items()]


@pytest.mark.parametrize("name", sorted(SMALL))
@pytest.mark.parametrize("fault", sorted(control.FAULTS))
def test_control_and_faults_come_out_not_correct(name, fault):
    result, _ = _small_run(name, wrap=control.FAULTS[fault])
    assert result["attempted"] >= 1
    assert not result["correct"]
    assert result["failed"] == result["attempted"]
