"""The benchmark's operators and work counter against brute force."""

import numpy as np
import pytest
import scipy.sparse as sp

from bench import operators, work


def _random(rng, m, n, density):
    d = (rng.random((m, n)) < density) * rng.standard_normal((m, n))
    return sp.csr_matrix(d.astype(np.float32))


def _brute(factors):
    """Multiply-adds and result structures by dense loops, hop by hop."""
    dense = [np.asarray(f.todense()) != 0 for f in factors]
    madds, nbytes, y = 0, 0, dense[-1]
    for x in reversed(dense[:-1]):
        z = np.zeros((x.shape[0], y.shape[1]), bool)
        for i in range(x.shape[0]):
            for k in range(x.shape[1]):
                if x[i, k]:
                    madds += int(y[k].sum())
                    z[i] |= y[k]
        nbytes += sum(int(m.sum()) * 8 + (m.shape[0] + 1) * 4 for m in (x, y, z))
        y = z
    return madds, nbytes


@pytest.mark.parametrize("hops,seed", [(1, 0), (1, 1), (2, 2), (2, 3)])
def test_work_counter_matches_brute_force(hops, seed):
    rng = np.random.default_rng(seed)
    dims = rng.integers(3, 12, hops + 2)
    factors = [_random(rng, int(dims[i]), int(dims[i + 1]), 0.3)
               for i in range(hops + 1)]
    got = work.product_work(factors)
    madds, nbytes = _brute(factors)
    assert got["multiply_adds"] == madds
    assert got["flops"] == 2 * madds
    assert got["bytes"] == nbytes


@pytest.mark.parametrize("dof", [1, 3])
def test_operators_shape_and_rows(dof):
    config = {"stencil": "box27", "restriction": "full_weighting", "grid": 6,
              "dof": dof, "coupling": np.eye(dof).tolist(), "center": 26.0,
              "off": -1.0, "dtype": "float32"}
    ops = operators.build(config)
    n = 6 ** 3 * dof
    assert ops.A.shape == (n, n)
    assert ops.R.shape == (3 ** 3 * dof, n)
    assert (ops.P != ops.R.T).nnz == 0
    row_nnz = np.diff(ops.A.indptr)
    assert row_nnz.max() == 27          # identity coupling: one entry per block
    # an interior point couples to all 26 neighbours and itself
    interior = np.ravel_multi_index((2, 2, 2), (6, 6, 6)) * dof
    assert row_nnz[interior] == row_nnz.max()
    # full weighting: an interior coarse point's weights sum to 2^3
    coarse = np.ravel_multi_index((1, 1, 1), (3, 3, 3)) * dof
    assert ops.R[coarse].sum() == pytest.approx(8.0)


def test_elasticity_rows_hold_81_entries():
    coupling = [[2.0, 0.3, 0.2], [0.3, 2.0, 0.3], [0.2, 0.3, 2.0]]
    ops = operators.build({"stencil": "box27", "restriction": "full_weighting",
                           "grid": 5, "dof": 3, "coupling": coupling,
                           "center": 26.0, "off": -1.0, "dtype": "float32"})
    assert np.diff(ops.A.indptr).max() == 81
