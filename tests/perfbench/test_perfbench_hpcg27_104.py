"""The ``hpcg27_104`` configuration builds HPCG's operators at the
published 104^3 local grid: the sizes the cell's plan and work rest on."""

import pytest

from bench import operators, registry

GRID = 104
COARSE = (GRID + 1) // 2


@pytest.mark.slow
def test_hpcg27_104_operators_have_hpcgs_published_sizes():
    """A: 104^3 rows, 27 entries per row less those cut at the boundary,
    (3 * 104 - 2)^3 in all; R and P = R^T: 52^3 coarse points, each the
    full-weighting box of its fine point 2c, 3^3 less the far faces cut,
    (3 * 52 - 1)^3 in all. About 7 s and 1.5 GB on one CPU core."""
    config = registry.read_json(registry.ROOT / "bench/configs/hpcg27_104.json")
    assert config["grid"] == GRID and "reduced_from" not in config
    ops = operators.build(config)
    assert ops.A.shape == (1_124_864, 1_124_864) == (GRID**3, GRID**3)
    assert ops.A.nnz == 29_791_000 == (3 * GRID - 2) ** 3
    assert ops.P.shape == (1_124_864, 140_608) == (GRID**3, COARSE**3)
    assert ops.P.nnz == ops.R.nnz == 3_723_875 == (3 * COARSE - 1) ** 3
    assert (ops.R.T != ops.P).nnz == 0
