"""Factor-2 full-weighting restriction: coarse point ``c`` sits on fine
point ``2c`` and takes its 3 x 3 x 3 box with weights that are products of
(1/2, 1, 1/2) per axis; each of ``dof`` unknowns is restricted alone."""

import numpy as np

from bench import operators


def matrix(config: dict):
    g, dof = int(config["grid"]), int(config["dof"])
    gc = (g + 1) // 2
    offsets = operators.box_offsets()
    weights = np.prod(np.array([0.5, 1.0, 0.5])[offsets + 1], axis=1)
    rows, cols, vals = operators.grid_coo(operators.grid_points(gc) * 2, g,
                                          offsets, weights)
    if dof > 1:
        rows, cols, vals = operators.expand_dof(rows, cols, vals, np.eye(dof))
    return operators.csr(rows, cols, vals, (gc ** 3 * dof, g ** 3 * dof))
