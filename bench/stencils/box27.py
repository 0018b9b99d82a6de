"""The 27-point box stencil: ``center`` on the point and ``off`` on its 26
neighbours (truncated at the boundary), ``grid`` points per edge, ``dof``
unknowns per point coupled by the ``coupling`` block. HPCG's operator is
``dof`` 1; Elasticity3D's is ``dof`` 3 (81 entries per row)."""

import numpy as np

from bench import operators


def matrix(config: dict):
    g, dof = int(config["grid"]), int(config["dof"])
    offsets = operators.box_offsets()
    weights = np.where((offsets == 0).all(axis=1), float(config["center"]),
                       float(config["off"]))
    rows, cols, vals = operators.grid_coo(operators.grid_points(g), g, offsets, weights)
    if dof > 1:
        block = np.asarray(config["coupling"], np.float64).reshape(dof, dof)
        rows, cols, vals = operators.expand_dof(rows, cols, vals, block)
    n = g ** 3 * dof
    return operators.csr(rows, cols, vals, (n, n))
