"""The benchmark's own operators on a structured 3-D grid, built with NumPy
and SciPy from a configuration file. The configuration's ``stencil`` names
``bench/stencils/<stencil>.py`` and its ``restriction``
``bench/restrictions/<restriction>.py``; each has a ``matrix(config)``
that returns a SciPy CSR, built from the helpers here. Nothing comes from
the program under test, so the reference product and the work counter see
the same matrices the program is handed, from code the program cannot
change.
"""

from __future__ import annotations

import dataclasses
from pathlib import Path

import numpy as np
import scipy.sparse as sp

from bench import registry


@dataclasses.dataclass(frozen=True)
class Operators:
    """Host CSR (float32 values, sorted indices) of one multigrid level."""

    A: sp.csr_matrix   # fine operator, n x n
    R: sp.csr_matrix   # restriction, n_coarse x n
    P: sp.csr_matrix   # prolongation, R^T


def box_offsets() -> np.ndarray:
    """The 27 offsets of a point's 3 x 3 x 3 box, the point itself included."""
    ax = np.arange(-1, 2)
    grids = np.meshgrid(ax, ax, ax, indexing="ij")
    return np.stack([g.ravel() for g in grids], axis=1)


def grid_points(grid: int) -> np.ndarray:
    """Coordinates of the ``grid**3`` points, in row order."""
    return np.stack(np.unravel_index(np.arange(grid ** 3), (grid,) * 3), axis=1)


def expand_dof(rows, cols, vals, block: np.ndarray):
    """Each scalar entry becomes a dof x dof block scaled by ``block``."""
    dof = block.shape[0]
    bi, bj = np.nonzero(block)
    rows = (rows[:, None] * dof + bi[None, :]).ravel()
    cols = (cols[:, None] * dof + bj[None, :]).ravel()
    vals = (vals[:, None] * block[bi, bj][None, :]).ravel()
    return rows, cols, vals


def grid_coo(points: np.ndarray, grid: int, offsets: np.ndarray, weights):
    """(row, fine column, weight) of every in-grid neighbour ``points + off``."""
    rows, cols, vals = [], [], []
    for off, w in zip(offsets, weights):
        nbr = points + off[None, :]
        ok = ((nbr >= 0) & (nbr < grid)).all(axis=1)
        rows.append(np.nonzero(ok)[0])
        cols.append(np.ravel_multi_index(tuple(nbr[ok].T), (grid,) * 3))
        vals.append(np.full(int(ok.sum()), w))
    return np.concatenate(rows), np.concatenate(cols), np.concatenate(vals)


def csr(rows, cols, vals, shape) -> sp.csr_matrix:
    m = sp.csr_matrix((vals.astype(np.float32), (rows, cols)), shape=shape)
    m.sum_duplicates()
    m.sort_indices()
    return m


def build(config: dict, root: Path = registry.ROOT) -> Operators:
    """A, R and P = R^T of ``config``, in float32."""
    if config["dtype"] != "float32":
        raise ValueError(f"the benchmark's operators are float32, not {config['dtype']!r}")
    A = registry.load("stencils", config["stencil"], root).matrix(config)
    R = registry.load("restrictions", config["restriction"], root).matrix(config)
    P = R.T.tocsr()
    P.sort_indices()
    return Operators(A=A, R=R, P=P)
