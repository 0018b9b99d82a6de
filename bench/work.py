"""The work a product requires, counted from its operands' structure alone,
whatever backend computes it.

Multiply-adds of ``X @ Y``: for every stored ``X[i, k]``, one per stored
entry of ``Y``'s row ``k``. Bytes: each operand read once and the result
written once, as float32 values, int32 column indices and int32 row
pointers. A two-hop product ``R @ (A @ P)`` counts both hops, the
intermediate written by the first and read by the second.
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp

VALUE_BYTES = 4
INDEX_BYTES = 4


def multiply_adds(X: sp.csr_matrix, Y: sp.csr_matrix) -> int:
    y_row_nnz = np.diff(Y.indptr).astype(np.int64)
    return int(y_row_nnz[X.indices[: X.nnz]].sum())


def csr_bytes(M: sp.csr_matrix) -> int:
    return M.nnz * (VALUE_BYTES + INDEX_BYTES) + (M.shape[0] + 1) * INDEX_BYTES


def pattern(X: sp.csr_matrix, Y: sp.csr_matrix) -> sp.csr_matrix:
    """Structure of ``X @ Y``: the product of the absolute values, in which
    no entry can cancel."""
    Z = abs(X).astype(np.float64) @ abs(Y).astype(np.float64)
    Z.sort_indices()
    return Z


def product_work(factors) -> dict:
    """Multiply-adds, flops (two per multiply-add) and required bytes of the
    product of ``factors``, left to right, computed right to left one hop at
    a time: ``[A, P]`` is one hop, ``[R, A, P]`` two (``A @ P``, then
    ``R @ T``)."""
    madds = nbytes = 0
    Y = factors[-1]
    for X in reversed(factors[:-1]):
        Z = pattern(X, Y)
        madds += multiply_adds(X, Y)
        nbytes += csr_bytes(X) + csr_bytes(Y) + csr_bytes(Z)
        Y = Z
    return {"multiply_adds": madds, "flops": 2 * madds, "bytes": nbytes}
