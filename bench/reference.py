"""The plain reference that decides ``correct``, and its control.

The reference multiplies the benchmark's own host operators with SciPy in
float64. A product of the program is held to it by three numbers:

* ``structure_diff``: entries the program holds outside the product's
  structure (that of the product of absolute values, in which nothing
  cancels), plus entries it holds twice. Exact: limit 0.
* ``left_out_err``: over the entries of that structure that the program
  leaves out, the worst ``|c_ref| / (|X| |Y|)``: the reference's value
  against the sum of the magnitudes of the entry's terms. A backend that
  stores only nonzeros may leave out an entry whose terms cancel to 0.0 in
  float32, and only such an entry: its limit is a few float32 roundings.
* ``value_err``: over every entry of that structure, the worst
  ``|c - c_ref| / (|X| |Y|)``, the gap against that same sum of
  magnitudes, which bounds float32 accumulation error whatever the order
  of summation; an entry left out counts as 0.

The control is the reference computed in the next precision below the
configuration's float32: every operand, and the intermediate of a two-hop
product, rounded to bfloat16, accumulation in float32. It must fail.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np
import scipy.sparse as sp

NUMBERS = ("structure_diff", "left_out_err", "value_err")


def _keys(indptr, indices, n_cols: int) -> np.ndarray:
    indptr = np.asarray(indptr, np.int64)
    rows = np.repeat(np.arange(indptr.size - 1, dtype=np.int64), np.diff(indptr))
    return rows * np.int64(n_cols) + np.asarray(indices, np.int64)[: indptr[-1]]


def _chain(factors, fn):
    out = fn(factors[-1])
    for f in reversed(factors[:-1]):
        out = fn(f) @ out
    out.sort_indices()
    return out


def _align(keys, values, onto):
    """``values`` at ``keys`` placed on the sorted keys ``onto`` (0 where a
    key of ``onto`` is missing), which of ``onto`` they reach, and which of
    ``keys`` were found there."""
    out, reached = np.zeros(onto.size), np.zeros(onto.size, bool)
    if not onto.size:
        return out, reached, np.zeros(keys.size, bool)
    pos = np.clip(np.searchsorted(onto, keys), 0, onto.size - 1)
    found = onto[pos] == keys
    out[pos[found]] = values[found]
    reached[pos[found]] = True
    return out, reached, found


def compare(out, factors) -> dict:
    """The three numbers for one product. ``out`` is ``(indptr, indices,
    data)`` of the program's result (entries past ``indptr[-1]`` are
    ignored); ``factors`` are the SciPy operands, left to right, multiplied
    right to left as the two-hop product computes them."""
    indptr, indices, data = (np.asarray(a) for a in out)
    n_cols = factors[-1].shape[1]
    bound = _chain(factors, lambda f: abs(f).astype(np.float64))
    ref = _chain(factors, lambda f: f.astype(np.float64))
    want = _keys(bound.indptr, bound.indices, n_cols)
    ours = _keys(indptr, indices, n_cols)
    got, held, inside = _align(ours, np.asarray(data, np.float64)[: ours.size], want)
    expect, _, _ = _align(_keys(ref.indptr, ref.indices, n_cols), ref.data, want)
    err = np.abs(got - expect) / bound.data
    left_out = np.abs(expect[~held]) / bound.data[~held]
    return {"structure_diff": int((~inside).sum() + ours.size - np.unique(ours).size),
            "left_out_err": float(left_out.max()) if left_out.size else 0.0,
            "value_err": float(err.max()) if err.size else 0.0}


def _round(m: sp.csr_matrix, dtype) -> sp.csr_matrix:
    out = m.astype(np.float32)
    out.data = out.data.astype(dtype).astype(np.float32)
    return out


def control_product(factors, dtype=ml_dtypes.bfloat16):
    """The reference at lower precision, as ``(indptr, indices, data)``:
    operands and every intermediate rounded to ``dtype``, products
    accumulated in float32."""
    out = _round(factors[-1], dtype)
    for f in reversed(factors[:-1]):
        out = _round(_round(f, dtype) @ out, dtype)
    out.sort_indices()
    return out.indptr, out.indices, out.data


def verdict(readings: dict, limits: dict) -> bool:
    """Whether every number is within its limit."""
    return all(readings[k] <= limits[k] for k in NUMBERS)
