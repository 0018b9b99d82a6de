"""The ESC body (``kkmem.spgemm_ranged_impl``) against SciPy and against the
previous body, which gathered, scattered and binary-searched element by
element over the product stream; that body is kept here as a reference.

The body keys the stream by ``row * n_cols + col`` in one uint32 where
``(n_rows + 1) * n_cols < 2**32`` and by the (row, col) pair otherwise; both
key layouts are pinned through ``TRACE_COUNTS``. ``indptr`` and ``indices``
must equal the reference's bit for bit; values may differ only by float32
summation order. Operands are canonical CSR (no (row, col) stored twice),
which the body's fixed-depth segmented sum needs; the symbolic phase refuses
other operands.
"""

import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp
from jaxlib.mlir import ir

from repro.core import backend_registry
from repro.core.chunking import instance_envelope
from repro.core.kkmem import (
    TRACE_COUNTS, key_layout, spgemm, spgemm_ranged, spgemm_ranged_impl,
    spgemm_symbolic_host,
)
from repro.core.planner import ChunkPlan
from repro.core.symbolic import spgemm_pattern_host, spgemm_structure_host
from repro.sparse import multigrid
from repro.sparse.csr import (
    CSR, csr_from_coo, csr_from_scipy_like, csr_row_of_entry, csr_transpose_host,
)
from repro.sparse.generators import random_uniform_degree


# ---------------------------------------------------------------------------
# the previous body, as a reference
# ---------------------------------------------------------------------------


def _reference_row_of_entry(m: CSR):
    entry = jnp.arange(m.nnz_pad, dtype=jnp.int32)
    row = jnp.searchsorted(m.indptr, entry, side="right") - 1
    return jnp.clip(row, 0, m.n_rows - 1).astype(jnp.int32)


def _reference_expand(A: CSR, B: CSR, r0, r1):
    bmax = max(B.max_row_nnz, 1)
    t = jnp.arange(A.nnz_pad, dtype=jnp.int32)
    row_a = _reference_row_of_entry(A)
    col_a = A.indices
    in_range = (col_a >= r0) & (col_a < r1) & (t < A.indptr[-1])
    b_row = jnp.clip(col_a - r0, 0, B.n_rows - 1)
    b_start = B.indptr[b_row]
    b_len = B.indptr[b_row + 1] - b_start
    j = jnp.arange(bmax, dtype=jnp.int32)
    valid = in_range[:, None] & (j[None, :] < b_len[:, None])
    src = jnp.clip(b_start[:, None] + j[None, :], 0, B.nnz_pad - 1)
    cols = jnp.where(valid, B.indices[src], 0)
    vals = jnp.where(valid, A.data[:, None] * B.data[src], 0.0)
    rows = jnp.where(valid, row_a[:, None], A.n_rows)
    return rows.reshape(-1), cols.reshape(-1), vals.reshape(-1)


def _reference_accumulate(rows, cols, vals, m: int, c_pad: int):
    order_c = jnp.argsort(cols, stable=True)
    rows_c, cols_c, vals_c = rows[order_c], cols[order_c], vals[order_c]
    order_r = jnp.argsort(rows_c, stable=True)
    rows_s, cols_s, vals_s = rows_c[order_r], cols_c[order_r], vals_c[order_r]
    valid = rows_s < m
    new_key = jnp.pad((rows_s[1:] != rows_s[:-1]) | (cols_s[1:] != cols_s[:-1]),
                      (1, 0), constant_values=True) & valid
    slot = jnp.where(valid, jnp.cumsum(new_key) - 1, c_pad)
    data = jnp.zeros(c_pad + 1, vals.dtype).at[slot].add(vals_s)[:c_pad]
    indices = jnp.zeros(c_pad + 1, jnp.int32).at[slot].max(
        jnp.where(valid, cols_s, 0).astype(jnp.int32))[:c_pad]
    out_rows = jnp.full(c_pad + 1, m, jnp.int32).at[slot].min(
        jnp.where(valid, rows_s, m).astype(jnp.int32))[:c_pad]
    indptr = jnp.searchsorted(out_rows, jnp.arange(m + 1, dtype=jnp.int32))
    return indptr.astype(jnp.int32), indices, data


@partial(jax.jit, static_argnames=("c_pad",))
def _reference_ranged(A, B, r0, r1, C_prev, c_pad):
    rows, cols, vals = _reference_expand(A, B, r0, r1)
    valid = jnp.arange(C_prev.nnz_pad) < C_prev.indptr[-1]
    rows = jnp.concatenate(
        [rows, jnp.where(valid, _reference_row_of_entry(C_prev), A.n_rows)])
    cols = jnp.concatenate([cols, jnp.where(valid, C_prev.indices, 0)])
    vals = jnp.concatenate([vals, jnp.where(valid, C_prev.data, 0.0)])
    return _reference_accumulate(rows, cols, vals, A.n_rows, c_pad)


# ---------------------------------------------------------------------------
# cases
# ---------------------------------------------------------------------------


def _prog(M, pad=0):
    M = sp.csr_matrix(M, dtype=np.float32)
    M.sort_indices()
    return csr_from_scipy_like(M.indptr, M.indices, M.data, M.shape,
                               pad_to=M.nnz + pad)


def _chunk(B, r0, r1, pad=3):
    """Rows r0:r1 of the SciPy matrix B as a program CSR, bounded like B."""
    Bc = _prog(B[r0:r1], pad)
    return CSR(Bc.indptr, Bc.indices, Bc.data, Bc.shape,
               max(int(np.diff(B.indptr).max()), 1))


def _random(rng, m, n, nnz):
    """A SciPy CSR with about ``nnz`` entries at random places."""
    r = rng.integers(0, m, nnz)
    c = rng.integers(0, n, nnz)
    v = rng.standard_normal(nnz).astype(np.float32)
    M = sp.csr_matrix((v, (r, c)), shape=(m, n))
    M.sum_duplicates()
    M.data[M.data == 0] = 1.0
    return M


def _run(A, B, r0, r1, Cp, c_pad):
    """The body and the reference on (A[:, r0:r1] x B[r0:r1] + Cp)."""
    args = (_prog(A, 2), _chunk(B, r0, r1), r0, r1, _prog(Cp, 5))
    got = spgemm_ranged(*args, c_pad)
    ref = _reference_ranged(*args, c_pad=c_pad)
    return got, [np.asarray(x) for x in ref]


def _structure(A, B, r0, r1, Cp):
    """Every (row, col) of |A[:, r0:r1]| x |B[r0:r1]| + |Cp|, and C in
    float64."""
    Ar = sp.csr_matrix(A[:, r0:r1], dtype=np.float64)
    Br = sp.csr_matrix(B[r0:r1], dtype=np.float64)
    pattern = (abs(Ar) @ abs(Br) + abs(Cp)).tocsr()
    pattern.sort_indices()
    return pattern, (Ar @ Br + sp.csr_matrix(Cp, dtype=np.float64)).tocsr()


def _check(got, ref, A, B, r0, r1, Cp, c_pad, rtol=1e-5):
    ip, ix, d = (np.asarray(x) for x in (got.indptr, got.indices, got.data))
    np.testing.assert_array_equal(ip, ref[0])
    np.testing.assert_array_equal(ix, ref[1])
    np.testing.assert_allclose(d, ref[2], rtol=rtol, atol=1e-6)
    pattern, exact = _structure(A, B, r0, r1, Cp)
    nnz = int(ip[-1])
    assert nnz == min(pattern.nnz, c_pad)
    # padding slots stay indices 0 and data 0
    assert not ix[nnz:].any() and not d[nnz:].any()
    if nnz == pattern.nnz:
        np.testing.assert_array_equal(ip, pattern.indptr)
        np.testing.assert_array_equal(ix[:nnz], pattern.indices)
        rows = np.repeat(np.arange(A.shape[0]), np.diff(ip))
        want = np.asarray(exact[rows, ix[:nnz]]).ravel()
        np.testing.assert_allclose(d[:nnz], want, rtol=rtol, atol=1e-5)


def _key_bumps(A, B, r0, r1, Cp, c_pad):
    """The TRACE_COUNTS keys that tracing the body once bumps."""
    before = dict(TRACE_COUNTS)
    jax.make_jaxpr(partial(spgemm_ranged_impl, c_pad=c_pad))(
        _prog(A, 2), _chunk(B, r0, r1), r0, r1, _prog(Cp, 5))
    return {k: v - before.get(k, 0) for k, v in TRACE_COUNTS.items()
            if v != before.get(k, 0)}


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_packed_key_matches_reference_and_scipy(seed):
    rng = np.random.default_rng(seed)
    m, k, n = 37, 29, 41
    A, B = _random(rng, m, k, 160), _random(rng, k, n, 140)
    Cp = _random(rng, m, n, 90)
    r0, r1 = 7, 23
    c_pad = _structure(A, B, r0, r1, Cp)[0].nnz + 11
    got, ref = _run(A, B, r0, r1, Cp, c_pad)
    _check(got, ref, A, B, r0, r1, Cp, c_pad)
    assert _key_bumps(A, B, r0, r1, Cp, c_pad) == {"esc_key_packed": 1}


# (n_rows, n_cols) around the packed key's limit, and past it by far
PAIR_GEOMETRIES = [(65534, 65537, "esc_key_packed"),   # (m + 1) n = 2**32 - 1
                   (65535, 65536, "esc_key_pair"),     # (m + 1) n = 2**32
                   (70001, 90001, "esc_key_pair")]


@pytest.mark.parametrize("m,n,key", PAIR_GEOMETRIES)
def test_key_layout_at_its_limit(m, n, key):
    """Entries in the last rows and columns, where a packed key would
    overflow first, and a fused C_prev."""
    rng = np.random.default_rng(m)
    k = 24
    A = _random(rng, m, k, 120)
    A = sp.vstack([A[: m - 3], _random(rng, 3, k, 40)]).tocsr()
    B = _random(rng, k, n, 60)
    B = sp.hstack([B[:, : n - 4], _random(rng, k, 4, 30)]).tocsr()
    Cp = (_random(rng, m, n, 50)
          + sp.csr_matrix(([3.0], ([m - 1], [n - 1])), shape=(m, n))).tocsr()
    c_pad = _structure(A, B, 0, k, Cp)[0].nnz + 7
    got, ref = _run(A, B, 0, k, Cp, c_pad)
    _check(got, ref, A, B, 0, k, Cp, c_pad)
    assert _key_bumps(A, B, 0, k, Cp, c_pad) == {key: 1}


@pytest.mark.parametrize("m,n,key", PAIR_GEOMETRIES + [(0, 2**32, "esc_key_pair"),
                                                     (5, 0, "esc_key_pair")])
def test_key_layout_names_the_key_the_body_sorts_on(m, n, key):
    assert "esc_key_" + key_layout(m, n) == key


@pytest.mark.parametrize("width", [1, 7, 8, 15, 16, 27])
@pytest.mark.parametrize("m,n", [(12, 10), (65535, 65536)])
def test_most_terms_an_entry_can_have(width, m, n):
    """Entry (i, j) gets a product from every one of row i's ``width`` A
    entries (A's densest row) plus C_prev's entry: the most the segmented
    sum's depth allows. Integer values, so the sum is exact in any order."""
    i, j = m - 2, n - 3
    A = sp.lil_matrix((m, width), dtype=np.float32)
    A[i, :] = np.arange(1, width + 1)
    A[0, 0] = 2.0
    B = sp.lil_matrix((width, n), dtype=np.float32)
    B[:, j] = (np.arange(width) % 5 + 1)[:, None]
    B[:, 1] = 1.0
    Cp = sp.lil_matrix((m, n), dtype=np.float32)
    Cp[i, j] = 7.0
    Cp[1, 2] = -1.0
    A, B, Cp = A.tocsr(), B.tocsr(), Cp.tocsr()
    c_pad = _structure(A, B, 0, width, Cp)[0].nnz + 3
    got, ref = _run(A, B, 0, width, Cp, c_pad)
    _check(got, ref, A, B, 0, width, Cp, c_pad)
    ip, ix, d = (np.asarray(x) for x in (got.indptr, got.indices, got.data))
    at = ip[i] + int(np.flatnonzero(ix[ip[i]:ip[i + 1]] == j)[0])
    expect = float(np.dot(np.arange(1, width + 1), np.arange(width) % 5 + 1)) + 7.0
    assert d[at] == expect


def test_cancelling_terms_stay_as_entries():
    """Terms that sum to 0.0, among the products and against C_prev, leave a
    stored 0.0 where the structure has an entry."""
    A = sp.csr_matrix(np.array([[1, 1, 0], [0, 2, 0], [0, 0, 0]], np.float32))
    B = sp.csr_matrix(np.array([[1, 3, 0, 0], [-1, 0, 2, 0], [0, 0, 0, 5]],
                               np.float32))
    Cp = sp.csr_matrix(np.array([[0, -3, 0, 0], [0, 0, -4, 0], [0, 0, 0, 0]],
                                np.float32))
    got, ref = _run(A, B, 0, 3, Cp, 8)
    _check(got, ref, A, B, 0, 3, Cp, 8)
    ip, ix, d = (np.asarray(x) for x in (got.indptr, got.indices, got.data))
    assert ip.tolist() == [0, 3, 5, 5]
    assert ix[:5].tolist() == [0, 1, 2, 0, 2]
    assert d[:5].tolist() == [0.0, 0.0, 2.0, -2.0, 0.0]


@pytest.mark.parametrize("short", [1, 9, 40])
def test_c_pad_overflow_drops_the_last_entries(short):
    """With ``c_pad`` below C's nnz, the first ``c_pad`` entries in (row, col)
    order are kept and the rest dropped, as the reference's drop bucket did."""
    rng = np.random.default_rng(short)
    A, B = _random(rng, 30, 20, 120), _random(rng, 20, 25, 100)
    Cp = _random(rng, 30, 25, 40)
    c_pad = _structure(A, B, 0, 20, Cp)[0].nnz - short
    got, ref = _run(A, B, 0, 20, Cp, c_pad)
    _check(got, ref, A, B, 0, 20, Cp, c_pad)
    assert int(got.indptr[-1]) == c_pad


def test_stream_shorter_than_c_pad():
    """``spgemm`` without C_prev: the product stream (2 slots) is shorter
    than the output (64), which the body pads."""
    A = _prog(sp.csr_matrix(np.array([[0, 2.0], [0, 0]], np.float32)))
    B = _prog(sp.csr_matrix(np.array([[1.0, 0], [0, 3.0]], np.float32)))
    C = spgemm(A, B, c_pad=64)
    assert np.asarray(C.indptr).tolist() == [0, 1, 1]
    assert np.asarray(C.indices)[:2].tolist() == [1, 0]
    assert np.asarray(C.data)[:2].tolist() == [6.0, 0.0]


# ---------------------------------------------------------------------------
# canonical operands
# ---------------------------------------------------------------------------

# 3 x 4 CSRs whose row 1 breaks canonical order: (1, 2) stored twice, as
# csr_from_coo(sum_duplicates=False) keeps it, and columns out of order
NOT_CANONICAL = {
    "repeat": ([0, 1, 3, 4], [0, 2, 2, 3]),
    "unsorted": ([0, 1, 3, 4], [0, 3, 1, 2]),
}
SYMBOLIC_ENTRIES = [spgemm_symbolic_host, spgemm_structure_host,
                    spgemm_pattern_host]


@pytest.mark.parametrize("entry", SYMBOLIC_ENTRIES)
@pytest.mark.parametrize("side", ["A", "B"])
@pytest.mark.parametrize("fault", sorted(NOT_CANONICAL))
def test_symbolic_phase_refuses_non_canonical_operands(entry, side, fault):
    """The body's segmented sum is deep enough only when no (row, col) is
    stored twice, so the symbolic phase that sizes every numeric call
    refuses such operands; e.g. I x B with a repeated B entry."""
    indptr, cols = NOT_CANONICAL[fault]
    bad = csr_from_scipy_like(indptr, cols, [1.0, 2.0, 3.0, 4.0], (3, 4))
    if fault == "repeat":
        coo = csr_from_coo([0, 1, 1, 2], cols, [1.0, 2.0, 3.0, 4.0], (3, 4),
                           sum_duplicates=False)
        np.testing.assert_array_equal(np.asarray(coo.indices), cols)
    A, B = (bad, _prog(sp.identity(4))) if side == "A" else \
        (_prog(sp.identity(3)), bad)
    with pytest.raises(ValueError, match=f"{side} is not canonical CSR: row 1"):
        entry(A, B)


def test_canonical_builders_pass_the_symbolic_phase():
    """What the host builders make is canonical: the generator's wide path
    (sampled with replacement past 2**20 columns, then coalesced), COO with
    repeats summed, a transpose, rows that end on a high column before the
    next row starts low, and no entries among padding."""
    wide = random_uniform_degree(6, (1 << 20) + 1, 40, seed=3)
    assert wide.max_row_nnz <= 40
    spgemm_structure_host(wide, _prog(sp.identity(wide.n_cols)))
    summed = csr_from_coo([0, 1, 1, 2], [0, 2, 2, 3], [1.0, 2.0, 3.0, 4.0],
                          (3, 4))
    assert np.asarray(summed.data).tolist() == [1.0, 5.0, 4.0]
    rising = _prog(np.array([[0, 0, 1], [1, 0, 1], [1, 1, 0]], np.float32))
    empty = csr_from_scipy_like([0, 0, 0], [], [], (2, 3), pad_to=3)
    for m in (summed, csr_transpose_host(summed), rising, empty):
        ws = spgemm_symbolic_host(m, _prog(sp.identity(m.n_cols)))
        assert ws.c_nnz == int(m.indptr[-1])


# ---------------------------------------------------------------------------
# row ids by counting
# ---------------------------------------------------------------------------

INDPTRS = [
    ([0, 0, 0, 2, 2, 5, 5, 5], 5),     # empty leading, middle, trailing rows
    ([0, 0, 0, 2, 2, 5, 5, 5], 9),     # the same with padding entries
    ([0, 3, 3, 4], 4),                 # indptr[-1] == nnz_pad
    ([0, 0, 0], 4),                    # no entries at all
    ([0, 1], 1),                       # one row, one entry
    ([0, 2, 4, 6, 6], 8),
]


@pytest.mark.parametrize("indptr,nnz_pad", INDPTRS)
def test_row_of_entry_counts_as_the_search_did(indptr, nnz_pad):
    ptr = jnp.asarray(indptr, jnp.int32)
    m = CSR(ptr, jnp.zeros(nnz_pad, jnp.int32), jnp.zeros(nnz_pad, jnp.float32),
            (len(indptr) - 1, 3), 0)
    np.testing.assert_array_equal(np.asarray(csr_row_of_entry(m)),
                                  np.asarray(_reference_row_of_entry(m)))


def test_row_of_entry_random_and_batched():
    rng = np.random.default_rng(5)
    mats = [_prog(_random(rng, 40, 9, 70), pad=6) for _ in range(3)]
    for m in mats:
        np.testing.assert_array_equal(np.asarray(csr_row_of_entry(m)),
                                      np.asarray(_reference_row_of_entry(m)))


# ---------------------------------------------------------------------------
# regression guard: no element-wise gather or scatter over the stream
# ---------------------------------------------------------------------------


def _walk(ops):
    for op in ops:
        o = op.operation
        yield o
        for region in o.regions:
            for block in region.blocks:
                yield from _walk(block.operations)


def _index_counts(lowered) -> dict:
    """Number of indices of every gather and scatter in the StableHLO."""
    module = lowered.compiler_ir("stablehlo")
    counts = {"stablehlo.gather": [], "stablehlo.scatter": []}
    with module.context:
        for o in _walk(module.body.operations):
            if o.name not in counts:
                continue
            shape = list(ir.RankedTensorType(o.operands[1].type).shape)
            dims = re.search(r"index_vector_dim = (\d+)", str(o.attributes))
            vec = int(dims.group(1)) if dims else len(shape)
            counts[o.name].append(
                int(np.prod([s for a, s in enumerate(shape) if a != vec])))
    return counts


def test_scan_core_has_no_stream_length_gather_or_scatter():
    A, _, P = multigrid.problem("brick3d", 6)
    thirds = (0, A.n_rows // 3, 2 * A.n_rows // 3, A.n_rows)
    plan = ChunkPlan("chunk2", thirds, (0, P.n_rows // 2, P.n_rows), 0.0, 0.0)
    env = instance_envelope(A, P, plan)
    target = backend_registry.get("scan").audit_trace(A, P, plan, env.c_pad, env)
    As, Bs = target.args[0], target.args[1]
    nnz_pad = As.indices.shape[-1]
    n = nnz_pad * Bs.max_row_nnz + env.c_pad
    assert len({n, nnz_pad, env.c_pad, As.n_rows + 1}) == 4
    counts = _index_counts(jax.jit(target.fn).lower(*target.args))
    assert n not in counts["stablehlo.gather"], counts
    assert n not in counts["stablehlo.scatter"], counts
    # B's rows come as one column gather per A entry, from its ELL tables
    assert nnz_pad in counts["stablehlo.gather"], counts

    # the guard sees what it guards against: the reference body at the
    # same shapes gathers and scatters over the whole stream
    def one_step(Ai, Bc, C):
        return _reference_ranged(Ai, Bc, 0, Bc.n_rows, C, c_pad=env.c_pad)

    ai, bc = (jax.tree.map(lambda x: x[0], s) for s in (As, Bs))
    c0 = jax.tree.map(lambda x: x[0], target.args[4])
    ref = _index_counts(jax.jit(one_step).lower(ai, bc, c0))
    assert n in ref["stablehlo.gather"] and n in ref["stablehlo.scatter"]
