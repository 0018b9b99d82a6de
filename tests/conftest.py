"""Shared test fixtures and hypothesis strategies (test modules import
``given``, ``settings`` and ``st`` from here).

NOTE: no XLA_FLAGS here on purpose — tests must see exactly 1 CPU device
(only launch/dryrun.py requests 512 placeholder devices).
"""

import jax
import numpy as np
import pytest
import jax.numpy as jnp  # noqa: F401  (re-exported convenience for tests)
# re-exported: test modules import given/settings/st from conftest
from hypothesis import given, settings, strategies as st  # noqa: F401

from repro.sparse.csr import CSR, csr_from_dense


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_executables_between_modules():
    """Drop jit caches at module boundaries.

    The full suite compiles hundreds of executables in one process (arch
    smokes, the conformance matrix, every chunked backend); on the 1-CPU CI
    box the accumulated XLA compile state eventually segfaults the CPU
    compiler mid-suite. No module relies on warm caches from a previous
    module — the trace-count pins all measure deltas within a single test.
    """
    yield
    jax.clear_caches()


def random_dense(rng, m, n, density):
    d = (rng.random((m, n)) < density) * rng.standard_normal((m, n))
    return d.astype(np.float32)


def random_csr(rng, m, n, density, pad_extra=0) -> CSR:
    d = random_dense(rng, m, n, density)
    nnz = int((d != 0).sum())
    return csr_from_dense(d, pad_to=nnz + pad_extra)


def csr_pair_cases(n_examples=8, max_dim=24, seed=0):
    """Deterministic (A, B) pairs with compatible inner dims, for
    parametrized tests."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_examples):
        m, k, n = (int(v) for v in rng.integers(1, max_dim + 1, 3))
        da, db = rng.uniform(0.05, 0.6, 2)
        out.append(
            (random_csr(rng, m, k, da, pad_extra=int(rng.integers(0, 8))),
             random_csr(rng, k, n, db, pad_extra=int(rng.integers(0, 8))))
        )
    return out


@st.composite
def csr_pair(draw, max_dim=24):
    """(A, B) with compatible inner dims for C = A x B."""
    m = draw(st.integers(1, max_dim))
    k = draw(st.integers(1, max_dim))
    n = draw(st.integers(1, max_dim))
    seed = draw(st.integers(0, 2**31 - 1))
    da = draw(st.floats(0.05, 0.6))
    db = draw(st.floats(0.05, 0.6))
    rng = np.random.default_rng(seed)
    return (random_csr(rng, m, k, da, pad_extra=draw(st.integers(0, 7))),
            random_csr(rng, k, n, db, pad_extra=draw(st.integers(0, 7))))


def live_pairs(A: CSR, p_ac, p_b) -> np.ndarray:
    """``[n_strips, n_chunks]``: whether strip i of A (rows ``p_ac[i]`` to
    ``p_ac[i + 1]``) holds an entry whose column is a row of B chunk j
    (``p_b[j]`` to ``p_b[j + 1]``), counted in NumPy from A's arrays."""
    ip, ix = np.asarray(A.indptr), np.asarray(A.indices)
    rows = np.repeat(np.arange(A.n_rows), np.diff(ip))
    strip = np.searchsorted(p_ac, rows, side="right") - 1
    chunk = np.searchsorted(p_b, ix[: ip[-1]], side="right") - 1
    live = np.zeros((len(p_ac) - 1, len(p_b) - 1), bool)
    live[strip, chunk] = True
    return live


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def assert_close(a, b, atol=1e-4, rtol=1e-4, msg=""):
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    np.testing.assert_allclose(a, b, atol=atol, rtol=rtol, err_msg=msg)
