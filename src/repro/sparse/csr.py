"""Static-shape padded CSR container (a JAX pytree) + conversions.

Conventions (chosen so every array has a *static* shape — a JAX requirement):
  * ``indptr``  : int32[n_rows + 1]  -- standard CSR row pointers. ``indptr[-1]`` is the
                  true nnz; entries past it in ``indices``/``data`` are padding.
  * ``indices`` : int32[nnz_pad]     -- column index per entry; padding entries are 0.
  * ``data``    : dtype[nnz_pad]     -- value per entry; padding entries are 0.0.
  * rows are contiguous (no per-row padding); all padding lives in the tail.
  * canonical: columns strictly increase within a row, so no (row, col) is stored
    twice. The host builders here produce it; SpGEMM requires it of its operands
    (the symbolic phase, ``repro.core.symbolic``, checks).
  * ``shape``, ``max_row_nnz`` are static metadata (pytree aux), so jit retraces only
    when the padded geometry changes, never per-value.

``max_row_nnz`` upper-bounds the densest row and sizes the per-row expansion buffers in
the KKMEM numeric phase (repro.core.kkmem).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("indptr", "indices", "data"),
    meta_fields=("shape", "max_row_nnz"),
)
@dataclasses.dataclass(frozen=True)
class CSR:
    """Padded compressed-sparse-row matrix."""

    indptr: jax.Array   # int32[n_rows + 1]
    indices: jax.Array  # int32[nnz_pad]
    data: jax.Array     # dtype[nnz_pad]
    shape: tuple        # (n_rows, n_cols), static
    max_row_nnz: int    # static upper bound on nnz of any row

    @property
    def n_rows(self) -> int:
        return self.shape[0]

    @property
    def n_cols(self) -> int:
        return self.shape[1]

    @property
    def nnz_pad(self) -> int:
        """Padded capacity (static)."""
        return self.indices.shape[0]

    def nnz(self):
        """True nnz (traced value under jit; concrete int outside)."""
        return self.indptr[-1]

    @property
    def dtype(self):
        return self.data.dtype

    def nbytes(self) -> int:
        """Padded byte footprint — what a memory level must actually hold."""
        return (
            self.indptr.size * self.indptr.dtype.itemsize
            + self.indices.size * self.indices.dtype.itemsize
            + self.data.size * self.data.dtype.itemsize
        )

    def row_lengths(self) -> jax.Array:
        return self.indptr[1:] - self.indptr[:-1]

    def astype(self, dtype) -> "CSR":
        return CSR(self.indptr, self.indices, self.data.astype(dtype), self.shape, self.max_row_nnz)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (
            f"CSR(shape={self.shape}, nnz_pad={self.nnz_pad}, "
            f"max_row_nnz={self.max_row_nnz}, dtype={self.dtype})"
        )


def csr_from_scipy_like(indptr, indices, data, shape, pad_to: int | None = None,
                        dtype=jnp.float32) -> CSR:
    """Build a CSR from host arrays (NumPy), padding the tail to ``pad_to``."""
    indptr = np.asarray(indptr, dtype=np.int32)
    indices = np.asarray(indices, dtype=np.int32)
    data = np.asarray(data)
    nnz = int(indptr[-1])
    cap = int(pad_to) if pad_to is not None else nnz
    if cap < nnz:
        raise ValueError(f"pad_to={cap} < nnz={nnz}")
    cap = max(cap, 1)   # zero-capacity arrays break XLA gathers downstream
    pad = cap - nnz
    if pad:
        indices = np.concatenate([indices[:nnz], np.zeros(pad, np.int32)])
        data = np.concatenate([data[:nnz], np.zeros(pad, data.dtype)])
    else:
        indices, data = indices[:nnz], data[:nnz]
    row_len = indptr[1:] - indptr[:-1]
    max_row = int(row_len.max()) if len(row_len) else 0
    return CSR(
        indptr=jnp.asarray(indptr),
        indices=jnp.asarray(indices),
        data=jnp.asarray(data, dtype=dtype),
        shape=(int(shape[0]), int(shape[1])),
        max_row_nnz=max_row,
    )


def csr_from_coo(rows, cols, vals, shape, pad_to: int | None = None, dtype=jnp.float32,
                 sum_duplicates: bool = True) -> CSR:
    """Host-side COO -> CSR (sorts by (row, col), optionally coalescing duplicates).

    ``sum_duplicates=False`` is for callers whose (row, col) are already unique;
    it keeps repeats as they are, which no SpGEMM entry point accepts."""
    rows = np.asarray(rows, dtype=np.int64)
    cols = np.asarray(cols, dtype=np.int64)
    vals = np.asarray(vals, dtype=np.float64)
    n_rows, n_cols = int(shape[0]), int(shape[1])
    key = rows * n_cols + cols
    order = np.argsort(key, kind="stable")
    key, vals = key[order], vals[order]
    if sum_duplicates and key.size:
        uniq, inv = np.unique(key, return_inverse=True)
        acc = np.zeros(uniq.size, np.float64)
        np.add.at(acc, inv, vals)
        key, vals = uniq, acc
    out_rows = key // n_cols
    out_cols = key % n_cols
    indptr = np.zeros(n_rows + 1, np.int64)
    np.add.at(indptr, out_rows + 1, 1)
    indptr = np.cumsum(indptr)
    return csr_from_scipy_like(indptr, out_cols, vals, (n_rows, n_cols), pad_to, dtype)


def csr_from_dense(dense, pad_to: int | None = None) -> CSR:
    """Host-side dense -> CSR."""
    dense = np.asarray(dense)
    rows, cols = np.nonzero(dense)
    return csr_from_coo(rows, cols, dense[rows, cols], dense.shape, pad_to,
                        dtype=jax.dtypes.canonicalize_dtype(dense.dtype),
                        sum_duplicates=False)


def csr_to_dense(m: CSR) -> jax.Array:
    """JAX-traceable densify (scatter-add; padding entries carry data==0 so they only
    ever add zero into column 0)."""
    n_rows, n_cols = m.shape
    entry = jnp.arange(m.nnz_pad, dtype=jnp.int32)
    row = jnp.searchsorted(m.indptr, entry, side="right") - 1
    row = jnp.clip(row, 0, n_rows - 1)
    dense = jnp.zeros((n_rows, n_cols), m.dtype)
    return dense.at[row, m.indices].add(m.data)


def csr_row_of_entry(m: CSR) -> jax.Array:
    """Row id of every padded entry (padding maps to the last row; its data is 0).

    Counted, not searched: a one at each row end ``indptr[1:]``, summed up to
    each entry — one update per row instead of a binary search per entry."""
    ends = jnp.zeros(m.nnz_pad, jnp.int32).at[m.indptr[1:]].add(1, mode="drop")
    return jnp.clip(jnp.cumsum(ends), 0, m.n_rows - 1).astype(jnp.int32)


def csr_rows_host(m: CSR, r0: int, r1: int, nnz_cap: int | None = None,
                  rows: int | None = None, max_row_nnz: int | None = None) -> CSR:
    """Rows ``m[r0:r1, :]`` of a CSR whose fields are host (NumPy) arrays, as
    a new host CSR padded to ``nnz_cap`` entries (at least one) and ``rows``
    rows, with row bound ``max_row_nnz``; each defaults to the slice's own.
    A target smaller than the slice raises, as ``csr_pad_to`` does."""
    ptr = np.asarray(m.indptr)
    s, e = int(ptr[r0]), int(ptr[r1])
    nnz, n = e - s, r1 - r0
    longest = int(np.diff(ptr[r0 : r1 + 1]).max()) if n else 0
    cap = nnz if nnz_cap is None else int(nnz_cap)
    rows = n if rows is None else int(rows)
    mrn = longest if max_row_nnz is None else int(max_row_nnz)
    if cap < nnz or rows < n or mrn < longest:
        raise ValueError(
            f"rows {r0}:{r1} hold {nnz} entries in {n} rows, the longest "
            f"{longest}; the target is nnz_cap={cap} rows={rows} max_row_nnz={mrn}")
    indptr = np.full(rows + 1, nnz, np.int32)
    indptr[: n + 1] = ptr[r0 : r1 + 1] - s
    indices = np.zeros(max(cap, 1), np.int32)
    indices[:nnz] = np.asarray(m.indices)[s:e]
    values = np.asarray(m.data)
    data = np.zeros(max(cap, 1), values.dtype)
    data[:nnz] = values[s:e]
    return CSR(indptr, indices, data, (rows, m.shape[1]), mrn)


def csr_select_rows_host(m: CSR, r0: int, r1: int, pad_to: int | None = None) -> CSR:
    """Row slice m[r0:r1, :] as a new CSR on the device, sliced on the host."""
    return jax.device_put(csr_rows_host(jax.device_get(m), r0, r1, pad_to))


def _union_bsr_caps(a: tuple, b: tuple) -> tuple:
    """Elementwise max of two block-cap tuples. Mixing a block-capped
    envelope with an uncapped one (or two different block sizes) is a caller
    bug — the union would either silently drop the caps or silently change
    the block geometry — so both fail loudly."""
    if not a and not b:
        return ()
    if not a or not b:
        raise ValueError(
            "cannot union a block-capped envelope with an uncapped one; "
            "build every instance envelope with the same block_size")
    if a[0] != b[0]:
        raise ValueError(f"block_size mismatch in envelope union: {a[0]} vs {b[0]}")
    return (a[0], *(max(x, y) for x, y in zip(a[1:], b[1:])))


@dataclasses.dataclass(frozen=True)
class GeometryEnvelope:
    """Padded geometry that a chunked-SpGEMM executable is compiled for.

    Every field is host-static, so the envelope doubles as a hashable compile
    key: two (A, B) instances with the same envelope (and plan) run through the
    same jitted scan without retracing. ``union`` over a batch of per-instance
    envelopes yields the smallest geometry that fits them all — the fix for
    heterogeneous-structure batches, where per-instance padding caps used to
    make ``csr_stack`` reject the batch.

    ``chunk_rows``/``strip_rows`` derive from the plan's row partitions (shared
    across a batch by construction); the nnz caps and ``max_row_nnz`` bounds
    are per-instance quantities that the envelope maxes over the batch.

    The *output-cap* fields (``c_nnz_cap``, ``c_max_row_nnz``) come from the
    symbolic phase (``repro.core.symbolic``): they bound the structure of C
    itself, which is what the sparse-output backend sizes its fixed-capacity
    CSR accumulator scratch with (``c_pad`` is the per-strip capacity that
    scratch is allocated to). A value of 0 means "not computed" (envelopes
    predating the symbolic fold-in); the algebra below absorbs 0 into any
    computed value under union and preserves it under quantization, so legacy
    envelopes stay valid compile keys.
    """

    a_shape: tuple      # (m, k) of every A instance
    b_shape: tuple      # (k, n) of every B instance
    a_nnz_cap: int      # padded nnz capacity of the whole-A operand (KNL)
    a_max_row_nnz: int  # bound on any A row (sizes nothing directly; meta)
    b_max_row_nnz: int  # bound on any B row (sizes the expansion buffer)
    chunk_rows: int     # rows every staged B chunk is padded to
    chunk_nnz_cap: int  # nnz capacity every staged B chunk is padded to
    strip_rows: int     # rows every staged A/C strip is padded to
    strip_nnz_cap: int  # nnz capacity every staged A strip is padded to
    c_pad: int          # output capacity (>= exact symbolic nnz of any C strip)
    dtype: str          # value dtype name ("float32", ...)
    c_nnz_cap: int = 0      # whole-C structure capacity (symbolic; 0 = unset)
    c_max_row_nnz: int = 0  # densest C row bound (symbolic; 0 = unset)
    # Block-geometry caps for block-structured (BSR) backends, as the tuple
    # (block_size, nbl_a_cap, nbl_b_cap, nc_cap, u_cap) from
    # ``repro.core.symbolic.bsr_plan_caps`` — already quantized there, so the
    # tuple IS the backend's compile key. ``()`` = not computed: block
    # analysis is opt-in (costs a host pass), and an uncapped envelope prices
    # block backends at infinity in the planner, excluding them from ``auto``.
    bsr_caps: tuple = ()

    def _check_compatible(self, other: "GeometryEnvelope") -> None:
        if (self.a_shape != other.a_shape or self.b_shape != other.b_shape
                or self.dtype != other.dtype):
            raise ValueError(
                "incompatible envelopes: "
                f"{self.a_shape}x{self.b_shape}/{self.dtype} vs "
                f"{other.a_shape}x{other.b_shape}/{other.dtype}"
            )

    def union(self, other: "GeometryEnvelope") -> "GeometryEnvelope":
        """Smallest envelope covering both (same shapes/dtype required)."""
        self._check_compatible(other)
        return GeometryEnvelope(
            a_shape=self.a_shape, b_shape=self.b_shape,
            a_nnz_cap=max(self.a_nnz_cap, other.a_nnz_cap),
            a_max_row_nnz=max(self.a_max_row_nnz, other.a_max_row_nnz),
            b_max_row_nnz=max(self.b_max_row_nnz, other.b_max_row_nnz),
            chunk_rows=max(self.chunk_rows, other.chunk_rows),
            chunk_nnz_cap=max(self.chunk_nnz_cap, other.chunk_nnz_cap),
            strip_rows=max(self.strip_rows, other.strip_rows),
            strip_nnz_cap=max(self.strip_nnz_cap, other.strip_nnz_cap),
            c_pad=max(self.c_pad, other.c_pad),
            dtype=self.dtype,
            c_nnz_cap=max(self.c_nnz_cap, other.c_nnz_cap),
            c_max_row_nnz=max(self.c_max_row_nnz, other.c_max_row_nnz),
            bsr_caps=_union_bsr_caps(self.bsr_caps, other.bsr_caps),
        )

    def dominates(self, other: "GeometryEnvelope") -> bool:
        """True when instances fitting ``other`` also fit this envelope."""
        try:
            self._check_compatible(other)
        except ValueError:
            return False
        return (self.a_nnz_cap >= other.a_nnz_cap
                and self.a_max_row_nnz >= other.a_max_row_nnz
                and self.b_max_row_nnz >= other.b_max_row_nnz
                and self.chunk_rows >= other.chunk_rows
                and self.chunk_nnz_cap >= other.chunk_nnz_cap
                and self.strip_rows >= other.strip_rows
                and self.strip_nnz_cap >= other.strip_nnz_cap
                and self.c_pad >= other.c_pad
                and self.c_nnz_cap >= other.c_nnz_cap
                and self.c_max_row_nnz >= other.c_max_row_nnz
                and self._dominates_bsr_caps(other))

    def _dominates_bsr_caps(self, other: "GeometryEnvelope") -> bool:
        # An uncapped request fits any envelope (block caps only matter to
        # block backends, which demand a capped envelope at dispatch); a
        # capped request needs same-block-size caps at least as large.
        if not other.bsr_caps:
            return True
        if not self.bsr_caps or self.bsr_caps[0] != other.bsr_caps[0]:
            return False
        return all(s >= o for s, o in zip(self.bsr_caps[1:], other.bsr_caps[1:]))

    def quantized(self, quantum: int = 32) -> "GeometryEnvelope":
        """Round the nnz caps up to ``quantum`` multiples and the row-nnz
        bounds up to powers of two, collapsing near-identical geometries into
        one bucket (fewer compiles, bounded padding waste)."""

        def up(v: int) -> int:
            return max(quantum, -(-int(v) // quantum) * quantum)

        def up_pow2(v: int) -> int:
            return 1 << max(int(v) - 1, 0).bit_length() if v > 1 else max(v, 1)

        return GeometryEnvelope(
            a_shape=self.a_shape, b_shape=self.b_shape,
            a_nnz_cap=up(self.a_nnz_cap),
            a_max_row_nnz=up_pow2(self.a_max_row_nnz),
            b_max_row_nnz=up_pow2(self.b_max_row_nnz),
            chunk_rows=self.chunk_rows,
            chunk_nnz_cap=up(self.chunk_nnz_cap),
            strip_rows=self.strip_rows,
            strip_nnz_cap=up(self.strip_nnz_cap),
            c_pad=up(self.c_pad),
            dtype=self.dtype,
            c_nnz_cap=up(self.c_nnz_cap) if self.c_nnz_cap else 0,
            c_max_row_nnz=(up_pow2(self.c_max_row_nnz)
                           if self.c_max_row_nnz else 0),
            # block caps arrive pre-quantized from the block symbolic phase
            # (their own block-count quantum, not the nnz quantum)
            bsr_caps=self.bsr_caps,
        )

    def staged_nbytes(self) -> int:
        """Bytes one instance's staged buffers occupy when padded to this
        envelope: the whole-A operand, one A strip, one B chunk, and the C
        output capacity, each as (indices + data) entries plus an int32
        indptr. A comparison measure for "how much padding does serving this
        request out of that envelope cost" — larger envelopes always score
        strictly higher, which is all the tightest-dominator argmin needs."""
        itemsize = int(np.dtype(self.dtype).itemsize)
        entry = 4 + itemsize          # int32 index + one value per nnz slot
        return int(
            self.a_nnz_cap * entry
            + self.strip_nnz_cap * entry + (self.strip_rows + 1) * 4
            + self.chunk_nnz_cap * entry + (self.chunk_rows + 1) * 4
            + self.c_pad * entry
        )

    @classmethod
    def batch(cls, envelopes) -> "GeometryEnvelope":
        """Union over per-instance envelopes (the batch's shared geometry)."""
        envelopes = list(envelopes)
        if not envelopes:
            raise ValueError("GeometryEnvelope.batch needs at least one envelope")
        out = envelopes[0]
        for env in envelopes[1:]:
            out = out.union(env)
        return out


def csr_pad_to(m: CSR, nnz_cap: int | None = None, rows: int | None = None,
               max_row_nnz: int | None = None) -> CSR:
    """Repad a CSR to a larger static geometry: grow the entry tail to
    ``nnz_cap``, append empty rows up to ``rows``, and/or raise the
    ``max_row_nnz`` bound. Growing only — shrinking the capacities would need
    the true nnz (a traced value under jit), and lowering ``max_row_nnz``
    below the actual densest row would silently truncate the SpGEMM expansion
    buffer downstream, so an undersized target (e.g. a stale envelope applied
    to a denser batch) fails loudly here instead."""
    nnz_cap = m.nnz_pad if nnz_cap is None else int(nnz_cap)
    rows = m.n_rows if rows is None else int(rows)
    mrn = m.max_row_nnz if max_row_nnz is None else int(max_row_nnz)
    if nnz_cap < m.nnz_pad or rows < m.n_rows or mrn < m.max_row_nnz:
        raise ValueError(
            f"csr_pad_to only grows: nnz_cap={nnz_cap} rows={rows} "
            f"max_row_nnz={mrn} vs nnz_pad={m.nnz_pad} n_rows={m.n_rows} "
            f"max_row_nnz={m.max_row_nnz}"
        )
    indptr, indices, data = m.indptr, m.indices, m.data
    if rows > m.n_rows:
        indptr = jnp.concatenate(
            [indptr, jnp.full(rows - m.n_rows, indptr[-1], jnp.int32)]
        )
    if nnz_cap > m.nnz_pad:
        indices = jnp.concatenate(
            [indices, jnp.zeros(nnz_cap - m.nnz_pad, jnp.int32)]
        )
        data = jnp.concatenate([data, jnp.zeros(nnz_cap - m.nnz_pad, m.dtype)])
    return CSR(indptr, indices, data, (rows, m.shape[1]), mrn)


def csr_stack(mats) -> CSR:
    """Stack uniformly-padded CSRs along a new leading axis (host-side).

    The result reuses the ``CSR`` container: every array field gains a leading
    ``len(mats)`` axis while ``shape``/``max_row_nnz`` keep the *per-element*
    geometry. That makes the stack directly usable as ``lax.scan`` xs (or
    ``vmap`` operands): slicing the leading axis of each field yields a valid
    per-chunk ``CSR`` with identical static metadata, so the scan body traces
    once. Element-wise host accessors (``nnz_pad`` etc.) are meaningless on the
    stacked object — unstack first.

    All inputs must share shape, indptr length, nnz capacity and
    ``max_row_nnz`` (what the chunkers' uniform padding guarantees).
    """
    mats = list(mats)
    if not mats:
        raise ValueError("csr_stack needs at least one matrix")
    first = mats[0]
    for m in mats[1:]:
        if (m.shape != first.shape or m.indptr.shape != first.indptr.shape
                or m.indices.shape != first.indices.shape
                or m.max_row_nnz != first.max_row_nnz
                or m.dtype != first.dtype):
            raise ValueError(
                "csr_stack requires uniform padded geometry: "
                f"{m!r} vs {first!r}"
            )
    return CSR(
        indptr=jnp.stack([m.indptr for m in mats]),
        indices=jnp.stack([m.indices for m in mats]),
        data=jnp.stack([m.data for m in mats]),
        shape=first.shape,
        max_row_nnz=first.max_row_nnz,
    )


def csr_unstack(stacked: CSR) -> list:
    """Inverse of ``csr_stack``: split the leading axis back into CSRs."""
    n = stacked.indptr.shape[0]
    return [
        CSR(stacked.indptr[i], stacked.indices[i], stacked.data[i],
            stacked.shape, stacked.max_row_nnz)
        for i in range(n)
    ]


def csr_transpose_host(m: CSR, pad_to: int | None = None) -> CSR:
    """Host-side transpose (multigrid P = R^T)."""
    indptr = np.asarray(m.indptr)
    indices = np.asarray(m.indices)
    data = np.asarray(m.data)
    nnz = int(indptr[-1])
    rows = np.repeat(np.arange(m.n_rows), indptr[1:] - indptr[:-1])
    return csr_from_coo(indices[:nnz], rows, data[:nnz], (m.shape[1], m.shape[0]),
                        pad_to, dtype=m.dtype, sum_duplicates=False)
