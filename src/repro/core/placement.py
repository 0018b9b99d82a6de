"""Selective data placement (paper §3.2.1, Table 3).

For ``C = A x B``: A is streamed (read once), C is streamed (written once), the
accumulators are cache-resident; only B is gathered irregularly. So when the fast
memory cannot hold the whole problem, placing **only B fast** recovers most of the
fast-memory performance — *iff* B fits ("This method, DP, only works when B fits
into HBM").

Placement is realized with ``jax.device_put`` + ``memory_kind`` shardings
(device memory vs ``pinned_host``); its performance is evaluated through the
memory cost model, while the functional result is identical — asserted in
tests.
"""

from __future__ import annotations

import dataclasses

import jax

from repro.core.memory_model import MemorySystem, SpGEMMCost, spgemm_cost
from repro.core.locality import LocalityStats, analyze
from repro.sparse.csr import CSR

SPACES = ("fast", "slow")


@dataclasses.dataclass(frozen=True)
class Placement:
    """Memory space per operand of C = A x B."""

    A: str = "slow"
    B: str = "slow"
    C: str = "slow"

    def __post_init__(self):
        for k in ("A", "B", "C"):
            if getattr(self, k) not in SPACES:
                raise ValueError(f"{k} space must be one of {SPACES}")

    def fast_bytes(self, bytes_A: float, bytes_B: float, bytes_C: float) -> float:
        return (
            (bytes_A if self.A == "fast" else 0.0)
            + (bytes_B if self.B == "fast" else 0.0)
            + (bytes_C if self.C == "fast" else 0.0)
        )


ALL_FAST = Placement("fast", "fast", "fast")
ALL_SLOW = Placement("slow", "slow", "slow")
DP = Placement("slow", "fast", "slow")  # the paper's recommendation


def dp_recommendation(system: MemorySystem, bytes_A: float, bytes_B: float,
                      bytes_C: float, reserve_fraction: float = 0.0) -> Placement:
    """The paper's DP policy: everything fast if it fits; else B fast if *it* fits;
    else everything slow (chunking territory — see repro.core.planner)."""
    cap = system.fast.capacity_bytes * (1.0 - reserve_fraction)
    if bytes_A + bytes_B + bytes_C <= cap:
        return ALL_FAST
    if bytes_B <= cap:
        return DP
    return ALL_SLOW


def paper_scale_cache(A: CSR, B: CSR, C_bytes: float = 0.0) -> float:
    """On-core cache capacity, scaled to the benchmark problem.

    The paper runs 1-32 GB problems against ~34 MB of on-core cache — a
    problem:cache ratio of ~70x at the small end. Our CPU-scale problems keep
    the paper's *structure* but not its size, so the modeled cache keeps the
    paper's ratio instead of an absolute capacity — otherwise every toy B is
    cache-resident and no memory-mode effect can exist."""
    total = A.nbytes() + B.nbytes() + float(C_bytes)
    return max(2 << 10, total / 70.0)


def placement_cost(system: MemorySystem, placement: Placement, A: CSR, B: CSR,
                   C_bytes: float, flops: float,
                   locality: LocalityStats | None = None,
                   cache_bytes: float | None = None) -> SpGEMMCost:
    """Modeled cost of one multiplication under ``placement`` (Table 3 analogue)."""
    st = locality or analyze(A, B)
    if cache_bytes is None:
        cache_bytes = paper_scale_cache(A, B, C_bytes)
    nnz_a = float(A.indptr[-1]) if not isinstance(A.indptr, jax.core.Tracer) else A.nnz_pad
    return spgemm_cost(
        system,
        bytes_A=A.nbytes(),
        bytes_B=B.nbytes(),
        bytes_C=C_bytes,
        flops=flops,
        b_row_reads=float(nnz_a),
        b_row_bytes=st.avg_b_row_bytes,
        b_miss_fraction=st.miss_fraction_bytes(cache_bytes),
        place_A=placement.A,
        place_B=placement.B,
        place_C=placement.C,
    )


def place(operand, space: str, system_name: str = "tpu_v5e"):
    """Physically place an operand pytree in a memory space: 'slow' maps to
    the ``pinned_host`` memory kind and 'fast' to device memory. A device
    without the memory kind raises; the placement is never silently
    dropped."""
    del system_name   # parity with the cost APIs; physical placement is kind-based
    if space not in SPACES:
        raise ValueError(f"space must be one of {SPACES}")
    dev = jax.devices()[0]
    kind = "device" if space == "fast" else "pinned_host"
    sharding = jax.sharding.SingleDeviceSharding(dev, memory_kind=kind)
    return jax.device_put(operand, sharding)
