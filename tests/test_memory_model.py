"""Memory cost model + placement: the paper's qualitative claims hold."""

import pytest

from repro.core.memory_model import (
    KNL, P100, TPU_V5E, memory_system_for, spgemm_cost,
)
from repro.core.placement import (
    Placement, ALL_FAST, ALL_SLOW, DP, dp_recommendation, placement_cost,
)
from repro.core.locality import analyze, miss_table
from repro.sparse import multigrid, generators


@pytest.fixture(scope="module")
def rxa_axp():
    A, R, P = multigrid.problem("laplace3d", 8)
    return A, R, P


def test_dp_policy_matches_paper():
    cap = P100.fast.capacity_bytes
    assert dp_recommendation(P100, cap / 4, cap / 4, cap / 4) == ALL_FAST
    assert dp_recommendation(P100, cap, cap / 2, cap / 2) == DP
    assert dp_recommendation(P100, cap, 2 * cap, cap) == ALL_SLOW


def test_b_pin_collapses_on_gpu(rxa_axp):
    """Paper Table 3: placing B in host-pinned memory costs 7x-29x; placing the
    (small) A is nearly free."""
    A, R, P = rxa_axp
    from repro.core.kkmem import spgemm_symbolic_host
    ws = spgemm_symbolic_host(R, A)   # R x A: A is the big irregular operand
    st = analyze(R, A)
    base = placement_cost(P100, ALL_FAST, R, A, ws.c_nnz * 12.0, ws.flops, st)
    b_pin = placement_cost(P100, Placement("fast", "slow", "fast"), R, A,
                           ws.c_nnz * 12.0, ws.flops, st)
    a_pin = placement_cost(P100, Placement("slow", "fast", "fast"), R, A,
                           ws.c_nnz * 12.0, ws.flops, st)
    assert b_pin.total / base.total > 3.0         # B_pin collapses
    assert a_pin.total / base.total < 2.0         # A_pin mild


def test_knl_gap_smaller_than_gpu_gap(rxa_axp):
    """Paper conclusion: bandwidth-only asymmetry (KNL) hurts far less than
    bandwidth+latency asymmetry (GPU pinned)."""
    A, R, P = rxa_axp
    from repro.core.kkmem import spgemm_symbolic_host
    ws = spgemm_symbolic_host(R, A)
    st = analyze(R, A)
    knl_fast = placement_cost(KNL, ALL_FAST, R, A, ws.c_nnz * 12.0, ws.flops, st)
    knl_slow = placement_cost(KNL, ALL_SLOW, R, A, ws.c_nnz * 12.0, ws.flops, st)
    gpu_fast = placement_cost(P100, ALL_FAST, R, A, ws.c_nnz * 12.0, ws.flops, st)
    gpu_slow = placement_cost(P100, ALL_SLOW, R, A, ws.c_nnz * 12.0, ws.flops, st)
    knl_gap = knl_slow.total / knl_fast.total
    gpu_gap = gpu_slow.total / gpu_fast.total
    assert gpu_gap > knl_gap
    assert knl_gap < 6.0          # paper: DDR as low as ~half of HBM perf
    assert gpu_gap > 5.0          # paper: pinned collapses by 7x-29x


def test_delta_sweep_direction():
    """Paper Table 2: increasing RHS density (delta) shrinks the DDR/HBM gap."""
    A, R, P = multigrid.problem("elasticity", 4)
    gaps = []
    for delta in (1, 4, 16, 64):
        B = generators.random_uniform_degree(R.n_cols, R.n_cols, delta, seed=1)
        from repro.core.kkmem import spgemm_symbolic_host
        ws = spgemm_symbolic_host(R, B)
        st = analyze(R, B)
        fast = placement_cost(KNL, ALL_FAST, R, B, ws.c_nnz * 12.0, ws.flops, st)
        slow = placement_cost(KNL, ALL_SLOW, R, B, ws.c_nnz * 12.0, ws.flops, st)
        gaps.append(slow.total / fast.total)
    assert gaps[-1] < gaps[0]


def test_rxa_worse_locality_than_axp(rxa_axp):
    A, R, P = rxa_axp
    axp = miss_table(A, P)
    rxa = miss_table(R, A)
    assert rxa["L2"] >= axp["L2"]


def test_latency_vs_bandwidth_terms():
    """Tiny rows on the P100 slow level are latency-dominated; fat rows are
    bandwidth-dominated — the prefetch-amortization effect (paper §3.1)."""
    thin = spgemm_cost(P100, bytes_A=1e6, bytes_B=1e8, bytes_C=1e6, flops=1e9,
                       b_row_reads=1e6, b_row_bytes=12, b_miss_fraction=0.5,
                       place_B="slow")
    fat = spgemm_cost(P100, bytes_A=1e6, bytes_B=1e8, bytes_C=1e6, flops=1e9,
                      b_row_reads=1e4, b_row_bytes=1200, b_miss_fraction=0.5,
                      place_B="slow")
    assert thin.t_B > fat.t_B


def test_tpu_preset_constants():
    from repro.kernels.scoped_vmem import VMEM_CAPACITY_BYTES

    assert TPU_V5E.flops_peak == 197e12
    assert abs(TPU_V5E.slow.bandwidth_Bps - 819e9) < 1e6
    # plans count each staged piece once; the pipeline holds two of each
    assert TPU_V5E.fast.capacity_bytes == 64 * (1 << 20)
    assert 2 * TPU_V5E.fast.capacity_bytes == VMEM_CAPACITY_BYTES


def test_memory_system_by_device_kind():
    assert memory_system_for("TPU v5 lite") is TPU_V5E
    for kind in ("cpu", "TPU v4", "TPU v6 lite"):
        with pytest.raises(ValueError, match="no memory system"):
            memory_system_for(kind)
