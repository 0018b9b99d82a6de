"""Paper Fig 11 + Table 4: triangle counting on three graph classes.

Real wall-clock of the masked L x L SpGEMM on synthetic graphs mirroring the
paper's classes (graph500-RMAT / social-powerlaw / web-crawl-ish banded), as
a machine-checkable JSON lane: the *fused* chunked path (mask applied inside
the hash accumulator's merge — ``repro.core.triangle.count_triangles``)
against the unfused ``kkmem.spgemm``-then-sort-merge baseline
(``count_triangles_kkmem``), plus the L1/L2 locality proxies of Table 4 and
the paper's claim that memory modes barely matter for this kernel (the
derived HBM-vs-DDR gap).

Timing discipline: the host symbolic phase runs ONCE per graph outside every
timed region — its workspace capacity feeds the baseline's numeric phase and
the derived placement costs — and the fused path's plan + masked caps are
likewise precomputed, so both timed callables are numeric-only.

``python -m benchmarks.triangle_counting [--smoke] [--lane ...]`` prints the
JSON report; the driver's ``triangle_counting`` suite wraps it as CSV rows.
"""

from __future__ import annotations

import argparse
import json
import statistics

import numpy as np

from benchmarks.common import emit, timeit
from repro.core.kkmem import spgemm_symbolic_host
from repro.core.locality import analyze
from repro.core.memory_model import KNL
from repro.core.placement import ALL_FAST, ALL_SLOW, placement_cost
from repro.core.triangle import (
    count_triangles, count_triangles_dense, count_triangles_kkmem,
)
from repro.sparse import graphs

GRAPHS = {
    "g500_s10": lambda: graphs.rmat(10, 8, seed=1),
    "social_powerlaw": lambda: graphs.powerlaw(2048, 8, seed=2),
    "web_like": lambda: graphs.rmat(10, 4, a=0.45, b=0.25, c=0.15, seed=3),
}

SMOKE_GRAPHS = {
    "g500_s8": lambda: graphs.rmat(8, 8, seed=1),
    "social_powerlaw": lambda: graphs.powerlaw(512, 8, seed=2),
    "web_like": lambda: graphs.rmat(8, 4, a=0.45, b=0.25, c=0.15, seed=3),
}


def run_triangle_counting(smoke: bool = False) -> dict:
    """The triangle-counting lane as a JSON report (Fig 11 + Table 4)."""
    from repro.core import backend_registry
    from repro.core.planner import plan_knl
    from repro.core.symbolic import masked_output_caps
    from repro.kernels.ranged_spgemm import default_interpret

    backend = backend_registry.masked_backends()[0]
    repeats = 2 if smoke else 3
    rows = []
    for name, make in (SMOKE_GRAPHS if smoke else GRAPHS).items():
        G = make()
        L = graphs.lower_triangular_degree_sorted(G)
        # Host precomputations, all OUTSIDE the timed regions: one symbolic
        # workspace reused by the baseline's numeric phase and the derived
        # placement costs, one plan + masked caps for the fused path.
        ws = spgemm_symbolic_host(L, L)
        plan = plan_knl(L, L, float("inf"))
        caps = masked_output_caps(L, plan.p_ac)

        tri = float(count_triangles(L, plan=plan, backend=backend, caps=caps))
        tri_base = float(count_triangles_kkmem(L, c_pad=ws.c_pad))
        assert tri == tri_base, (
            f"{name}: fused count {tri} != unfused baseline {tri_base}")
        assert tri == float(count_triangles_dense(L)), (
            f"{name}: fused count {tri} disagrees with the dense oracle")

        chunked_us = timeit(
            lambda L=L, plan=plan, caps=caps: count_triangles(
                L, plan=plan, backend=backend, caps=caps),
            repeats=repeats)
        kkmem_us = timeit(
            lambda L=L, c=ws.c_pad: count_triangles_kkmem(L, c_pad=c),
            repeats=repeats)

        st = analyze(L, L)
        fast = placement_cost(KNL, ALL_FAST, L, L, ws.c_nnz * 12.0,
                              ws.flops, st)
        slow = placement_cost(KNL, ALL_SLOW, L, L, ws.c_nnz * 12.0,
                              ws.flops, st)
        rows.append({
            "graph": name,
            "n": L.n_rows,
            "nnz_l": int(np.asarray(L.indptr)[-1]),
            "triangles": tri,
            "chunked_us": round(chunked_us, 1),
            "kkmem_us": round(kkmem_us, 1),
            "chunked_vs_kkmem": round(kkmem_us / chunked_us, 3),
            "l1_miss": round(float(st.miss_fraction_bytes(32 << 10)), 4),
            "l2_miss": round(float(st.miss_fraction_bytes(1 << 20)), 4),
            "hbm_ddr_gap": round(slow.total / fast.total, 3),
        })
    return {
        "bench": "triangle_counting",
        "backend": backend,
        "interpret_mode": default_interpret(),
        "smoke": smoke,
        # lane-level scalar, which the CI smoke step asserts on
        "chunked_vs_kkmem_speedup": round(statistics.median(
            r["chunked_vs_kkmem"] for r in rows), 3),
        "rows": rows,
    }


def run():
    """The triangle lane as driver CSV rows (Fig 11 + Table 4 names)."""
    report = run_triangle_counting()
    for row in report["rows"]:
        emit(f"fig11/{row['graph']}/count", row["chunked_us"],
             f"{row['triangles']:.0f}")
        emit(f"fig11/{row['graph']}/kkmem_baseline", row["kkmem_us"],
             f"speedup={row['chunked_vs_kkmem']}x")
        emit(f"table4/{row['graph']}/L1miss", 0.0, f"{row['l1_miss']:.4f}")
        emit(f"table4/{row['graph']}/L2miss", 0.0, f"{row['l2_miss']:.4f}")
        emit(f"fig11/{row['graph']}/hbm_ddr_gap", 0.0,
             f"{row['hbm_ddr_gap']:.3f}")


JSON_LANES = {
    "triangle_counting": run_triangle_counting,
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI (seconds, still valid JSON)")
    ap.add_argument("--lane", choices=sorted(JSON_LANES),
                    default="triangle_counting",
                    help="which JSON lane to print")
    args = ap.parse_args()
    print(json.dumps(JSON_LANES[args.lane](smoke=args.smoke), indent=2))


if __name__ == "__main__":
    main()
