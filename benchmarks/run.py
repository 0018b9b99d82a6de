"""Benchmark driver: one suite per paper table/figure (plus executor and
serving lanes).

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run table2 fig11   # a subset

Prints ``name,us_per_call,derived`` CSV (see benchmarks/common.py for the
semantics of each column). A SUITES value is ``module`` (whose ``run()`` is
called) or ``module:function`` for lanes that live inside a bigger module."""

from __future__ import annotations

import sys
import time

SUITES = {
    "fig3_4_6_7": "benchmarks.memory_modes",      # KNL + GPU memory modes
    "table2": "benchmarks.delta_sweep",           # delta sweep
    "table3": "benchmarks.data_placement",        # selective placement (+Figs 9/10)
    "fig12_13": "benchmarks.chunking_bench",      # chunked algorithms (+Alg 1)
    "triangle_counting": "benchmarks.triangle_counting",  # Fig 11 + Table 4
    "chunkability": "benchmarks.chunkability",    # Bender properties
    "kernels": "benchmarks.kernels_bench",        # Pallas kernel microbenches
    "roofline": "benchmarks.roofline_table",      # §Roofline aggregation
    "serving": "benchmarks.spgemm_serving:run_suite",   # SpGEMMService vs naive
    "scan_vs_loop": "benchmarks.chunking_bench:run_loop_vs_scan",
    "scan_vs_pallas": "benchmarks.chunking_bench:run_csv_scan_vs_pallas",
    "accumulator_shootout":
        "benchmarks.chunking_bench:run_csv_accumulator_shootout",
    "bsr_blocking": "benchmarks.chunking_bench:run_csv_bsr_blocking",
}


def _resolve(spec: str):
    mod_name, _, fn_name = spec.partition(":")
    mod = __import__(mod_name, fromlist=["run"])
    return getattr(mod, fn_name or "run")


def main() -> None:
    from benchmarks.common import enable_compile_cache

    enable_compile_cache()
    args = sys.argv[1:]
    picks = args if args else list(SUITES)
    print("name,us_per_call,derived")
    for name in picks:
        if name not in SUITES:
            print(f"# unknown suite {name!r}; have {list(SUITES)}", file=sys.stderr)
            continue
        fn = _resolve(SUITES[name])
        t0 = time.time()
        print(f"# --- {name} ({SUITES[name]}) ---")
        fn()
        print(f"# {name} done in {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
