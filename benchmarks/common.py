"""Shared benchmark machinery.

Every benchmark prints ``name,us_per_call,derived`` CSV rows:
  * us_per_call — real median wall-clock of the JAX computation on this CPU
    (algorithmic work is real; only the *memory-system* behaviour is modeled).
  * derived     — the paper-comparable number (modeled GFLOP/s, speedup, count),
    produced by the calibrated two-level memory model (repro.core.memory_model).

The paper's absolute GFLOP/s need its machines; what we reproduce exactly are
its DECISIONS and RELATIVE effects (EXPERIMENTS.md maps each row to the paper
claim it validates).
"""

from __future__ import annotations

import os
import time
from pathlib import Path

import jax
import numpy as np

ROWS = []

COMPILE_CACHE_DIR = Path(__file__).resolve().parents[1] / ".jax_cache"


def enable_compile_cache() -> None:
    """Keep JAX's persistent compilation cache where
    ``JAX_COMPILATION_CACHE_DIR`` says (JAX reads the variable itself), or,
    where it is unset, at the fixed ``.jax_cache/`` at the repository root,
    where later runs from the same checkout find it. Called by entry points
    only, never by library code or tests."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))


def emit(name: str, us_per_call: float, derived: str | float):
    row = f"{name},{us_per_call:.1f},{derived}"
    ROWS.append(row)
    print(row, flush=True)


def emit_compare(name: str, us_base: float, us_new: float):
    """Emit a measured base-vs-new comparison; derived = real speedup."""
    speedup = us_base / us_new if us_new > 0 else float("inf")
    emit(name, us_new, f"{speedup:.2f}x_vs_base({us_base:.1f}us)")


def timeit(fn, *args, repeats: int = 5, warmup: int = 1) -> float:
    """Median wall-clock microseconds; blocks on JAX async dispatch."""
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)


# problem sizes tuned to finish in seconds on CPU while keeping the paper's
# structure (R short+wide, A regular, nnz/row = 7/13/27/81)
BENCH_SIZES = {
    "laplace3d": 14,
    "bigstar2d": 44,
    "brick3d": 11,
    "elasticity": 7,
}
