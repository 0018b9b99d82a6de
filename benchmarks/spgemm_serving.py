"""Serving throughput: SpGEMMService (bucketed vmapped batches) vs naive
per-instance dispatch, across batch sizes and mixed-structure workloads.

Each workload submits ``n`` small C = A x B requests two ways:

  * service — queue everything into ``SpGEMMService``, one ``flush()``: one
    vmapped-scan execution per geometry bucket microbatch;
  * naive   — a Python loop of per-instance ``chunked_spgemm`` calls (the
    dispatch pattern the service replaces).

Mixed workloads draw sparsity densities from a small set, so instances differ
in structure — the heterogeneous-batch case that needs geometry envelopes.

Every row is measured in two regimes, because they answer different questions:

  * ``fresh`` — a wave of never-seen matrices after one cold warmup wave.
    Fresh structures mean fresh padded geometries: the naive path retraces
    per new geometry while the service's quantized buckets absorb them, so
    this regime measures exactly the per-multiply setup amortization the
    service exists for (it flatters the service on purpose — that's the
    effect, not an artifact).
  * ``warm``  — re-serving the *identical* requests, all compiles cached on
    both sides: pure steady-state dispatch + execution. At tiny CPU sizes
    the service loses here (vmap lanes serialize on CPU and envelope/
    microbatch padding is wasted work); the regime keeps the fresh numbers
    honest.

The service runs with its serving optimizations on: buffer donation into the
bucket-owned jitted cores and tail-width learning (warmup waves repeat until
a wave compiles nothing, so learned widths are warm before timing starts).
``run`` asserts every warm row reports ``compiles == 0`` — a warm-regime
compile means the executable cache leaked, and the regime's numbers would be
lies.

Output is a single JSON document on stdout (machine-checkable; CI smoke runs
``--smoke`` and asserts it parses), with per-row/per-regime service/naive
microseconds, requests-per-second throughput, and speedup, plus top-level
``fresh_speedup``/``warm_speedup`` medians, which the CI smoke step asserts
on.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.core.chunking import chunked_spgemm
from repro.core.planner import ChunkPlan
from repro.serve.spgemm_service import SpGEMMService
from repro.sparse.csr import csr_from_dense


def _random_csr(rng, m, n, density):
    d = (rng.random((m, n)) < density) * rng.standard_normal((m, n))
    return csr_from_dense(d.astype(np.float32))


def _requests(rng, n, dim, densities):
    out = []
    for i in range(n):
        d = densities[i % len(densities)]
        out.append((_random_csr(rng, dim, dim, d), _random_csr(rng, dim, dim, d)))
    return out


def _serve_service(service, reqs):
    t0 = time.perf_counter()
    for A, B in reqs:
        service.submit(A, B)
    responses = service.flush()
    return (time.perf_counter() - t0) * 1e6, responses


def _serve_naive(reqs, plan):
    t0 = time.perf_counter()
    outs = []
    for A, B in reqs:
        C, _ = chunked_spgemm(A, B, plan)
        outs.append(C)
    jax.block_until_ready([(C.indptr, C.indices, C.data) for C in outs])
    return (time.perf_counter() - t0) * 1e6, outs


def run(dim: int, batch_sizes, densities_by_workload, max_batch: int,
        quantum: int, seed: int = 0, retrace_budget: int = 16) -> dict:
    half = dim // 2
    plan = ChunkPlan("knl", (0, dim), (0, half, dim), 0.0, 0.0)
    rows = []
    for workload, densities in densities_by_workload.items():
        for n in batch_sizes:
            rng = np.random.default_rng(seed)
            service = SpGEMMService(plan, quantum=quantum, max_batch=max_batch,
                                    retrace_budget=retrace_budget,
                                    donate_buffers=True,
                                    learn_tail_widths=True)
            # warmup waves (not reported) until one compiles nothing: first
            # compiles on both sides, plus any tail widths the service learns
            # from this workload's flush pattern
            warmup = _requests(rng, n, dim, densities)
            for _ in range(6):
                compiles0 = service.stats.compiles
                _serve_service(service, warmup)
                if service.stats.compiles == compiles0:
                    break
            _serve_naive(warmup, plan)
            # fresh regime: never-seen structures -> new geometries; the
            # naive path retraces per geometry, the service's buckets don't
            timed = _requests(rng, n, dim, densities)
            compiles0 = service.stats.compiles
            fresh_service_us, fresh_responses = _serve_service(service, timed)
            fresh_naive_us, _ = _serve_naive(timed, plan)
            fresh_compiles = service.stats.compiles - compiles0
            assert len(fresh_responses) == n
            # warm regime: identical requests again, zero compiles anywhere
            compiles1 = service.stats.compiles
            warm_service_us, warm_responses = _serve_service(service, timed)
            warm_naive_us, _ = _serve_naive(timed, plan)
            warm_compiles = service.stats.compiles - compiles1
            # the warm regime's whole claim is "all executables cached": a
            # compile here means the cache leaked and the timing is a lie
            assert warm_compiles == 0, (
                f"warm regime compiled {warm_compiles}x "
                f"(workload={workload}, n={n})")
            for regime, service_us, naive_us, responses, compiles in (
                    ("fresh", fresh_service_us, fresh_naive_us,
                     fresh_responses, fresh_compiles),
                    ("warm", warm_service_us, warm_naive_us,
                     warm_responses, warm_compiles)):
                rows.append({
                    "workload": workload,
                    "regime": regime,
                    "n_requests": n,
                    "service_us": round(service_us, 1),
                    "naive_us": round(naive_us, 1),
                    "service_rps": round(n / (service_us * 1e-6), 1),
                    "naive_rps": round(n / (naive_us * 1e-6), 1),
                    "speedup": round(naive_us / service_us, 3),
                    "buckets": service.n_buckets,
                    "compiles": compiles,
                    "mean_latency_us": round(
                        1e6 * sum(r.latency_s for r in responses) / n, 1),
                })
    def _median_speedup(regime):
        v = sorted(r["speedup"] for r in rows if r["regime"] == regime)
        return round(v[len(v) // 2], 3) if v else 0.0

    # top-level scalars, so the CI smoke step can assert the regime gap
    return {
        "bench": "spgemm_serving",
        "dim": dim,
        "max_batch": max_batch,
        "quantum": quantum,
        "retrace_budget": retrace_budget,
        "fresh_speedup": _median_speedup("fresh"),
        "warm_speedup": _median_speedup("warm"),
        "rows": rows,
    }


def run_suite():
    """Driver entry point (``python -m benchmarks.run serving``): a small
    serving sweep emitted as the driver's ``name,us_per_call,derived`` CSV
    rows. The standalone ``main()`` JSON document remains the primary output
    (CI smoke-parses it); this lane makes serving reachable from the same
    driver as every paper table/figure."""
    from benchmarks.common import emit

    report = run(dim=24, batch_sizes=[3, 6], max_batch=4, quantum=32,
                 densities_by_workload={"uniform": [0.2],
                                        "mixed": [0.08, 0.25]})
    for row in report["rows"]:
        emit(
            f"serving/{row['workload']}/{row['regime']}/n={row['n_requests']}"
            f"[buckets={row['buckets']}]",
            row["service_us"],
            f"{row['speedup']}x_vs_naive({row['service_rps']}rps)",
        )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI (seconds, still valid JSON)")
    ap.add_argument("--dim", type=int, default=None)
    ap.add_argument("--batch-sizes", type=int, nargs="+", default=None)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--quantum", type=int, default=32)
    ap.add_argument("--retrace-budget", type=int, default=16,
                    help="bound on distinct compiled geometry buckets")
    args = ap.parse_args()

    if args.smoke:
        dim = args.dim or 16
        batch_sizes = args.batch_sizes or [2, 3, 5]
        workloads = {"uniform": [0.2], "mixed": [0.1, 0.3]}
    else:
        dim = args.dim or 48
        batch_sizes = args.batch_sizes or [4, 8, 16]
        workloads = {"uniform": [0.15],
                     "mixed": [0.05, 0.1, 0.2, 0.3]}
    report = run(dim, batch_sizes, workloads, args.max_batch, args.quantum,
                 retrace_budget=args.retrace_budget)
    print(json.dumps(report, indent=2))


if __name__ == "__main__":
    main()
