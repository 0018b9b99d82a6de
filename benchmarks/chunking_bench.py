"""Paper Alg 1 + Figs 12/13: chunked SpGEMM vs whole-problem placements.

KNL (Alg 1): chunk B through an 8 GiB fast window for R x A (the only case the
paper finds chunking profitable on KNL) and report modeled GFLOP/s including the
copy cost, vs DDR and HBM.

GPU (Figs 12/13): Chunk8 / Chunk16 (fast window of 8/16 "GiB" scaled to bench
size) with the Alg-4 planner choosing the streaming order; derived speedup vs
host-pinned — the paper reports 3.1x-14.7x.

Executor lanes: ``run_loop_vs_scan`` (host loop vs device-resident lax.scan,
CSV rows), ``run_scan_vs_pallas`` (scan vs the explicitly double-buffered
Pallas backend), ``run_accumulator_shootout`` (the three-way dense-slab
vs ESC-sparse vs hash-probe accumulator comparison across an output-density
sweep, with all three planner fast-memory models and the ``backend="auto"``
pick per row), and ``run_bsr_blocking`` (the blocked MXU-tile accumulator vs
the entry-level ones across a blockiness sweep — where the auto dispatch
starts and stops selecting ``backend="bsr"``). The JSON lanes power
``python benchmarks/chunking_bench.py [--smoke] [--lane ...]``, which prints
one JSON document (the ``BENCH_chunking.json`` schema:
``{"bench": ..., "rows": [...]}``) that CI smoke-parses like the serving
bench.
"""

from __future__ import annotations

import argparse
import json

import numpy as np

from benchmarks.common import emit, emit_compare, timeit, BENCH_SIZES
from repro.core.chunking import chunked_spgemm, default_c_pad
from repro.core.kkmem import spgemm_symbolic_host
from repro.core.locality import analyze
from repro.core.memory_model import KNL, P100
from repro.core.placement import ALL_FAST, ALL_SLOW, placement_cost
from repro.core.planner import plan_chunks, plan_knl, row_bytes_csr
from repro.sparse import multigrid


def _modeled_chunk_gflops(system, _plan, stats, ws, st, A, B) -> float:
    """Kernel runs at fast-memory speed; staged copies pay the copy engine."""
    nnz_a = float(np.asarray(A.indptr)[-1])
    from repro.core.memory_model import spgemm_cost

    kernel = spgemm_cost(
        system, bytes_A=A.nbytes(), bytes_B=B.nbytes(), bytes_C=ws.c_nnz * 12.0,
        flops=ws.flops, b_row_reads=nnz_a, b_row_bytes=st.avg_b_row_bytes,
        b_miss_fraction=st.miss_fraction_bytes(1 << 20),
        place_A="fast", place_B="fast", place_C="fast",
        copy_bytes=stats.copy_bytes)
    return kernel.gflops(ws.flops)


def run():
    for prob in ("laplace3d", "elasticity"):
        n = BENCH_SIZES[prob]
        A, R, P = multigrid.problem(prob, n)
        # --- KNL Alg 1 on R x A ------------------------------------------------
        ws = spgemm_symbolic_host(R, A)
        st = analyze(R, A)
        size_b = float(row_bytes_csr(A).sum())
        for frac, label in ((0.5, "Chunk-half"), (0.25, "Chunk-quarter")):
            plan = plan_knl(R, A, fast_limit_bytes=size_b * frac)
            C, stats = chunked_spgemm(R, A, plan)
            us = timeit(lambda R=R, A=A, p=plan: chunked_spgemm(R, A, p),
                        repeats=2)
            g = _modeled_chunk_gflops(KNL, plan, stats, ws, st, R, A)
            emit(f"alg1/knl/{prob}/RxA/{label}", us, f"{g:.3f}")
        ddr = placement_cost(KNL, ALL_SLOW, R, A, ws.c_nnz * 12.0, ws.flops, st)
        hbm = placement_cost(KNL, ALL_FAST, R, A, ws.c_nnz * 12.0, ws.flops, st)
        emit(f"alg1/knl/{prob}/RxA/DDR", 0.0, f"{ddr.gflops(ws.flops):.3f}")
        emit(f"alg1/knl/{prob}/RxA/HBM", 0.0, f"{hbm.gflops(ws.flops):.3f}")

        # --- GPU Figs 12/13 ----------------------------------------------------
        for tag, (L, Rt) in {"AxP": (A, P), "RxA": (R, A)}.items():
            ws = spgemm_symbolic_host(L, Rt)
            st = analyze(L, Rt)
            total = float(row_bytes_csr(L).sum() + row_bytes_csr(Rt).sum()
                          + ws.c_nnz * 12.0)
            pinned = placement_cost(P100, ALL_SLOW, L, Rt, ws.c_nnz * 12.0,
                                    ws.flops, st)
            for frac, label in ((0.5, "Chunk16"), (0.25, "Chunk8")):
                crb = np.full(L.n_rows, max(ws.c_nnz / L.n_rows, 1.0) * 12.0)
                plan = plan_chunks(L, Rt, crb, P100,
                                   fast_limit_bytes=total * frac)
                C, stats = chunked_spgemm(L, Rt, plan)
                us = timeit(lambda L=L, Rt=Rt, p=plan: chunked_spgemm(L, Rt, p),
                            repeats=2)
                g = _modeled_chunk_gflops(P100, plan, stats, ws, st, L, Rt)
                speedup = g / pinned.gflops(ws.flops)
                emit(f"fig12_13/gpu/{prob}/{tag}/{label}"
                     f"[{plan.algorithm};ac={plan.n_ac};b={plan.n_b}]",
                     us, f"{speedup:.2f}x_vs_pinned")

    # The executor comparison sweeps (loop vs scan, scan vs pallas) are their
    # own driver lanes — `scan_vs_loop` / `scan_vs_pallas` in
    # benchmarks.run.SUITES — so a full `python -m benchmarks.run` covers
    # them exactly once.


def run_loop_vs_scan():
    from repro.core.planner import ChunkPlan

    prob = "laplace3d"
    A, R, P = multigrid.problem(prob, BENCH_SIZES[prob])

    cases = []
    # 1-D B streaming (Alg 1) at two fast-window sizes
    for frac, label in ((0.5, "knl-half"), (0.125, "knl-eighth")):
        cases.append((plan_knl(A, P, fast_limit_bytes=P.nbytes() * frac),
                      label))
    # 2-D plans: both streaming orders on an explicit 3x4 partition
    n_a, n_b = A.n_rows, P.n_rows
    p_ac = tuple(int(v) for v in np.linspace(0, n_a, 4))
    p_b = tuple(int(v) for v in np.linspace(0, n_b, 5))
    for alg in ("chunk1", "chunk2"):
        cases.append((ChunkPlan(alg, p_ac, p_b, 0.0, 0.0), f"{alg}-3x4"))

    for plan, label in cases:
        c_pad = default_c_pad(A, P, plan)
        us_loop = timeit(lambda plan=plan, c_pad=c_pad: chunked_spgemm(
            A, P, plan, c_pad, backend="loop"), repeats=3)
        us_scan = timeit(lambda plan=plan, c_pad=c_pad: chunked_spgemm(
            A, P, plan, c_pad, backend="scan"), repeats=3)
        emit_compare(
            f"scan_vs_loop/{prob}/AxP/{label}"
            f"[{plan.algorithm};ac={plan.n_ac};b={plan.n_b}]",
            us_loop, us_scan)


def run_scan_vs_pallas(smoke: bool = False) -> dict:
    """Scan (XLA-scheduled transfers) vs Pallas (explicit double-buffered
    prefetch) on the same plans, as a machine-checkable JSON report.

    On CPU the Pallas path runs in interpret mode over *densified* staged
    pieces, so the absolute numbers only validate plumbing; the lane exists so
    the comparison harness (and its JSON schema) is exercised continuously and
    ready for real-TPU runs, where the dense slabs hit the MXU and the DMA
    overlap is the paper's measured effect.
    """
    from repro.core.planner import ChunkPlan

    prob = "laplace3d"
    size = 5 if smoke else 8
    A, R, P = multigrid.problem(prob, size)
    n_a, n_b = A.n_rows, P.n_rows

    cases = [(plan_knl(A, P, fast_limit_bytes=P.nbytes() * 0.4), "knl-chunks")]
    p_ac = tuple(int(v) for v in np.linspace(0, n_a, 3))
    p_b = tuple(int(v) for v in np.linspace(0, n_b, 4))
    for alg in ("chunk1", "chunk2"):
        cases.append((ChunkPlan(alg, p_ac, p_b, 0.0, 0.0), f"{alg}-2x3"))

    repeats = 2 if smoke else 3
    rows = []
    for plan, label in cases:
        c_pad = default_c_pad(A, P, plan)
        # plan-derived stats are deterministic: take them from the warmup
        # call instead of re-executing after the timed runs
        _, stats_scan = chunked_spgemm(A, P, plan, c_pad, backend="scan")
        _, stats_pallas = chunked_spgemm(A, P, plan, c_pad, backend="pallas")
        us_scan = timeit(lambda plan=plan, c_pad=c_pad: chunked_spgemm(
            A, P, plan, c_pad, backend="scan"), repeats=repeats)
        us_pallas = timeit(lambda plan=plan, c_pad=c_pad: chunked_spgemm(
            A, P, plan, c_pad, backend="pallas"), repeats=repeats)
        rows.append({
            "case": f"{prob}/AxP/{label}",
            "algorithm": plan.algorithm,
            "n_ac": plan.n_ac,
            "n_b": plan.n_b,
            "scan_us": round(us_scan, 1),
            "pallas_us": round(us_pallas, 1),
            "pallas_vs_scan": round(us_scan / us_pallas, 3) if us_pallas
            else float("inf"),
            "scan_copy_bytes": stats_scan.copy_bytes,
            "pallas_copy_bytes": stats_pallas.copy_bytes,
        })
    from repro.kernels.ranged_spgemm import default_interpret

    return {
        "bench": "chunking_scan_vs_pallas",
        "problem": prob,
        "size": size,
        "interpret_mode": default_interpret(),
        "rows": rows,
    }


def run_csv_scan_vs_pallas():
    """The scan-vs-pallas lane as driver CSV rows (JSON stays in ``main``)."""
    report = run_scan_vs_pallas()
    for row in report["rows"]:
        emit(f"scan_vs_pallas/{row['case']}"
             f"[{row['algorithm']};ac={row['n_ac']};b={row['n_b']}]",
             row["pallas_us"], f"{row['pallas_vs_scan']}x_vs_scan")


def _lowerable_argmin(models: dict) -> str:
    """The byte argmin among the backends ``auto`` may pick (those the TPU
    compiler accepts); ``byte_winner`` is the argmin over every model."""
    from repro.core import backend_registry

    return min((s.name for s in backend_registry.auto_specs()),
               key=lambda be: models[be].fast_bytes_needed)


def run_accumulator_shootout(smoke: bool = False) -> dict:
    """Three-way accumulator comparison — dense-slab Pallas vs ESC
    sparse-output vs hash-probe — across an output-density sweep, as a
    machine-checkable JSON report (the PR-4 ``dense_vs_sparse_accum`` lane
    grown a third column).

    Fixed (A, plan, n_cols); B's density sweeps so nnz(C) / (m * n) sweeps.
    Each row carries the three measured runtimes *and* the three planner
    fast-memory models (``planned_stats_dense_slab`` / ``planned_stats_sparse``
    / ``planned_stats_hash``): on CPU interpret mode the runtimes only
    validate plumbing, but the byte models are backend truth on any hardware.
    ``byte_winner`` is the per-row argmin; what ``backend="auto"`` resolves
    (``select_accumulator_backend``) is asserted to be the argmin among the
    backends the TPU compiler accepts; the ``crossover`` block reports the largest swept density
    at which each pairwise comparison still favors the compressed side.
    """
    from repro.core.chunking import instance_envelope
    from repro.core.planner import (
        ChunkPlan, backend_fast_models, select_accumulator_backend,
    )
    from repro.core.symbolic import strip_output_caps
    from repro.sparse.csr import csr_from_dense

    rng = np.random.default_rng(17)
    m, k, n = (40, 36, 96) if smoke else (96, 80, 256)
    b_densities = (0.003, 0.01, 0.05, 0.25) if smoke else (
        0.002, 0.005, 0.01, 0.03, 0.08, 0.25)
    a = ((rng.random((m, k)) < 0.08) * rng.standard_normal((m, k)))
    A = csr_from_dense(a.astype(np.float32))
    p_ac = tuple(int(v) for v in np.linspace(0, m, 3))
    p_b = tuple(int(v) for v in np.linspace(0, k, 4))
    plan = ChunkPlan("chunk1", p_ac, p_b, 0.0, 0.0)

    repeats = 2 if smoke else 3
    rows = []
    for db in b_densities:
        b = ((rng.random((k, n)) < db) * rng.standard_normal((k, n)))
        B = csr_from_dense(b.astype(np.float32))
        # one symbolic expansion per row: caps feed c_pad, the envelope, and
        # the exact output density (strips partition all rows, so their nnz
        # sums to nnz(C))
        caps = strip_output_caps(A, B, plan.p_ac)
        c_pad = caps.c_pad
        c_nnz = sum(caps.strip_nnz)
        env = instance_envelope(A, B, plan, caps=caps)
        models = backend_fast_models(plan, env)
        auto_pick = select_accumulator_backend(plan, env)
        row = {
            "case": f"synthetic/{m}x{k}x{n}/db={db}",
            "c_density": round(c_nnz / float(m * n), 5),
        }
        for backend in ("pallas", "sparse", "hash"):
            us = timeit(lambda be=backend, B=B, c_pad=c_pad: chunked_spgemm(
                A, B, plan, c_pad, backend=be), repeats=repeats)
            row[f"{backend}_us"] = round(us, 1)
            row[f"{backend}_fast_bytes"] = models[backend].fast_bytes_needed
        row["byte_winner"] = min(
            ("pallas", "sparse", "hash"),
            key=lambda be, row=row: row[f"{be}_fast_bytes"])
        row["auto_backend"] = auto_pick
        assert auto_pick == _lowerable_argmin(models), (
            f"auto dispatch disagrees with the byte argmin at {row['case']}")
        row["sparse_vs_dense_bytes"] = round(
            row["sparse_fast_bytes"] / row["pallas_fast_bytes"], 3)
        row["hash_vs_dense_bytes"] = round(
            row["hash_fast_bytes"] / row["pallas_fast_bytes"], 3)
        row["hash_vs_esc_bytes"] = round(
            row["hash_fast_bytes"] / row["sparse_fast_bytes"], 3)
        rows.append(row)
    from repro.kernels.sparse_accum_spgemm import default_interpret

    def crossover(wins):
        """Largest swept C density at which ``wins(row)`` still holds."""
        winning = [r["c_density"] for r in rows if wins(r)]
        return max(winning) if winning else None

    return {
        "bench": "chunking_accumulator_shootout",
        "problem": f"synthetic/{m}x{k}x{n}",
        "interpret_mode": default_interpret(),
        "crossover": {
            # ESC byte model below the dense slab's
            "sparse_vs_dense_c_density": crossover(
                lambda r: r["sparse_vs_dense_bytes"] < 1.0),
            # hash byte model below the dense slab's
            "hash_vs_dense_c_density": crossover(
                lambda r: r["hash_vs_dense_bytes"] < 1.0),
            # hash byte model below ESC's (the shrunken-workspace claim)
            "hash_vs_esc_c_density": crossover(
                lambda r: r["hash_vs_esc_bytes"] < 1.0),
        },
        "byte_winner_by_density": {
            str(r["c_density"]): r["byte_winner"] for r in rows
        },
        "rows": rows,
    }


def run_csv_accumulator_shootout():
    """The accumulator-shootout lane as driver CSV rows."""
    report = run_accumulator_shootout()
    for row in report["rows"]:
        emit(f"accumulator_shootout/{row['case']}"
             f"[c_density={row['c_density']}]",
             row["hash_us"],
             f"winner={row['byte_winner']};"
             f"hash_vs_esc={row['hash_vs_esc_bytes']}x_bytes")


def run_bsr_blocking(smoke: bool = False) -> dict:
    """Blocked (BSR/MXU-tile) vs entry-level accumulators across a
    *blockiness* sweep, as a machine-checkable JSON report.

    Fixed shape and roughly fixed nnz; what sweeps is how that nnz is
    organized — from dense block-diagonal 8x8 tiles (blockiness 1.0, every
    staged piece a handful of MXU tiles) to fully scattered singles
    (blockiness 0.0, every entry its own mostly-empty tile). Each row
    carries the measured runtimes, every registered accumulator's planner
    fast-memory model under the block-capped envelope, and the
    ``backend="auto"`` pick — asserted equal to the byte argmin among the
    backends the TPU compiler accepts, and pinned
    to ``bsr`` on the blockiest row / to an entry-level backend on the
    fully scattered row. That crossover is the lane's product: the planner
    prices the zero-padding waste of blocked staging honestly, so auto only
    selects the MXU-shaped backend where block structure amortizes it.
    """
    from repro.core.chunking import instance_envelope
    from repro.core.kkmem import spgemm_dense_oracle
    from repro.core.planner import (
        ChunkPlan, backend_fast_models, select_accumulator_backend,
    )
    from repro.sparse.csr import csr_from_dense, csr_to_dense

    bs = 8
    m = 64 if smoke else 128
    budget = (m // bs) // 2        # nnz budget in dense-block units: half the
    rng = np.random.default_rng(23)  # diagonal, so scatter stays truly sparse

    def blocky(frac: float):
        """Block-diagonal dense tiles for ``frac`` of the nnz budget, the
        remainder scattered as entry-level singles."""
        n_blocks = round(frac * budget)
        d = np.zeros((m, m), np.float32)
        for i in range(n_blocks):
            s = i * bs
            d[s:s + bs, s:s + bs] = rng.standard_normal((bs, bs))
        scatter = (budget - n_blocks) * bs * bs
        if scatter:
            idx = rng.choice(m * m, size=scatter, replace=False)
            d.flat[idx] = rng.standard_normal(scatter)
        return csr_from_dense(d)

    plan = ChunkPlan("knl", (0, m), (0, m // 2, m), 0.0, 0.0)
    repeats = 2 if smoke else 3
    rows = []
    for frac in (1.0, 0.5, 0.0):
        A, B = blocky(frac), blocky(frac)
        env = instance_envelope(A, B, plan, block_size=bs)
        models = backend_fast_models(plan, env)
        auto_pick = select_accumulator_backend(plan, env)
        row = {"case": f"synthetic/{m}x{m}x{m}/blockiness={frac}",
               "blockiness": frac,
               "nnz_a": int(np.asarray(A.indptr)[-1])}
        for backend, model in models.items():
            row[f"{backend}_fast_bytes"] = model.fast_bytes_needed
        for backend in ("pallas", "hash", "bsr"):
            kw = {"block_size": bs} if backend == "bsr" else {}
            C, _ = chunked_spgemm(A, B, plan, backend=backend, **kw)
            us = timeit(lambda be=backend, k=kw, A=A, B=B: chunked_spgemm(
                A, B, plan, backend=be, **k), repeats=repeats)
            row[f"{backend}_us"] = round(us, 1)
        # the blocked backend must stay correct at every blockiness
        assert np.allclose(np.asarray(csr_to_dense(C)),
                           np.asarray(spgemm_dense_oracle(A, B)), atol=1e-4)
        row["byte_winner"] = min(models, key=lambda be, models=models:
                                 models[be].fast_bytes_needed)
        row["auto_backend"] = auto_pick
        assert auto_pick == _lowerable_argmin(models), (
            f"auto dispatch disagrees with the byte argmin at {row['case']}")
        rows.append(row)
    assert rows[0]["byte_winner"] == "bsr", \
        "block-diagonal tiles must price the blocked backend cheapest"
    assert rows[-1]["byte_winner"] != "bsr", \
        "scattered singles must price the blocked backend out"
    from repro.kernels.ranged_spgemm import default_interpret

    return {
        "bench": "chunking_bsr_blocking",
        "problem": f"synthetic/{m}x{m}x{m}",
        "block_size": bs,
        "interpret_mode": default_interpret(),
        "byte_winner_by_blockiness": {
            str(r["blockiness"]): r["byte_winner"] for r in rows
        },
        "rows": rows,
    }


def run_csv_bsr_blocking():
    """The BSR blocking lane as driver CSV rows."""
    report = run_bsr_blocking()
    for row in report["rows"]:
        emit(f"bsr_blocking/{row['case']}[nnz_a={row['nnz_a']}]",
             row["bsr_us"],
             f"winner={row['byte_winner']};"
             f"bsr_vs_pallas_bytes="
             f"{round(row['bsr_fast_bytes'] / row['pallas_fast_bytes'], 3)}x")


JSON_LANES = {
    "scan_vs_pallas": run_scan_vs_pallas,
    "accumulator_shootout": run_accumulator_shootout,
    "bsr_blocking": run_bsr_blocking,
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI (seconds, still valid JSON)")
    ap.add_argument("--lane", choices=sorted(JSON_LANES),
                    default="scan_vs_pallas",
                    help="which JSON lane to print")
    args = ap.parse_args()
    print(json.dumps(JSON_LANES[args.lane](smoke=args.smoke), indent=2))


if __name__ == "__main__":
    main()
