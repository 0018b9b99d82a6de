"""Bring-up smoke of the SpGEMM main path on one TPU chip.

    python3 chip_smoke.py [--seed 0]

One process, one chip. Through the entry points a user calls, it

1. builds the HPCG reference local operator (the 27-point stencil on a
   104^3 grid, ``multigrid.problem("brick3d", 104)``) with seeded values and
   plans ``A x P`` against the chip's memory system;
2. runs ``chunked_spgemm(A, P, plan, backend="scan")``, cold (compile
   included) and warm;
3. runs the fused Galerkin product ``R x (A x P)`` through
   ``pipeline_spgemm(..., backend="scan")``, cold and warm;
4. checks both against ``scipy.sparse`` on the host: identical structure,
   values within float32 accumulation error;
5. answers ``SpGEMMService`` requests (its default ``scan`` backend), fresh
   then warm, each checked against scipy;
6. runs every Pallas backend that compiles for the chip at the largest
   geometry its staging admits, checked against scipy, and requires its
   core to hold a Mosaic kernel (``tpu_custom_call``).

Earlier lines name the device, the sizes, each phase's wall seconds and
which backends ran or are refused. The last line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
Without a TPU, or outside a checkout of this repository, it exits non-zero
and prints no result. These are bring-up timings on the host clock, not a
benchmark.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
# brick3d grid edges and the service's microbatch: HPCG's reference local
# grid (104) for the scan products; for pallas the largest grid whose dense
# staging the planner admits in the v5e's HBM (36 overflows it); for bsr
# the largest whose prefetched slot tables fit SMEM (14 overflows it)
SIZES = dict(grid=104, service_grid=24, requests=4, pallas_grid=34,
             bsr_grid=12)


def _say(line: str) -> None:
    print(line, flush=True)


class Phases:
    """Wall seconds per named phase, on the host clock."""

    def __init__(self):
        self.seconds = {}

    def run(self, name: str, fn, *args, **kwargs):
        import jax

        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args, **kwargs))
        self.seconds[name] = time.perf_counter() - t0
        _say(f"phase {name}: {self.seconds[name]:.3f} s")
        return out


def _seeded(m, rng):
    """``m`` with each stored value scaled by a seeded factor in [0.5, 1.5):
    same structure, values that no symmetry of the stencil can mask."""
    import jax.numpy as jnp
    import numpy as np

    from repro.sparse.csr import CSR

    data = np.asarray(m.data).copy()
    nnz = int(np.asarray(m.indptr)[-1])
    data[:nnz] *= rng.uniform(0.5, 1.5, nnz).astype(data.dtype)
    return CSR(m.indptr, m.indices, jnp.asarray(data), m.shape, m.max_row_nnz)


def _scipy(m):
    import numpy as np
    import scipy.sparse as sp

    indptr = np.asarray(m.indptr, np.int64)
    nnz = int(indptr[-1])
    return sp.csr_matrix((np.asarray(m.data)[:nnz].astype(np.float64),
                          np.asarray(m.indices)[:nnz], indptr), shape=m.shape)


def check_product(name: str, C, factors) -> None:
    """``C`` against the scipy product of ``factors`` (left to right): the
    same sparsity structure as the product of the factors' absolute values
    (which no cancellation can thin), and each value within 1e-4 of the
    sum of its terms' magnitudes (float32 accumulation of up to a few
    hundred terms per entry)."""
    import numpy as np

    ref = bound = None
    for f in factors:
        s = _scipy(f)
        ref = s if ref is None else ref @ s
        bound = abs(s) if bound is None else bound @ abs(s)
    bound.sort_indices()
    ours = _scipy(C)
    if not (np.array_equal(ours.indptr, bound.indptr)
            and np.array_equal(ours.indices, bound.indices)):
        raise AssertionError(f"{name}: structure differs from scipy "
                             f"(nnz {ours.nnz} vs {bound.nnz})")
    rows = np.repeat(np.arange(ours.shape[0]), np.diff(ours.indptr))
    want = np.asarray(ref[rows, ours.indices]).ravel()
    err = np.abs(ours.data - want)
    tol = 1e-4 * bound.data + 1e-30
    if not (err <= tol).all():
        worst = int(np.argmax(err / tol))
        raise AssertionError(f"{name}: value {ours.data[worst]} vs scipy "
                             f"{want[worst]} (bound {bound.data[worst]})")
    _say(f"check {name}: matches scipy, nnz {ours.nnz}, max |err| "
         f"{float(err.max()):.3e}")


def _shape(name: str, m) -> str:
    import numpy as np

    return f"{name} {m.shape[0]}x{m.shape[1]} nnz {int(np.asarray(m.indptr)[-1])}"


def hpcg_products(args, system, rng, phases: Phases) -> None:
    """Phases 1-4: chunked A x P and the Galerkin R x (A x P) through scan."""
    from repro.core.chunking import chunked_spgemm
    from repro.core.pipeline_spgemm import pipeline_spgemm
    from repro.core.planner import plan_chunks, plan_pipeline, row_bytes_csr
    from repro.core.symbolic import (
        pipeline_output_caps, spgemm_pattern_host, strip_output_caps,
    )
    from repro.sparse import multigrid

    A, R, P = phases.run("generate", multigrid.problem, "brick3d", args.grid)
    A = _seeded(A, rng)
    _say(f"size hpcg brick3d grid {args.grid}: {_shape('A', A)}; "
         f"{_shape('P', P)}; {_shape('R', R)}")
    T = phases.run("ap_host_symbolic", spgemm_pattern_host, A, P)
    plan = phases.run("ap_plan", plan_chunks, A, P, row_bytes_csr(T), system)
    caps = phases.run("ap_host_caps", strip_output_caps, A, P, plan.p_ac)
    _say(f"plan ap: {plan.algorithm} n_ac={plan.n_ac} n_b={plan.n_b} "
         f"c_pad={caps.c_pad} nnz(A x P)={int(T.indptr[-1])}")
    C, _ = phases.run("ap_scan_first_call", chunked_spgemm, A, P, plan,
                      caps.c_pad, backend="scan")
    check_product("ap_scan", C, [A, P])
    phases.run("ap_scan_warm_call", chunked_spgemm, A, P, plan, caps.c_pad,
               backend="scan")

    pplan = phases.run("rap_plan", plan_pipeline, A, P, R, system,
                       t_pattern=T)
    pcaps = phases.run("rap_host_symbolic", pipeline_output_caps, A, P, R,
                       pplan.plan1.p_ac, pplan.plan2.p_ac)
    _say(f"plan rap: hop1 {pplan.plan1.algorithm} "
         f"{pplan.plan1.n_ac}x{pplan.plan1.n_b}, hop2 {pplan.plan2.algorithm} "
         f"{pplan.plan2.n_ac}x{pplan.plan2.n_b}, t_resident="
         f"{pplan.t_resident}")
    Cc, _ = phases.run("rap_scan_first_call", pipeline_spgemm, A, P, R, pplan,
                       backend="scan", caps=pcaps)
    check_product("rap_scan", Cc, [R, A, P])
    phases.run("rap_scan_warm_call", pipeline_spgemm, A, P, R, pplan,
               backend="scan", caps=pcaps)


def service_requests(args, system, rng, phases: Phases) -> None:
    """Phase 5: SpGEMMService requests, one fresh microbatch then one warm."""
    from repro.serve.spgemm_service import SpGEMMService
    from repro.sparse import multigrid

    A, _, P = multigrid.problem("brick3d", args.service_grid)
    _say(f"size service brick3d grid {args.service_grid}: {_shape('A', A)}; "
         f"{_shape('P', P)}; {args.requests} requests per microbatch")
    service = SpGEMMService(fast_limit_bytes=system.fast.capacity_bytes,
                            max_batch=args.requests)
    for regime in ("fresh", "warm"):
        operands = [_seeded(A, rng) for _ in range(args.requests)]
        futures = [service.submit(Ai, P) for Ai in operands]
        responses = phases.run(f"service_{regime}", service.drain)
        assert len(responses) == len(futures), (regime, len(responses))
        for resp, Ai in zip(sorted(responses, key=lambda r: r.req_id),
                            operands):
            check_product(f"service_{regime}_{resp.req_id}", resp.C, [Ai, P])
        _say(f"service {regime}: backend {service.backend}, compile "
             f"{sum(r.compile_s for r in responses):.3f} s, exec "
             f"{responses[0].exec_s:.3f} s per microbatch of "
             f"{responses[0].batch_size}")
    assert service.stats.compiles >= 1


def require_mosaic_kernel(name: str, target) -> None:
    """The backend's core, staged as its executor stages it, must lower to a
    Mosaic kernel: in interpret mode it would hold none."""
    import jax

    if "tpu_custom_call" not in jax.jit(target.fn).lower(*target.args).as_text():
        raise AssertionError(f"{name}: core holds no Mosaic kernel")


def pallas_backends(args, system, rng, phases: Phases) -> dict:
    """Phase 6: each Pallas backend that compiles for the chip."""
    from repro.core import backend_registry
    from repro.core.chunking import chunked_spgemm, instance_envelope
    from repro.core.planner import (
        ChunkPlan, plan_dense_slab, select_accumulator_backend,
    )
    from repro.sparse import multigrid

    ran = {}
    for spec in backend_registry.specs():
        if spec.mosaic_refusal is not None:
            _say(f"backend {spec.name}: refused on the TPU "
                 f"({spec.mosaic_refusal})")
            continue
        if spec.traffic_model is None:        # loop and scan: no Pallas core
            continue
        grid = args.pallas_grid if spec.name == "pallas" else args.bsr_grid
        A, _, P = multigrid.problem("brick3d", grid)
        A = _seeded(A, rng)
        if spec.name == "pallas":
            # the dense-slab planner sizes the strips and chunks; "auto"
            # then resolves to pallas, as it would for any caller
            plan = phases.run("pallas_plan", plan_dense_slab, A, P, system)
            backend = "auto"
        else:
            # bsr stages every (strip, chunk) pair by densifying it on the
            # host; two by two pairs cover both streaming roles
            half = lambda n: (0, n // 2, n)       # noqa: E731
            plan = ChunkPlan("chunk1", half(A.n_rows), half(P.n_rows),
                             0.0, 0.0)
            backend = spec.name
        env = instance_envelope(A, P, plan, block_size=spec.block_size)
        if backend == "auto":
            picked = select_accumulator_backend(plan, env)
            if picked != spec.name:
                raise AssertionError(f"auto resolved to {picked}, not "
                                     f"{spec.name}")
        _say(f"size {spec.name} brick3d grid {grid}: {_shape('A', A)}; "
             f"{_shape('P', P)}; plan {plan.algorithm} "
             f"{plan.n_ac}x{plan.n_b}, backend={backend!r}"
             + (f", modeled VMEM {plan.fast_bytes_needed:.0f} B"
                if plan.fast_bytes_needed else ""))
        require_mosaic_kernel(spec.name,
                              spec.audit_trace(A, P, plan, env.c_pad, env))
        C, _ = phases.run(f"{spec.name}_first_call", chunked_spgemm, A, P,
                          plan, backend=backend)
        check_product(spec.name, C, [A, P])
        phases.run(f"{spec.name}_warm_call", chunked_spgemm, A, P, plan,
                   backend=backend)
        ran[spec.name] = grid
    return ran


def run(args, system) -> None:
    import numpy as np

    rng = np.random.default_rng(args.seed)
    phases = Phases()
    hpcg_products(args, system, rng, phases)
    service_requests(args, system, rng, phases)
    ran = pallas_backends(args, system, rng, phases)
    _say("backends run: scan (hpcg, service), "
         + ", ".join(f"{n} (grid {g})" for n, g in ran.items()))
    _say("phase seconds: " + json.dumps(
        {k: round(v, 3) for k, v in phases.seconds.items()}))


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    args = argparse.Namespace(**SIZES, **vars(parser.parse_args()))

    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke.py must run from a checkout of this repository",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU found (platform {devices[0].platform!r})",
              file=sys.stderr)
        return 3
    from benchmarks.common import enable_compile_cache
    from repro.core.memory_model import memory_system_for

    enable_compile_cache()
    kind = devices[0].device_kind
    system = memory_system_for(kind)
    _say(f"device: {kind} ({devices[0].platform}, {len(devices)} chip(s)); "
         f"planning against {system.name}, fast limit "
         f"{system.fast.capacity_bytes:.0f} B")
    run(args, system)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
