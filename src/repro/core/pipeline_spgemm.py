"""Fused two-hop sparse pipelines: the Galerkin triple product ``R x (A x P)``.

The multigrid setup phase (paper §4.1.1) and the masked triangle count
(§4.1.2) are both *products of products*: a first SpGEMM whose output is
immediately consumed by a second. Running them as two independent
``chunked_spgemm`` calls wastes the structure the composed symbolic phase
already knows — the intermediate ``T = A x P`` round-trips through slow
memory even when it would fit in fast memory alongside hop 2's staging.

This module is the two-hop planner+executor:

* the **composed symbolic phase** (``repro.core.symbolic.
  pipeline_output_caps``) pre-sizes both hops in one pass — hop 1's exact
  output structure *is* hop 2's streamed-operand input, so one
  :class:`PipelineEnvelope` (a hop-1 + hop-2 envelope pair, hashable)
  covers the whole triple product before any tracing;
* the **planner extension** (``repro.core.planner.plan_pipeline``) budgets
  fast memory for the *resident intermediate*: T's CSR triple stays staged
  between the hops when both hops' peaks still fit with it held alongside,
  and spills to slow memory otherwise;
* the **executor** (:func:`pipeline_spgemm`) runs both hops through any
  registered backend, propagating the pre-sized caps so neither hop
  re-expands the symbolic structure;
* the **audit hook** (:func:`pipeline_audit_traces`, :func:`audit_pipeline`)
  stages both hops' cores exactly as the executor would, so the static
  verifier's VMEM/traffic/retrace analyses cover two-hop staging, and the
  composed byte model (:func:`pipeline_fast_model`) is held to counting the
  resident intermediate **exactly once** (:func:`check_pipeline_model`).
"""

from __future__ import annotations

import dataclasses

from repro.core import backend_registry
from repro.core.chunking import (
    ChunkStats, chunk_whole_fast, instance_envelope,
)
from repro.core.planner import BackendFastModel, PipelinePlan, plan_pipeline
from repro.core.spans import span
from repro.core.symbolic import PipelineCaps, pipeline_output_caps
from repro.sparse.csr import CSR, GeometryEnvelope


@dataclasses.dataclass(frozen=True)
class PipelineEnvelope:
    """The compile key of one two-hop pipeline: both hops' padded
    geometries, pre-sized together by the composed symbolic phase. Hop 1's
    output caps are hop 2's input caps by construction (``hop2.b_max_row_nnz``
    is the densest row of the intermediate ``T``), which is what makes the
    pair a *single* envelope over the triple product rather than two
    independent ones."""

    hop1: GeometryEnvelope   # T = A x P
    hop2: GeometryEnvelope   # C = R x T


def pipeline_envelope(A: CSR, P: CSR, R: CSR, plan: PipelinePlan,
                      caps: PipelineCaps) -> PipelineEnvelope:
    """Both hop envelopes from one composed symbolic pass. The hop-2
    envelope is built against the intermediate's exact *pattern* (structure
    equals the numeric T bitwise), so it can be constructed — and an
    executable compiled — before hop 1 ever runs."""
    return PipelineEnvelope(
        hop1=instance_envelope(A, P, plan.plan1, caps=caps.hop1),
        hop2=instance_envelope(R, caps.t_pattern, plan.plan2, caps=caps.hop2),
    )


@dataclasses.dataclass
class PipelineStats:
    """Observed staging traffic of one pipeline run. The per-hop
    :class:`ChunkStats` log the executors' staging events; ``spill_bytes``
    is the *extra* slow-memory round trip of the intermediate when the plan
    spilled it (one write-out after hop 1 plus one read per hop-2 streamed
    pass) — zero on the resident path, where T never leaves fast memory
    between the hops."""

    plan: PipelinePlan
    hop1: ChunkStats
    hop2: ChunkStats
    spilled: bool
    spill_bytes: float

    @property
    def copy_bytes(self) -> float:
        return self.hop1.copy_bytes + self.hop2.copy_bytes + self.spill_bytes


def _run_hop(X: CSR, Y: CSR, plan, caps, backend: str):
    """One hop through a registered backend at pre-sized caps (no repeat
    symbolic expansion — the composed phase already ran)."""
    if plan.algorithm == "whole_fast":
        return chunk_whole_fast(X, Y, caps.c_pad)
    spec = backend_registry.get(backend)
    fn = spec.executors.get(plan.algorithm)
    if fn is None:
        raise ValueError(f"unknown algorithm {plan.algorithm!r}")
    kwargs = {"caps": caps} if spec.needs_output_caps else {}
    return fn(X, Y, plan, caps.c_pad, **kwargs)


def _spill_to_slow(T: CSR) -> CSR:
    """Round-trip the intermediate through slow (host) memory: the spill
    path's physical analogue — hop 2 restages T from slow instead of
    consuming the fast-resident triple."""
    with span("spill") as sp:
        return sp.get(T)


def pipeline_spgemm(A: CSR, P: CSR, R: CSR, plan: PipelinePlan | None = None,
                    *, system=None, fast_limit_bytes: float | None = None,
                    backend: str = "scan", caps: PipelineCaps | None = None):
    """Execute ``C = R x (A x P)`` as a fused two-hop pipeline.

    Returns ``(C, PipelineStats)``. ``plan`` defaults to
    ``planner.plan_pipeline(A, P, R, system, fast_limit_bytes)`` (``system``
    is then required); ``caps`` defaults to the composed symbolic phase at
    the plan's partitions. ``backend`` names any registered backend; both
    hops run through it. On the resident path the intermediate's device CSR
    flows straight into hop 2's staging; on the spill path it round-trips
    through host memory and the stats carry the extra copy events.
    """
    with span("pipeline", backend=backend):
        if plan is None:
            if system is None:
                raise ValueError(
                    "pipeline_spgemm needs either a PipelinePlan or a "
                    "MemorySystem to plan against")
            plan = plan_pipeline(A, P, R, system,
                                 fast_limit_bytes=fast_limit_bytes)
        if caps is None:
            caps = pipeline_output_caps(A, P, R, plan.plan1.p_ac,
                                        plan.plan2.p_ac)
        resident = plan.t_resident
        with span("hop", hop=1, algorithm=plan.plan1.algorithm,
                  resident=resident):
            T, stats1 = _run_hop(A, P, plan.plan1, caps.hop1, backend)
        spill_bytes = 0.0
        if not resident:
            T = _spill_to_slow(T)
            t_reads = plan.plan2.n_ac if plan.plan2.algorithm == "chunk1" else 1
            spill_bytes = float(T.nbytes()) * (1 + t_reads)
        with span("hop", hop=2, algorithm=plan.plan2.algorithm,
                  resident=resident):
            C, stats2 = _run_hop(R, T, plan.plan2, caps.hop2, backend)
    return C, PipelineStats(plan=plan, hop1=stats1, hop2=stats2,
                            spilled=not resident, spill_bytes=spill_bytes)


# ---------------------------------------------------------------------------
# static-audit hook: two-hop staging under the analysis passes
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PipelineFastModel:
    """Composed peak-resident claim of one pipeline under one backend: each
    hop's registered byte model, plus the resident intermediate counted
    **exactly once** on top of whichever hop peaks — T is one buffer that
    persists across both hops, not a per-hop allocation. Double-counting it
    is the modeling bug :func:`check_pipeline_model` exists to catch."""

    backend: str
    hop1: BackendFastModel
    hop2: BackendFastModel
    t_bytes: float           # staged footprint of the resident intermediate
    t_resident: bool
    fast_bytes_needed: float  # max(hop peaks) + (t_bytes if resident)


def pipeline_fast_model(plan: PipelinePlan, penv: PipelineEnvelope,
                        backend: str) -> PipelineFastModel:
    """Compose the backend's per-hop byte models into the pipeline claim."""
    spec = backend_registry.get(backend)
    if spec.byte_model is None:
        raise ValueError(f"backend {backend!r} registers no byte model")
    m1 = spec.byte_model(plan.plan1, penv.hop1)
    m2 = spec.byte_model(plan.plan2, penv.hop2)
    extra = plan.t_bytes if plan.t_resident else 0.0
    return PipelineFastModel(
        backend=spec.name, hop1=m1, hop2=m2, t_bytes=plan.t_bytes,
        t_resident=plan.t_resident,
        fast_bytes_needed=max(m1.fast_bytes_needed, m2.fast_bytes_needed)
        + extra,
    )


def check_pipeline_model(model: PipelineFastModel) -> list:
    """The composed model's consistency invariant: its claim must equal
    max(hop peaks) plus the resident intermediate counted exactly once.
    A model that adds ``t_bytes`` into both hops (or on top of their sum)
    inflates the claim — it still *dominates* any trace, which is exactly
    why domination alone cannot catch it and this equality check exists."""
    extra = model.t_bytes if model.t_resident else 0.0
    want = (max(model.hop1.fast_bytes_needed, model.hop2.fast_bytes_needed)
            + extra)
    if model.fast_bytes_needed != want:
        return [
            f"composed pipeline byte model is inconsistent: claims "
            f"{model.fast_bytes_needed:.0f} B but max(hop1 "
            f"{model.hop1.fast_bytes_needed:.0f}, hop2 "
            f"{model.hop2.fast_bytes_needed:.0f}) + resident intermediate "
            f"{extra:.0f} = {want:.0f} B — the intermediate persists across "
            f"both hops and must be counted exactly once"]
    return []


def pipeline_audit_traces(A: CSR, P: CSR, R: CSR, plan: PipelinePlan,
                          backend: str,
                          caps: PipelineCaps | None = None) -> list:
    """Stage both hops' cores for abstract tracing, exactly as the executor
    would. Returns ``[(hop_label, TraceTarget, hop_plan, hop_envelope),
    ...]``; hop 2 is staged against the intermediate's exact *pattern* (the
    audit never needs numeric values). ``whole_fast`` hops have no chunked
    core and are omitted."""
    spec = backend_registry.get(backend)
    if not spec.supports_audit:
        raise ValueError(f"backend {backend!r} registers no audit_trace")
    if caps is None:
        caps = pipeline_output_caps(A, P, R, plan.plan1.p_ac, plan.plan2.p_ac)
    penv = pipeline_envelope(A, P, R, plan, caps)
    out = []
    for label, X, Y, hplan, henv in (
            ("hop1", A, P, plan.plan1, penv.hop1),
            ("hop2", R, caps.t_pattern, plan.plan2, penv.hop2)):
        if hplan.algorithm == "whole_fast":
            continue
        target = spec.audit_trace(X, Y, hplan, henv.c_pad, henv)
        out.append((label, target, hplan, henv))
    return out


def audit_pipeline(A: CSR, P: CSR, R: CSR, plan: PipelinePlan,
                   backend: str = "sparse",
                   caps: PipelineCaps | None = None):
    """Static audit of one pipeline: trace each hop's core, check the
    backend's per-hop byte model dominates each traced VMEM footprint, and
    hold the composed :class:`PipelineFastModel` to its once-counted
    resident-intermediate invariant *and* to dominating the traced two-hop
    peak. Returns ``(record, violations)`` in the shape of
    ``repro.analysis.report.audit_backend_case``."""
    import jax

    from repro.analysis.vmem import audit_vmem

    if caps is None:
        caps = pipeline_output_caps(A, P, R, plan.plan1.p_ac, plan.plan2.p_ac)
    penv = pipeline_envelope(A, P, R, plan, caps)
    model = pipeline_fast_model(plan, penv, backend)
    violations = list(check_pipeline_model(model))
    record = {"backend": backend, "t_resident": plan.t_resident,
              "t_bytes": plan.t_bytes, "hops": {}}
    traced_peak = 0.0
    spec = backend_registry.get(backend)
    for label, target, hplan, henv in pipeline_audit_traces(
            A, P, R, plan, backend, caps=caps):
        traced = jax.make_jaxpr(target.fn)(*target.args)
        hmodel = spec.byte_model(hplan, henv)
        vaudit = audit_vmem(traced, hmodel)
        if vaudit.dominated is False:
            violations.append(
                f"{label}: byte model undercounts the traced VMEM footprint "
                f"(model {vaudit.model_bytes:.0f} B < traced "
                f"{vaudit.traced_bytes:.0f} B)")
        traced_peak = max(traced_peak, vaudit.traced_bytes)
        record["hops"][label] = dataclasses.asdict(vaudit)
    resident_extra = plan.t_bytes if plan.t_resident else 0.0
    if traced_peak and model.fast_bytes_needed < traced_peak + resident_extra:
        violations.append(
            f"composed model {model.fast_bytes_needed:.0f} B does not cover "
            f"the traced two-hop peak {traced_peak:.0f} B plus the resident "
            f"intermediate {resident_extra:.0f} B")
    record["fast_bytes_needed"] = model.fast_bytes_needed
    record["traced_peak"] = traced_peak
    record["n_violations"] = len(violations)
    return record, violations
