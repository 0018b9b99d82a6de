"""Backend registry: one `BackendSpec` per chunked-SpGEMM backend.

Before this module, adding a backend meant a wiring pass across the whole
stack: an `if/elif` arm in ``chunked_spgemm``, another in
``chunked_spgemm_batched``, a hand-built ``_*_CORES_BATCHED`` dict, a
trace-suffix entry in ``SpGEMMService``, a byte model hooked into the
planner's accumulator tuple, and hand-maintained backend lists in the
conformance suite and CI smoke lanes. The registry collapses all of that
into **one registration call**: a backend ships its kernel module plus a
:class:`BackendSpec`, and the dispatchers (``chunked_spgemm``,
``chunked_spgemm_batched``, ``SpGEMMService``), the planner's ``auto``
resolve, the conformance matrix, and the bench/CI lane lists all derive
from ``specs()`` / ``all_backends()``.

The spec is deliberately thin — callables, templates, and capability
flags — so the registry stays import-light: this module imports nothing
from the rest of the package at module scope. Registrations live at the
bottom of ``repro.core.chunk_stream`` (the module that owns the executor
cores); :func:`ensure_registered` imports it on first use, which keeps
``import repro.core.backend_registry`` free of JAX work.

Contracts a spec must honor (enforced by the conformance suite's
registry-completeness test):

* ``executors`` maps every plan algorithm (``knl``/``chunk1``/``chunk2``)
  to an unbatched executor ``fn(A, B, plan, c_pad, ...) -> (C, ChunkStats)``.
  Executors with ``needs_output_caps`` additionally receive the symbolic
  phase's ``StripOutputCaps`` as ``caps=`` (the dispatcher amortizes the
  host expansion).
* ``run_batched(As, Bs, plan, envelope, *, caps_list, validate_caps)``
  runs the whole microbatch under a shared
  :class:`~repro.sparse.csr.GeometryEnvelope`; ``None`` means the backend
  is unbatched-only (the host-loop oracle).
* ``trace_key`` / ``trace_key_batched`` are ``"{alg}"``-templates naming
  the backend's ``TRACE_COUNTS`` keys — the compile-accounting contract
  the serving layer and the exact trace-count tests pin.
* ``byte_model(plan, envelope) -> BackendFastModel`` is the planner-side
  peak-resident model ``backend="auto"`` argmins over; accumulator
  backends (``is_accumulator``) must provide one. A model may return an
  infinite ``fast_bytes_needed`` when the envelope lacks the fields it
  prices (the BSR model without block caps), which excludes the backend
  from that resolve without special-casing the planner.
* ``needs_block_caps`` marks backends whose compile geometry is the
  envelope's ``bsr_caps`` block bounds; the dispatchers build/require
  block-capped envelopes for them, using ``block_size`` as the default
  block edge.
* ``mosaic_refusal`` names why the TPU compiler refuses a backend's kernel
  (``None``: it compiles). ``auto`` never considers such a backend, on any
  platform, and :func:`get` refuses to hand it out on the TPU — so the
  refusal is raised before anything is compiled. ``tests/test_tpu_compile.py``
  compiles every backend for a described v5e and holds each flag to the
  compiler's verdict.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping

ALGORITHMS = ("knl", "chunk1", "chunk2")


@dataclasses.dataclass(frozen=True)
class OpFlow:
    """Per-operand copy-event model: the ordered byte sizes of every
    slow->fast (or fast->slow) copy one pallas operand performs across the
    whole grid. ``key`` names the logical operand (several CSR field
    operands may share one key — their per-event bytes then sum into the
    single ``ChunkStats`` event the executor logs)."""

    key: str
    events: tuple     # ordered per-copy byte sizes, one float per copy event


@dataclasses.dataclass(frozen=True)
class ExpectedTraffic:
    """A backend's declared data-movement model for one staged core:
    the per-operand copy-event lists the traced jaxpr must reproduce
    *exactly* (``analysis/traffic.py`` checks equality, not domination),
    plus the ``ChunkStats``-granularity event lists the executors report
    (same-key operand flows merged event-wise). ``stats_exempt`` names a
    documented reason the stats tie is skipped (e.g. the BSR executor's
    per-pair host staging loop, which the pipeline-model stats
    intentionally idealize); the per-operand flow check still applies."""

    in_ops: tuple                  # tuple[OpFlow, ...], slow->fast
    out_ops: tuple                 # tuple[OpFlow, ...], fast->slow
    stats_in: tuple = ()           # ChunkStats.per_copy_in the executor logs
    stats_out: tuple = ()          # ChunkStats.per_copy_out the executor logs
    stats_exempt: str | None = None


@dataclasses.dataclass(frozen=True)
class TraceTarget:
    """An abstract-traceable handle on one backend core: ``fn(*args)`` must
    trace under ``jax.make_jaxpr`` without device execution (statics already
    bound into ``fn``). This is what a spec's ``audit_trace`` builds and what
    every ``repro.analysis`` pass consumes — the registry-level audit
    capability, kept here so the analysis package and the executor module
    never import each other."""

    fn: Callable
    args: tuple
    meta: Mapping[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class BackendSpec:
    """Everything the dispatch/planning/serving layers need to run a backend."""

    name: str
    executors: Mapping[str, Callable]           # algorithm -> unbatched executor
    run_batched: Callable | None = None         # batched entry; None = unbatched-only
    byte_model: Callable | None = None          # (plan, envelope) -> BackendFastModel
    trace_key: str | None = None                # "{alg}"-template, unbatched cores
    trace_key_batched: str | None = None        # "{alg}"-template, batched cores
    needs_output_caps: bool = False             # executor takes caps=StripOutputCaps
    needs_block_caps: bool = False              # envelope must carry bsr_caps
    is_accumulator: bool = False                # participates in backend="auto"
    block_size: int | None = None               # default block edge (block backends)
    # mask capability: the backend can fuse an output mask into its merge —
    # ``run_masked(A, B, mask, plan, c_pad, caps=...) -> (C, ChunkStats)``
    # computes ``(A x B) ∘ mask`` with the mask applied *inside* the kernel
    # (no unmasked C ever materialized). None = unmasked-only; the fused
    # triangle-counting path (repro.core.triangle) resolves through this.
    run_masked: Callable | None = None
    # audit capability: (A, B, plan, c_pad, envelope) -> TraceTarget staging
    # the backend's jitted core exactly as the executors would, so the static
    # verifier (repro.analysis) can abstract-trace it. None = not auditable
    # (the host-loop oracle has no jitted core).
    audit_trace: Callable | None = None
    # traffic capability: (A, B, plan, c_pad, envelope, meta) -> ExpectedTraffic,
    # the per-copy-event byte model `analysis/traffic.py` holds the traced
    # jaxpr to (exact equality). `meta` is the TraceTarget.meta of the
    # matching audit_trace — the statics (scalar-prefetch tables, chunk
    # counts) both sides were staged from, and the ChunkStats its staging
    # logs (`meta["stats"]`). None = flow equality not checked
    # (the scan backend is device-resident: its stats are a replay oracle by
    # design, with no per-chunk pallas copies to reconcile).
    traffic_model: Callable | None = None
    # executable-cache capability: ``(donate=False) -> dict`` building a
    # FRESH set of jitted batched cores (same keying the module-level cores
    # use), passed back into ``run_batched(..., cores=...)``. Module-level
    # cores live in module-global jit caches — dropping a serving bucket
    # would never free its executables. A per-bucket core set makes the
    # bucket the sole owner of its compiled programs, so evicting the bucket
    # really frees them (and a refault really recompiles). ``donate=True``
    # additionally donates the staged batch buffers (the C accumulator
    # stacks) into the cores. None = backend has no batched cores to scope.
    make_batched_cores: Callable | None = None
    # why Mosaic refuses to compile this backend's kernel for the TPU;
    # None = it compiles (see the module docstring)
    mosaic_refusal: str | None = None

    @property
    def supports_batched(self) -> bool:
        return self.run_batched is not None

    @property
    def supports_audit(self) -> bool:
        return self.audit_trace is not None

    @property
    def supports_traffic(self) -> bool:
        return self.traffic_model is not None

    @property
    def supports_mask(self) -> bool:
        return self.run_masked is not None


_REGISTRY: dict[str, BackendSpec] = {}


def register(spec: BackendSpec) -> BackendSpec:
    """Register a backend. Name collisions fail loudly — a duplicate
    registration is always a wiring bug (double import paths)."""
    if spec.name in _REGISTRY:
        raise ValueError(f"backend {spec.name!r} already registered")
    if spec.name == "auto":
        raise ValueError("'auto' is the dispatch mode, not a registrable backend")
    missing = [alg for alg in ALGORITHMS if alg not in spec.executors]
    if missing:
        raise ValueError(f"backend {spec.name!r} missing executors for {missing}")
    if spec.is_accumulator and spec.byte_model is None:
        raise ValueError(
            f"accumulator backend {spec.name!r} needs a planner byte model")
    # the trace-key contract: keys are per-algorithm, so a template without
    # the "{alg}" slot would collapse all three algorithms onto one counter
    # and silently break the serving layer's compile accounting. Fail at
    # import, where the registration lives, not at first format().
    for field in ("trace_key", "trace_key_batched"):
        template = getattr(spec, field)
        if template is not None and "{alg}" not in template:
            raise ValueError(
                f"backend {spec.name!r}: {field}={template!r} must contain "
                "the '{alg}' placeholder (one TRACE_COUNTS key per algorithm)")
    if spec.traffic_model is not None and spec.audit_trace is None:
        raise ValueError(
            f"backend {spec.name!r} registers a traffic_model without an "
            "audit_trace: the flow-equality analysis has no traced jaxpr "
            "to hold the model to")
    if spec.needs_block_caps and spec.block_size is None:
        raise ValueError(
            f"backend {spec.name!r} needs_block_caps but registers no "
            "block_size: the dispatchers could not build its default "
            "block-capped envelope")
    _REGISTRY[spec.name] = spec
    return spec


def ensure_registered() -> None:
    """Import the module that owns the executor cores (and therefore the
    registrations). Idempotent: module bodies run once."""
    import repro.core.chunk_stream  # noqa: F401  (registrations at module bottom)


def get(name: str) -> BackendSpec:
    """Resolve a backend name; unknown names raise the dispatcher's
    canonical error, and so does a backend whose kernel the TPU compiler
    refuses when the default device is a TPU."""
    import jax

    ensure_registered()
    spec = _REGISTRY.get(name)
    if spec is None:
        raise ValueError(f"unknown backend {name!r}")
    if spec.mosaic_refusal is not None and jax.default_backend() == "tpu":
        raise ValueError(
            f"backend {name!r} does not compile for the TPU: "
            f"{spec.mosaic_refusal}")
    return spec


def specs() -> tuple:
    """All registered specs, in registration order (the order is the
    planner's tie-break priority for accumulators)."""
    ensure_registered()
    return tuple(_REGISTRY.values())


def all_backends() -> tuple:
    """Registered backend names, registration order (excludes ``auto``)."""
    return tuple(s.name for s in specs())


def batched_backends() -> tuple:
    """Names of backends with a batched entry point."""
    return tuple(s.name for s in specs() if s.supports_batched)


def accumulator_specs() -> tuple:
    """Specs with a planner byte model (accumulators), priority order."""
    return tuple(s for s in specs() if s.is_accumulator)


def auto_specs() -> tuple:
    """Specs the planner's ``auto`` resolve chooses among: the accumulators
    the TPU compiler accepts, priority order."""
    return tuple(s for s in accumulator_specs() if s.mosaic_refusal is None)


def masked_backends() -> tuple:
    """Names of backends that can fuse an output mask into their kernel."""
    return tuple(s.name for s in specs() if s.supports_mask)
