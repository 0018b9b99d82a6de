"""Static backend auditor: positive corpus runs, negative fixtures proving
each analysis catches its bug class, registry validation, and the
same-envelope retrace pin.

Everything here is abstract tracing (``jax.make_jaxpr``) plus host
arithmetic — no kernel executes, so the whole file stays in the fast lane.
Corpus geometries use their own dims/seeds (211+), disjoint from the
conformance cases whose first-trace deltas are pinned exactly.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis import (
    audit_all, audit_vmem, build_program, check_dma_structure,
    check_interleave, check_lint, check_retrace, check_traffic, explore,
    lint_traced, normalize_analyses, simulate_schedule,
)
from repro.analysis import corpus
from repro.core import backend_registry
from repro.core.backend_registry import BackendSpec, TraceTarget
from repro.core.chunking import instance_envelope
from repro.kernels.dma_schedule import SlotSchedule, TWO_SLOT
from repro.kernels.hash_accum_spgemm import probe_step_bound


# ---------------------------------------------------------------------------
# positive: the shipped backends pass every analysis
# ---------------------------------------------------------------------------


def test_audit_clean_on_fast_corpus():
    """Every auditable backend x algorithm passes all analyses on the fast
    corpus subset (the CLI / static-audit CI job runs the full corpus)."""
    rep = audit_all(cases=["skewed_rows"])
    assert rep["ok"], rep["violations"]
    # every accumulator backend's byte model was actually domination-checked
    checked = {r["backend"] for r in rep["records"]
               if r["dominated"] is True}
    assert {"pallas", "sparse", "hash", "bsr"} <= checked
    # the host-loop oracle is the only non-auditable backend
    assert [s["backend"] for s in rep["skipped"]] == ["loop"]
    # flow equality actually ran on every backend with a traffic model, and
    # the scan backend's exemption is recorded, not silently skipped
    for r in rep["records"]:
        if r["backend"] in ("pallas", "sparse", "hash", "bsr"):
            assert r["traffic"]["checked"], r
            assert r["traffic"]["in_events"] > 0, r
        elif r["backend"] == "scan":
            assert r["traffic"]["checked"] is False
            assert "reason" in r["traffic"]
        # zero lint errors on the shipped kernels (warnings are the on-TPU
        # validation worklist and do not fail the audit)
        assert r["lint"]["counts"]["error"] == 0, r
    # the streaming backends' two-slot schedules were model-checked
    streamed = {r["backend"] for r in rep["records"]
                if any(s["ok"] for s in r["interleave"]["streams"])}
    assert {"pallas", "sparse", "hash"} <= streamed


def test_audit_analyses_subset():
    """The --analyses subset machinery: only the requested passes run and
    their record fields appear."""
    rep = audit_all(cases=["skewed_rows"], backends=["pallas"],
                    algorithms=["knl"], analyses=["lint"])
    assert rep["ok"]
    assert rep["analyses"] == ["lint"]
    (record,) = rep["records"]
    assert "lint" in record and "vmem" not in record and "traffic" not in record
    with pytest.raises(ValueError, match="unknown analyses"):
        normalize_analyses(["lint", "nonsense"])


def test_schedule_simulation_race_free():
    for total in (0, 1, 2, 3, 7, 12):
        assert simulate_schedule(total) == []


def test_retrace_identical_across_backends():
    """Same envelope, different instance data => byte-identical jaxprs, for
    every registered backend with a jitted core (the compile-key pin)."""
    backend_registry.ensure_registered()
    A, B = corpus.build_case("dense_row")
    A2, B2 = corpus.retrace_pair(A, B)
    for spec in backend_registry.specs():
        if not spec.supports_audit:
            continue
        for algorithm in ("knl", "chunk2"):
            plan = corpus.make_plan(algorithm, A, B)
            block = spec.block_size if spec.needs_block_caps else None
            env = instance_envelope(A, B, plan, block_size=block).union(
                instance_envelope(A2, B2, plan, block_size=block))
            t1 = spec.audit_trace(A, B, plan, env.c_pad, env)
            t2 = spec.audit_trace(A2, B2, plan, env.c_pad, env)
            assert check_retrace(t1, t2) == [], (spec.name, algorithm)


# ---------------------------------------------------------------------------
# negative fixtures: each analysis demonstrably catches its bug class
# ---------------------------------------------------------------------------


def test_undercounting_byte_model_is_flagged():
    """A model claiming fewer bytes than the trace stages must fail the
    domination check — the planner-undercount bug class."""
    spec = backend_registry.get("sparse")
    A, B = corpus.build_case("skewed_rows")
    plan = corpus.make_plan("chunk1", A, B)
    env = instance_envelope(A, B, plan)
    target = spec.audit_trace(A, B, plan, env.c_pad, env)
    traced = jax.make_jaxpr(target.fn)(*target.args)
    honest = spec.byte_model(plan, env)
    assert audit_vmem(traced, honest).dominated is True
    lying = dataclasses.replace(honest, fast_bytes_needed=64.0)
    assert audit_vmem(traced, lying).dominated is False


def _pipeline_fixture(frac=0.25):
    from repro.core.memory_model import P100
    from repro.core.planner import plan_pipeline
    from repro.core.symbolic import pipeline_output_caps
    from repro.sparse import multigrid

    A, R, P = multigrid.problem("laplace3d", 4)
    limit = float(A.nbytes() + P.nbytes() + R.nbytes()) * frac
    plan = plan_pipeline(A, P, R, P100, fast_limit_bytes=limit)
    caps = pipeline_output_caps(A, P, R, plan.plan1.p_ac, plan.plan2.p_ac)
    return A, P, R, plan, caps


def test_pipeline_audit_clean_on_chunked_hops():
    """Both hops of the two-hop pipeline trace and pass the vmem domination
    check plus the composed-model checks, for the sparse and hash backends."""
    from repro.core.pipeline_spgemm import audit_pipeline

    A, P, R, plan, caps = _pipeline_fixture()
    assert "whole_fast" not in (plan.plan1.algorithm, plan.plan2.algorithm)
    for backend in ("sparse", "hash"):
        record, violations = audit_pipeline(A, P, R, plan, backend=backend,
                                            caps=caps)
        assert violations == [], (backend, violations)
        assert set(record["hops"]) == {"hop1", "hop2"}
        for hop in record["hops"].values():
            assert hop["model_bytes"] >= hop["traced_bytes"]


def test_pipeline_double_counted_intermediate_is_flagged():
    """The negative fixture for the composed byte model: a model that adds
    the resident intermediate's bytes *twice* (once per hop) still dominates
    every trace — domination alone cannot catch it — but must fail the
    once-counted consistency invariant."""
    from repro.core.pipeline_spgemm import (
        check_pipeline_model, pipeline_envelope, pipeline_fast_model,
    )

    A, P, R, plan, caps = _pipeline_fixture()
    penv = pipeline_envelope(A, P, R, plan, caps)
    honest = pipeline_fast_model(plan, penv, "sparse")
    assert honest.t_bytes > 0
    assert check_pipeline_model(honest) == []
    double_counted = dataclasses.replace(
        honest, fast_bytes_needed=honest.fast_bytes_needed + honest.t_bytes)
    violations = check_pipeline_model(double_counted)
    assert violations and "counted exactly once" in violations[0]


class _SlotAliasingSchedule(SlotSchedule):
    """Broken schedule: the prefetch targets the slot being read."""

    def prefetch_slot(self, lin):
        return self.read_slot(lin)


class _OneSlotSchedule(SlotSchedule):
    """Broken schedule: single-slot 'double' buffer (every copy collides)."""

    n_slots = 1


def test_slot_aliasing_schedule_is_flagged():
    violations = simulate_schedule(6, _SlotAliasingSchedule())
    assert any("write-after-read race" in v for v in violations)
    assert simulate_schedule(6, TWO_SLOT) == []


def test_one_slot_schedule_is_rejected_at_construction():
    """Below two slots, every prefetch collides with the read by
    construction — the schedule class refuses to exist."""
    with pytest.raises(ValueError, match="n_slots >= 2"):
        _OneSlotSchedule()


def test_schedule_replay_edge_cases():
    """Host-replay boundary conditions: an empty stream has nothing to
    race, a single-chunk stream is prime-only (no prefetch), and wider
    double buffers replay clean too."""
    assert simulate_schedule(0) == []
    assert simulate_schedule(1) == []

    class _ThreeSlot(SlotSchedule):
        n_slots = 3

    for total in (0, 1, 2, 5, 9):
        assert simulate_schedule(total, _ThreeSlot()) == []


def _toy_missing_wait_core():
    """A two-slot-shaped kernel that starts a DMA and reads the buffer
    without ever waiting — the unsynchronized-read bug class."""

    def kernel(x_hbm, o_ref, buf, sem):
        pltpu.make_async_copy(x_hbm, buf.at[0], sem.at[0]).start()
        o_ref[...] = buf[0]

    @jax.jit
    def core(x):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=0,
                grid=(1,),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec(x.shape, lambda i: (0, 0)),
                scratch_shapes=[
                    pltpu.VMEM((2,) + x.shape, jnp.float32),
                    pltpu.SemaphoreType.DMA((2,)),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
            interpret=True,
        )(x)

    return core


def test_missing_dma_wait_is_flagged():
    core = _toy_missing_wait_core()
    traced = jax.make_jaxpr(core)(jnp.ones((4, 8), jnp.float32))
    violations = check_dma_structure(traced)
    assert any("no dma_wait" in v for v in violations)
    assert any("read before any dma_wait" in v
               or "before any dma_wait" in v for v in violations)


def test_leaked_python_scalar_is_flagged():
    """A core that bakes a value from the instance *data* into the trace
    diverges between same-envelope instances — the silent-retrace bug."""
    A, _ = corpus.build_case("skewed_rows")
    A2, _ = corpus.retrace_pair(A, A)
    cap = max(np.asarray(A.data).size, np.asarray(A2.data).size)

    def make_target(M):
        leak = float(np.asarray(M.data)[0])   # Python scalar from the data
        staged = np.zeros(cap, np.float32)    # envelope-shaped staging
        staged[: np.asarray(M.data).size] = np.asarray(M.data)

        def core(data):
            return data * leak

        return TraceTarget(fn=jax.jit(core), args=(jnp.asarray(staged),))

    violations = check_retrace(make_target(A), make_target(A2))
    assert violations and "leaked" in violations[0]


def test_staging_aval_mismatch_is_flagged():
    a = TraceTarget(fn=jax.jit(lambda x: x), args=(jnp.ones((3,)),))
    b = TraceTarget(fn=jax.jit(lambda x: x), args=(jnp.ones((4,)),))
    violations = check_retrace(a, b)
    assert violations and "staging is broken" in violations[0]


def test_hash_probe_bound_matches_planner():
    """The hash kernel's while-loop bound is the planner's table size; an
    audit expecting a different bound must flag it."""
    from repro.analysis.dma import check_while_bounds

    spec = backend_registry.get("hash")
    A, B = corpus.build_case("duplicate_heavy")
    plan = corpus.make_plan("chunk1", A, B)
    env = instance_envelope(A, B, plan)
    target = spec.audit_trace(A, B, plan, env.c_pad, env)
    traced = jax.make_jaxpr(target.fn)(*target.args)
    bound = probe_step_bound(target.meta["table_size"])
    assert check_while_bounds(traced, expected_bound=bound) == []
    assert check_while_bounds(traced, expected_bound=bound + 1)


def _traced_and_expected(backend="pallas", algorithm="chunk1",
                         case="skewed_rows"):
    spec = backend_registry.get(backend)
    A, B = corpus.build_case(case)
    plan = corpus.make_plan(algorithm, A, B)
    block = spec.block_size if spec.needs_block_caps else None
    env = instance_envelope(A, B, plan, block_size=block)
    target = spec.audit_trace(A, B, plan, env.c_pad, env)
    traced = jax.make_jaxpr(target.fn)(*target.args)
    expected = spec.traffic_model(A, B, plan, env.c_pad, env, target.meta)
    return traced, expected, target.meta.get("scalar_args", ())


def test_traffic_flow_divergence_is_flagged():
    """A traffic model missing one copy event diverges from the trace —
    flow equality is per-event, so the diff names the extra traced copy."""
    traced, expected, scalars = _traced_and_expected()
    clean, _ = check_traffic(traced, expected, scalar_args=scalars)
    assert clean == []
    short = dataclasses.replace(
        expected.in_ops[1], events=expected.in_ops[1].events[:-1])
    tampered = dataclasses.replace(
        expected, in_ops=(expected.in_ops[0], short, expected.in_ops[2]))
    violations, _ = check_traffic(traced, tampered, scalar_args=scalars)
    assert any("copy events" in v and "slow->fast" in v for v in violations)


def test_traffic_stats_undercount_is_flagged():
    """A kernel moving more bytes than its ChunkStats report breaks the
    stats tie: the merged model flow no longer matches the logged events."""
    traced, expected, scalars = _traced_and_expected(backend="sparse")
    undercounted = dataclasses.replace(
        expected, stats_in=expected.stats_in[:-1])
    violations, _ = check_traffic(traced, undercounted, scalar_args=scalars)
    assert any("stats tie broken" in v for v in violations)
    assert any("absent from the stats" in v for v in violations)


def test_traffic_wrong_event_size_diff_names_the_event():
    """Per-event diff: a single wrong byte size is located by index."""
    traced, expected, scalars = _traced_and_expected()
    events = list(expected.in_ops[0].events)
    events[1] = events[1] + 4.0
    bad = dataclasses.replace(expected.in_ops[0], events=tuple(events))
    tampered = dataclasses.replace(
        expected, in_ops=(bad,) + expected.in_ops[1:])
    violations, _ = check_traffic(traced, tampered, scalar_args=scalars)
    assert any("first divergence at event 1" in v for v in violations)


def test_interleave_counterexample_on_aliasing_schedule():
    """The model checker proves the aliasing schedule unsafe with a
    *minimal* counterexample: two starts into the same slot, nothing else."""
    cex = explore(build_program(4, _SlotAliasingSchedule()), n_slots=2)
    assert cex is not None
    assert "still in flight" in cex.hazard
    assert len(cex.trace) == 2          # shortest possible witness
    text = cex.describe()
    assert "shortest interleaving" in text
    assert simulate_schedule(4, _SlotAliasingSchedule()) != []


def test_interleave_clean_on_two_slot_schedule():
    for total in (0, 1, 2, 6):
        for n_fields in (1, 3):
            ops = build_program(total, TWO_SLOT, n_fields)
            assert explore(ops, n_slots=2, n_fields=n_fields) is None


def test_interleave_deadlock_is_flagged():
    """A schedule that never primes slot 0 leaves step 0's wait forever
    unsatisfiable — reported as a deadlock, not an infinite search."""

    class _NoPrime(SlotSchedule):
        def is_prime_step(self, lin):
            return False

    cex = explore(build_program(3, _NoPrime()), n_slots=2)
    assert cex is not None and "deadlock" in cex.hazard


def test_interleave_checks_real_streaming_backends():
    traced, _, _ = _traced_and_expected(backend="sparse")
    violations, info = check_interleave(traced)
    assert violations == []
    assert info["streams"] and info["streams"][0]["n_fields"] == 3


def _toy_lintable_core(bound_ref: bool):
    """A kernel with a deliberately misaligned block shape and, when
    ``bound_ref`` is set, a while loop whose trip bound is read from a ref
    (statically unbounded — the lint's error class)."""

    def kernel(n_ref, o_ref):
        if bound_ref:
            def cond(c):
                return c < n_ref[0]
        else:
            def cond(c):
                return c < 7
        jax.lax.while_loop(cond, lambda c: c + 1, 0)
        o_ref[...] = jnp.zeros_like(o_ref)

    @jax.jit
    def core(n):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(1,),
                in_specs=[],
                out_specs=pl.BlockSpec((4, 40), lambda i, n: (0, 0)),
            ),
            out_shape=jax.ShapeDtypeStruct((4, 40), jnp.float32),
            interpret=True,
        )(n)

    return core


def test_lint_flags_nonstatic_while_bound():
    traced = jax.make_jaxpr(_toy_lintable_core(bound_ref=True))(
        jnp.arange(1, dtype=jnp.int32))
    violations, info = check_lint(traced)
    assert any("no statically evident trip bound" in v for v in violations)
    assert info["counts"]["error"] >= 1
    # the literal-bounded variant of the same kernel lints clean of errors
    clean = jax.make_jaxpr(_toy_lintable_core(bound_ref=False))(
        jnp.arange(1, dtype=jnp.int32))
    assert check_lint(clean)[0] == []


def test_lint_flags_misaligned_block_shape():
    traced = jax.make_jaxpr(_toy_lintable_core(bound_ref=False))(
        jnp.arange(1, dtype=jnp.int32))
    diags = lint_traced(traced)
    lane = [d for d in diags if d.check == "tile-alignment"
            and "lane dim 40" in d.message]
    assert lane and all(d.severity == "warning" for d in lane)
    sub = [d for d in diags if d.check == "tile-alignment"
           and "sublane dim 4" in d.message]
    assert sub


def test_lint_flags_untrusted_esc_and_hash_lanes():
    """The ROADMAP's untrusted primitives surface as warnings on the real
    sparse/hash kernels (the on-TPU validation worklist), never errors."""
    for backend in ("sparse", "hash"):
        traced, _, _ = _traced_and_expected(backend=backend)
        violations, info = check_lint(traced)
        assert violations == [], (backend, violations)
        suspects = [d for d in info["diagnostics"]
                    if d["check"] == "primitive-allowlist"
                    and d["severity"] == "warning"]
        assert suspects, backend
    assert any("sort" in d["where"] or "scatter" in d["where"]
               for d in suspects)


# ---------------------------------------------------------------------------
# registry validation (import-time spec contracts)
# ---------------------------------------------------------------------------


def _spec_kwargs(**overrides):
    base = {
        "name": "_audit_test_backend",
        "executors": dict.fromkeys(backend_registry.ALGORITHMS, lambda: None),
    }
    base.update(overrides)
    return base


def _expect_register_error(match, **overrides):
    spec = BackendSpec(**_spec_kwargs(**overrides))
    with pytest.raises(ValueError, match=match):
        backend_registry.register(spec)
    assert spec.name not in backend_registry._REGISTRY


def test_register_rejects_trace_key_without_alg_placeholder():
    _expect_register_error("'{alg}' placeholder",
                           trace_key="static_key_no_placeholder")


def test_register_rejects_batched_trace_key_without_alg_placeholder():
    _expect_register_error("'{alg}' placeholder",
                           trace_key="{alg}_ok",
                           trace_key_batched="batched_no_placeholder")


def test_register_rejects_block_caps_without_block_size():
    _expect_register_error("registers no\\s+block_size",
                           needs_block_caps=True)


def test_register_rejects_traffic_model_without_audit_trace():
    _expect_register_error(
        "traffic_model without an\\s+audit_trace",
        traffic_model=lambda *a: None)


def test_register_rejects_missing_executor():
    spec = BackendSpec(name="_audit_test_backend",
                       executors={"knl": lambda: None})
    with pytest.raises(ValueError, match="missing executors"):
        backend_registry.register(spec)


def test_registered_specs_satisfy_the_validated_contracts():
    """The shipped roster passes the new import-time validations (they ran
    at registration; re-assert the invariants directly)."""
    for spec in backend_registry.specs():
        for template in (spec.trace_key, spec.trace_key_batched):
            assert template is None or "{alg}" in template, spec.name
        if spec.needs_block_caps:
            assert spec.block_size is not None, spec.name
