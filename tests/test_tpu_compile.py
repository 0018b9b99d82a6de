"""Compile the chip path for a described TPU v5e, without a chip.

The TPU compiler is installed with JAX and compiles for a chip that is
described rather than attached. Every backend core the chip path can run
(``scan``, ``pallas`` in all three streaming orders, ``bsr``) is compiled
here at real widths, and every backend flagged ``mosaic_refusal`` is held to
that flag: its compile must be refused, so the flag cannot go stale.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library, and
the test workers each import every test file.

Code that asks ``jax.default_backend()`` still sees the CPU here, so the
``on_tpu`` fixture steers that one call for the duration of a test.
"""

import os

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import backend_registry
from repro.core.chunking import instance_envelope
from repro.core.planner import ChunkPlan, select_accumulator_backend
from repro.sparse import multigrid

ALGORITHMS = backend_registry.ALGORITHMS
SPECS = {s.name: s for s in backend_registry.specs()}
LOWERING = [n for n, s in SPECS.items()
            if s.supports_audit and s.mosaic_refusal is None]
REFUSED = [n for n, s in SPECS.items() if s.mosaic_refusal is not None]
# brick3d grid edge per backend: the 27-point operator times the factor-2
# prolongation, as large as a compile of about two seconds allows. The Pallas
# cores' compile time is their kernel's (lane-dense slabs at grid 12, 8x8
# MXU tiles for bsr); scan's is its two sorts', which grows with the product
# count (~15 s from 10^5 products up), so scan compiles at a small grid here
# and at the HPCG grid in chip_smoke.py.
GRID = {"scan": 4, "pallas": 12, "bsr": 8}
# What Mosaic's lowering says when it refuses a kernel, per streaming order
# of the CSR-scratch backends: the knl order stages whole operands and fails
# inside the body; the strip orders fail first on their (1, 1, cap) blocks.
REFUSAL_TEXT = {
    ("sparse", "knl"): r"not a fori_loop index",
    ("hash", "knl"): r"Unimplemented primitive in Pallas TPU lowering.*"
                     r"dynamic_slice",
    "strips": r"block shape are divisible by 8 and 128",
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no topology
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Make the platform test answer "tpu" for one test. Jit caches are
    dropped on both sides so no CPU (interpret-mode) trace is reused and
    none leaks out."""
    jax.clear_caches()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    yield
    jax.clear_caches()


def _plan(algorithm: str, A, B) -> ChunkPlan:
    def thirds(n):
        return (0, n // 3, 2 * n // 3, n)

    p_ac = (0, A.n_rows) if algorithm == "knl" else thirds(A.n_rows)
    return ChunkPlan(algorithm, p_ac, thirds(B.n_rows), 0.0, 0.0)


def _staged_target(backend: str, algorithm: str, grid: int):
    A, _, P = multigrid.problem("brick3d", grid)
    plan = _plan(algorithm, A, P)
    spec = SPECS[backend]
    env = instance_envelope(A, P, plan, block_size=spec.block_size)
    return spec.audit_trace(A, P, plan, env.c_pad, env)


def _compile(target, sharding):
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        target.args)
    return jax.jit(target.fn).lower(*shapes).compile()


@pytest.mark.parametrize("algorithm", ALGORITHMS)
@pytest.mark.parametrize("backend", LOWERING)
def test_core_compiles_for_v5e(backend, algorithm, one_chip, on_tpu):
    target = _staged_target(backend, algorithm, GRID[backend])
    text = _compile(target, one_chip).as_text()
    # a Pallas backend's kernel must be a Mosaic custom call on the chip
    has_kernel = "tpu_custom_call" in text
    assert has_kernel == (backend != "scan"), backend


@pytest.mark.parametrize("backend", [n for n in LOWERING if n != "scan"])
def test_kernel_dots_keep_f32(backend):
    """Mosaic's default f32 dot rounds both operands to bf16, which the
    interpreter does not: entries whose terms cancel then come out zero on
    the chip alone. Every dot in a chip kernel must ask for HIGHEST."""
    from repro.analysis import jaxpr_tools

    target = _staged_target(backend, "chunk1", 4)
    jaxpr = jax.make_jaxpr(target.fn)(*target.args)
    dots = [eqn for call in jaxpr_tools.pallas_calls(jaxpr)
            for eqn in jaxpr_tools.find_eqns(jaxpr_tools.kernel_jaxpr(call),
                                             "dot_general")]
    assert dots, backend
    highest = jax.lax.Precision.HIGHEST
    assert all(eqn.params["precision"] == (highest, highest)
               for eqn in dots), [eqn.params["precision"] for eqn in dots]


@pytest.mark.parametrize("algorithm", ALGORITHMS)
@pytest.mark.parametrize("backend", REFUSED)
def test_refused_backend_does_not_compile(backend, algorithm, one_chip,
                                          on_tpu):
    """A backend flagged ``mosaic_refusal`` must really be refused; when
    the compiler starts accepting it, the flag has to go."""
    from repro.analysis import corpus

    A, B = corpus.build_case("skewed_rows")
    plan = corpus.make_plan(algorithm, A, B)
    env = instance_envelope(A, B, plan)
    target = SPECS[backend].audit_trace(A, B, plan, env.c_pad, env)
    text = REFUSAL_TEXT.get((backend, algorithm), REFUSAL_TEXT["strips"])
    with pytest.raises((NotImplementedError, ValueError), match=text):
        _compile(target, one_chip)


@pytest.mark.parametrize("backend", REFUSED)
def test_refused_backend_raises_before_compiling_on_tpu(backend, on_tpu):
    from repro.core.chunking import chunked_spgemm
    from repro.analysis import corpus

    A, B = corpus.build_case("skewed_rows")
    plan = corpus.make_plan("chunk1", A, B)
    with pytest.raises(ValueError, match="does not compile for the TPU"):
        chunked_spgemm(A, B, plan, backend=backend)
    assert SPECS[backend].mosaic_refusal


def test_count_triangles_refused_on_tpu_and_kkmem_runs(on_tpu):
    from repro.core.triangle import (
        count_triangles, count_triangles_dense, count_triangles_kkmem,
    )
    from repro.sparse import graphs

    L = graphs.lower_triangular_degree_sorted(graphs.rmat(6, 4, seed=3))
    with pytest.raises(ValueError, match="does not compile for the TPU"):
        count_triangles(L)
    assert float(count_triangles_kkmem(L)) == float(count_triangles_dense(L))


def test_auto_picks_only_lowering_backends():
    """``auto`` chooses among the compiled backends on every platform."""
    A, _, P = multigrid.problem("laplace3d", 6)
    for algorithm in ALGORITHMS:
        plan = _plan(algorithm, A, P)
        for block in (None, 8):
            env = instance_envelope(A, P, plan, block_size=block)
            pick = select_accumulator_backend(plan, env)
            assert SPECS[pick].mosaic_refusal is None, (algorithm, pick)


def test_default_interpret_is_keyed_on_platform(monkeypatch):
    from repro.kernels.ranged_spgemm import default_interpret

    for platform, interpret in (("cpu", True), ("tpu", False)):
        monkeypatch.setattr(jax, "default_backend", lambda p=platform: p)
        assert default_interpret() is interpret
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="no path for platform 'gpu'"):
        default_interpret()


def test_vmem_limit_covers_the_kernel_and_caps_at_the_chip():
    from repro.kernels import scoped_vmem

    small = scoped_vmem.compiler_params(blocked=[((8, 128), np.float32)])
    assert small.vmem_limit_bytes == scoped_vmem.DEFAULT_SCOPED_VMEM_BYTES
    big = ((2048, 4096), np.float32)              # 32 MiB, two buffers
    params = scoped_vmem.compiler_params(blocked=[big])
    assert params.vmem_limit_bytes >= 2 * (32 << 20)
    assert params.vmem_limit_bytes <= scoped_vmem.VMEM_CAPACITY_BYTES
    with pytest.raises(ValueError, match="more than the chip"):
        scoped_vmem.compiler_params(blocked=[big, big])
    # a narrow block still occupies whole (8, 128) tiles
    assert scoped_vmem.tiled_nbytes((5, 9), np.float32) == 8 * 128 * 4
