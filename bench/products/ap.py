"""``A @ P`` through ``chunked_spgemm``. The traffic file's ``planner``
sizes the plan (``"chunks"``: ``plan_chunks`` on the product's CSR rows;
``"dense_slab"``: ``plan_dense_slab``), and its ``caps`` says whether the
strips' output capacity comes from set-up (``"setup"``) or from the call
itself (``"per_call"``)."""

from bench import program


def prepare(traffic, ops, system, spans):
    from repro.core import planner, symbolic
    from repro.core.chunking import chunked_spgemm

    kw = program.backend(traffic)
    A, P = program.to_program(ops.A), program.to_program(ops.P)
    with spans.span("host_plan"):
        if traffic["planner"] == "chunks":
            T = symbolic.spgemm_pattern_host(A, P)
            plan = planner.plan_chunks(A, P, planner.row_bytes_csr(T), system)
        elif traffic["planner"] == "dense_slab":
            plan = planner.plan_dense_slab(A, P, system)
        else:
            raise ValueError(f"unknown planner {traffic['planner']!r} for A @ P")
        c_pad = None
        if traffic["caps"] == "setup":
            c_pad = symbolic.strip_output_caps(A, P, plan.p_ac).c_pad

    def call(data):
        return chunked_spgemm(program.with_values(A, data), P, plan, c_pad, **kw)[0]

    return program.Product(call, ops, ("A", "P"), program.describe(plan))
