"""``R @ (A @ P)`` through ``pipeline_spgemm``, planned by ``plan_pipeline``
(the traffic file's ``planner`` is ``"pipeline"``). With ``caps``
``"setup"`` the composed symbolic phase runs once in set-up; with
``"per_call"`` the program runs it on every call."""

from bench import program


def prepare(traffic, ops, system, spans):
    from repro.core import planner, symbolic
    from repro.core.pipeline_spgemm import pipeline_spgemm

    if traffic["planner"] != "pipeline":
        raise ValueError(f"unknown planner {traffic['planner']!r} for R @ (A @ P)")
    kw = program.backend(traffic)
    A, P, R = (program.to_program(m) for m in (ops.A, ops.P, ops.R))
    with spans.span("host_plan"):
        T = symbolic.spgemm_pattern_host(A, P)
        plan = planner.plan_pipeline(A, P, R, system, t_pattern=T)
        caps = None
        if traffic["caps"] == "setup":
            caps = symbolic.pipeline_output_caps(
                A, P, R, plan.plan1.p_ac, plan.plan2.p_ac, t_pattern=T)

    def call(data):
        return pipeline_spgemm(program.with_values(A, data), P, R, plan,
                               caps=caps, **kw)[0]

    return program.Product(
        call, ops, ("R", "A", "P"),
        f"hop1 {program.describe(plan.plan1)}, hop2 {program.describe(plan.plan2)}, "
        f"t_resident={plan.t_resident}")
