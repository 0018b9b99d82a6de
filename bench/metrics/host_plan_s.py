"""Host seconds of set-up's symbolic work and planning: the ``host_plan``
span around the calls to ``spgemm_pattern_host``, the ``plan_*`` planners
and the ``*_output_caps`` symbolic phases."""


def read(run):
    seconds = run.spans.total("host_plan")
    return {"value": seconds} if seconds > 0 else None
