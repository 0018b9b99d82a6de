"""Share of the traced window in which no operation ran on the device:
1 - (union of device-operation intervals) / window, in percent."""


def read(run):
    if run.trace is None:
        return None
    return {"value": 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)}
