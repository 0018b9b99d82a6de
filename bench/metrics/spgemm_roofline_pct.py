"""The least time the window's products need on this chip over the device's
busy time in the window, in percent. The least time of a product is the
larger of its required flops over the peak FLOP/s and its required bytes
over the peak HBM bandwidth (``bench/work.py``, ``bench/peaks.json``);
``bound`` says which of the two it was."""


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    n = len(run.products)
    compute = run.work["flops"] * n / run.cell.peaks["flops_per_s"]
    memory = run.work["bytes"] * n / run.cell.peaks["hbm_bytes_per_s"]
    return {"value": 100.0 * max(compute, memory) / run.trace.busy_s,
            "bound": "memory" if memory >= compute else "compute"}
