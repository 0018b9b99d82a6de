"""Required flops of the window's completed products (two per multiply-add,
counted from the operands by ``bench/work.py``), over the seconds from the
window's start to the end of its last product, in GFLOP/s."""


def read(run):
    return {"value": run.work["flops"] * len(run.products) / run.window_s / 1e9}
