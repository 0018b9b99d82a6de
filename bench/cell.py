"""One run of one cell: set-up, the measured window, and the check.

Set-up builds the operators on the host from the configuration, hands them
to the program, runs the traffic mix's symbolic work and planning, and one
warm-up product. The window then runs products back to back in one closed
loop, each on fresh values of A made on the device from the seed, starting
products while the window lasts and finishing every product it starts.
Once the window has closed and the device's peak memory has been read, each
product of the window is compared with the plain reference.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

from bench import operators, reference, registry, work

COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class Spans:
    """The benchmark's own host spans, on ``time.perf_counter``, mirrored
    into the profiler's trace as ``bench/<name>`` annotations."""

    def __init__(self):
        self.items = []

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench/{name}"):
            yield
        self.items.append((name, t0, time.perf_counter()))

    def total(self, name: str) -> float:
        return sum(t1 - t0 for n, t0, t1 in self.items if n == name)


class CompileLog:
    """Times of JAX's lowering events: one per executable built, whether its
    binary then comes from the compilation cache or from the compiler."""

    def __init__(self):
        import jax

        self.times = []
        self._listener = lambda name, *_, **__: (
            self.times.append(time.perf_counter()) if name == COMPILE_EVENT else None)
        jax.monitoring.register_event_duration_secs_listener(self._listener)

    def close(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._listener)

    def count(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.times)


def seed_key(seed: int):
    """A JAX key from any whole number, however large."""
    import jax

    state = np.random.SeedSequence(seed % 2**128).generate_state(1)[0]
    return jax.random.key(int(state))


def make_values(base, seed: int, scale):
    """``values(k)``: A's entries times uniform factors in ``scale``, drawn
    on the device for product ``k``; the same seed and ``k`` give the same
    values."""
    import jax

    key = seed_key(seed)
    lo, hi = (float(v) for v in scale)

    @jax.jit
    def scaled(base, key, k):
        u = jax.random.uniform(jax.random.fold_in(key, k), base.shape,
                               base.dtype, lo, hi)
        return base * u

    return lambda k: scaled(base, key, k)


@dataclasses.dataclass
class Run:
    """What one run measured; the metric readers take their numbers from it."""

    cell: object            # run.Cell: the workload, its files and the peaks
    setup_s: float
    spans: Spans
    products: list          # [(k, start, end)] on perf_counter
    window: tuple           # (start, end of the last product)
    work: dict              # per product: multiply_adds, flops, bytes
    window_compiles: int
    memory_peak_bytes: int
    readings: dict          # worst of each compared number over the products
    failed: int
    trace: object = None    # trace.Summary of the window, with --trace 1

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def peak_bytes(stats: dict) -> int:
    """Peak device memory of a process: its buffers' peak plus the peak
    the TPU runtime reserved for its programs' temporaries, which
    ``peak_bytes_in_use`` leaves out."""
    return stats.get("peak_bytes_in_use", 0) + stats.get("peak_bytes_reserved", 0)


def _host(C):
    import jax

    return jax.device_get((C.indptr, C.indices, C.data))


def run_cell(cell, seed: int, seconds: float, trace_dir, system, devices,
             t_start: float, log=print, wrap=None) -> Run:
    """Set-up, window and check of ``cell`` (``run.Cell``). ``trace_dir``
    set means the window runs under the profiler, writing there. ``wrap``,
    given, takes the ``program.Product`` and returns the call to time in
    place of its own (the control and the fault tests)."""
    import jax

    spans = Spans()
    with spans.span("operators"):
        ops = operators.build(cell.config, cell.root)
    product = registry.load("products", cell.traffic["product"], cell.root).prepare(
        cell.traffic, ops, system, spans)
    call = wrap(product) if wrap else product.call
    log(f"plan: {product.plan}")
    base = jax.device_put(ops.A.data.astype(np.float32), devices[0])
    values = make_values(base, seed, cell.traffic["a_value_scale"])
    with spans.span("warmup"):
        jax.block_until_ready(call(values(0)))
    setup_s = time.perf_counter() - t_start

    compiles = CompileLog()
    try:
        if trace_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 1
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        outputs, done = [], []
        with spans.span("window"):
            t0 = time.perf_counter()
            k = 1
            while not done or time.perf_counter() - t0 < seconds:
                with spans.span(f"product {k}"):
                    start = time.perf_counter()
                    C = jax.block_until_ready(call(values(k)))
                    done.append((k, start, time.perf_counter()))
                outputs.append(_host(C))
                k += 1
        if trace_dir is not None:
            jax.profiler.stop_trace()
    finally:
        compiles.close()
    window = (t0, done[-1][2])
    window_compiles = compiles.count(*window)
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(peak_bytes(st) for st in stats)
    log(f"memory stats: {stats}")
    log("product seconds: " + " ".join(f"{e - s:.4f}" for _, s, e in done))
    del C, call
    product.call = None                 # frees the plan and caps it holds

    limits = cell.limits
    worst = dict.fromkeys(reference.NUMBERS, 0)
    failed = 0
    for (k, _, _), out in zip(done, outputs):
        readings = reference.compare(out, product.factors(np.asarray(values(k))))
        failed += not reference.verdict(readings, limits)
        worst = {n: max(worst[n], readings[n]) for n in reference.NUMBERS}
    return Run(
        cell=cell, setup_s=setup_s, spans=spans, products=done,
        window=window, work=work.product_work(product.factors(ops.A.data)),
        window_compiles=window_compiles, memory_peak_bytes=int(peak),
        readings=worst, failed=failed)
