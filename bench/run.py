"""Run one cell of the SpGEMM benchmark once, on the chip it is started on.

    python3 bench/run.py --workload hpcg27.rap --seed 7 --seconds 10 --trace 0

The cell, its configuration, its traffic mix, its metrics and its limits
are found by name (``bench/registry.py``): ``BENCHMARK.json`` at the root of
the checkout names the cell's configuration (``bench/configs/<config>.json``,
whose stencil and restriction are ``bench/stencils/<stencil>.py`` and
``bench/restrictions/<restriction>.py``) and traffic
(``bench/traffic/<traffic>.json``, whose product is
``bench/products/<product>.py``); every metric is read by
``bench/metrics/<metric>.py``; the check's limits are
``bench/limits/<workload>.json``; the chip's peaks are ``bench/peaks.json``.

With ``--trace 0`` the result reports the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window. The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``check``, the compared numbers beside their
limits, which also end standard error. Without a TPU listed in
``peaks.json``, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import registry  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"


class NoDevice(RuntimeError):
    """No chip this benchmark can measure, or too few."""


@dataclasses.dataclass
class Cell:
    workload: dict
    config: dict
    traffic: dict
    limits: dict
    peaks: dict
    end_to_end: list        # metric entries of BENCHMARK.json
    per_layer: list
    root: Path              # the checkout whose files these are


def resolve(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files; its
    ``peaks`` are filled in once the device is known."""
    spec = registry.read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    config = next(c for c in spec["configs"] if c["name"] == w["config"])
    return Cell(
        workload=w,
        config=registry.read_json(root / config["file"]),
        traffic=registry.data("traffic", w["traffic"], root),
        limits=registry.data("limits", name, root),
        peaks={},
        end_to_end=spec["end_to_end"],
        per_layer=spec["per_layer"],
        root=root,
    )


def reader(metric: str, root: Path = ROOT):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``; it
    returns ``None`` where the run has nothing for the metric to read."""
    return registry.load("metrics", metric, root).read


def peaks_for(device_kind: str, root: Path = ROOT) -> dict:
    """The peaks of ``device_kind`` in ``bench/peaks.json``; a kind that is
    not listed is an error."""
    peaks = registry.read_json(root / "bench" / "peaks.json")
    if device_kind not in peaks:
        raise NoDevice(f"{device_kind!r} is not in peaks.json; listed {sorted(peaks)}")
    return peaks[device_kind]


def require_devices(chips: int):
    """The devices to run on: TPUs whose kind ``peaks.json`` lists, at
    least ``chips`` of them."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoDevice(f"no TPU: found {devices[0].platform} {devices[0].device_kind!r}")
    peaks_for(devices[0].device_kind)
    if len(devices) < chips:
        raise NoDevice(f"the cell asks for {chips} chips; found {len(devices)}")
    return devices[:chips]


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache: where ``JAX_COMPILATION_CACHE_DIR``
    says, else at the fixed ``.jax_cache/`` of this checkout, so that only
    the first run of a cell here compiles. Every executable is kept."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def measure(cell: Cell, seed: int, seconds: float, trace: bool, devices, system,
            t_start: float, log=None, wrap=None) -> dict:
    """One run of ``cell``; returns the result line's object."""
    from bench import cell as cell_mod, trace as trace_mod

    log = log or (lambda line: print(line, file=sys.stderr, flush=True))
    trace_dir = Path(tempfile.mkdtemp(prefix="bench-trace-")) if trace else None
    try:
        run = cell_mod.run_cell(cell, seed, seconds, trace_dir, system, devices,
                                t_start, log=log, wrap=wrap)
        if trace_dir is not None:
            files = sorted(trace_dir.rglob("*.xplane.pb"))
            run.trace = trace_mod.summarize(trace_mod.load(files[-1]))
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    entries = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in entries:
        got = reader(m["name"], cell.root)(run)
        if got is not None:
            metrics[m["name"]] = {**got, "unit": m["unit"]}
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind, "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": run.failed == 0 and len(run.products) > 0,
              "attempted": len(run.products), "failed": run.failed,
              "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = {"device_ops": run.trace.device_ops,
                               "idle_gaps": run.trace.idle_gaps}
    result["check"] = {n: {"value": v, "limit": cell.limits[n]}
                       for n, v in run.readings.items()}
    log(f"products {len(run.products)} in {run.window_s:.3f} s, set-up "
        f"{run.setup_s:.3f} s, per product {json.dumps(run.work)}")
    for n, v in result["check"].items():
        log(f"check {n} {v['value']!r} limit {v['limit']!r}")
    return result


def main(argv=None) -> int:
    t_start = time.perf_counter()
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"the program is not in this checkout ({ROOT / 'src' / 'repro'})",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        cell = resolve(args.workload)
        devices = require_devices(int(cell.workload["chips"]))
    except (KeyError, NoDevice) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    cell.peaks = peaks_for(devices[0].device_kind)
    enable_compile_cache()
    from repro.core.memory_model import memory_system_for

    result = measure(cell, args.seed, args.seconds, bool(args.trace), devices,
                     memory_system_for(devices[0].device_kind), t_start)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
