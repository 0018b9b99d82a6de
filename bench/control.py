"""The control and the planted faults, run through the whole harness.

    python3 bench/control.py --workload hpcg27.rap --fault control \
        --seeds 1 2 3 --seconds 5

Each seed is one run of the cell in this process, with the timed call
replaced: ``control`` puts the reference in the program's place, computed
in bfloat16 (``reference.control_product``); ``altered_answer`` scales the
largest entry of the program's product by 1.01; ``half_rows_left_out``
drops every entry of the lower half of its rows. Each must come out as not
correct. One JSON line per seed gives the compared numbers. The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import collections
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]

HostCSR = collections.namedtuple("HostCSR", "indptr indices data")


def control(product):
    from bench import reference

    def call(values):
        factors = product.factors(np.asarray(values))
        return HostCSR(*reference.control_product(factors))

    return call


def _host(C) -> HostCSR:
    import jax

    return HostCSR(*(np.array(a) for a in jax.device_get((C.indptr, C.indices, C.data))))


def altered_answer(product):
    def call(values):
        C = _host(product.call(values))
        nnz = int(C.indptr[-1])
        i = int(np.argmax(np.abs(C.data[:nnz])))
        C.data[i] *= 1.01
        return C

    return call


def half_rows_left_out(product):
    def call(values):
        C = _host(product.call(values))
        half = (C.indptr.size - 1) // 2
        cut = int(C.indptr[half])
        indptr = np.minimum(C.indptr, cut)
        return HostCSR(indptr, C.indices[:cut], C.data[:cut])

    return call


FAULTS = {"control": control, "altered_answer": altered_answer,
          "half_rows_left_out": half_rows_left_out}


def main(argv=None) -> int:
    t_start = time.perf_counter()
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--fault", choices=sorted(FAULTS), default="control")
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--seconds", type=float, default=5.0)
    args = parser.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import run
    from repro.core.memory_model import memory_system_for

    cell = run.resolve(args.workload)
    devices = run.require_devices(int(cell.workload["chips"]))
    cell.peaks = run.peaks_for(devices[0].device_kind)
    run.enable_compile_cache()
    system = memory_system_for(devices[0].device_kind)
    for seed in args.seeds:
        result = run.measure(cell, seed, args.seconds, False, devices, system,
                             t_start, wrap=FAULTS[args.fault])
        print(json.dumps({"workload": args.workload, "fault": args.fault,
                          "seed": seed, "correct": result["correct"],
                          "attempted": result["attempted"],
                          "check": result["check"]}), flush=True)
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
