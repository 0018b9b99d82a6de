"""The program's own host spans in a profiler trace, reduced beside the
device's operations.

The program writes ``spgemm/<phase>`` annotations (``repro.core.spans``) on
the profiler's clock, with their arguments and byte counts (``d2h_bytes``,
``h2d_bytes``) as event stats. This module reads them from the same
``.xplane.pb`` that ``bench/trace.py`` reads, and gives, for the window:

- each phase's own time (its duration less that of the program spans nested
  in it on its thread) and its summed byte counts;
- per product, what the program-span metrics read: ``window_symbolic_s``,
  ``stage_s``, ``assemble_s`` (``fetch`` + ``assemble``) and
  ``host_device_mb``;
- each idle gap of the device named after the benchmark span around it and
  the program span with the most own time in it, with its hop
  (``product 6: spgemm/fetch hop 2``), or by ``trace.py``'s Python frames
  where no program span overlaps it;
- the share of the device's idle time inside ``bench/product`` spans that
  some program span covers.

A trace without program spans reads 0 in every phase. Run on a trace file:

    python3 bench/program_spans.py <file.xplane.pb>

prints one JSON object.
"""

from __future__ import annotations

import collections
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

PREFIX = "spgemm/"
PRODUCT_SPAN = "bench/product"
COUNTS = ("d2h_bytes", "h2d_bytes")


def load(path) -> list:
    """The ``spgemm/*`` events of an ``.xplane.pb`` as ``(name, start_ns,
    end_ns, stats, thread)``; ``thread`` tells apart the host lines."""
    import jax

    out = []
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                out.extend((e.name, e.start_ns, e.end_ns, dict(e.stats), (plane.name, i))
                           for e in line.events if e.name.startswith(PREFIX))
    return out


def _by_thread(program) -> dict:
    """Each thread's spans as ``(label, start, end)``: the name, and for a
    span inside a hop span, ``hop <n>`` after it (a hop span is
    ``spgemm/hop <n>``)."""
    threads = collections.defaultdict(list)
    for name, s, e, stats, thread in program:
        threads[thread].append((name, s, e, stats))
    out = {}
    for thread, spans in threads.items():
        hops = [(s, e, st["hop"]) for n, s, e, st in spans if n == PREFIX + "hop"]
        named = []
        for n, s, e, st in spans:
            if n == PREFIX + "hop":
                named.append((f"{n} {st['hop']}", s, e))
                continue
            hop = next((h for hs, he, h in hops if hs <= s and e <= he), None)
            named.append((n if hop is None else f"{n} hop {hop}", s, e))
        out[thread] = named
    return out


def own_ns(program, lo: float, hi: float) -> collections.Counter:
    """Own time of each labelled span inside ``[lo, hi]``, summed over the
    threads; labels without own time there are left out."""
    own = collections.Counter()
    for spans in _by_thread(program).values():
        own.update(trace.self_ns(spans, lo, hi))
    return +own


def _phase(label: str) -> str:
    """``spgemm/fetch hop 2`` -> ``fetch``."""
    return label[len(PREFIX):].split(" ", 1)[0]


def phases(program, lo: float, hi: float) -> dict:
    """``{phase: {"self_s", "d2h_bytes", "h2d_bytes"}}`` over the spans
    that overlap ``[lo, hi]``; a span's counts go to the window it
    overlaps."""
    out = collections.defaultdict(lambda: dict.fromkeys(("self_s", *COUNTS), 0))
    for name, s, e, stats, _ in program:
        if e > lo and s < hi:
            p = out[_phase(name)]
            for k in COUNTS:
                p[k] += stats.get(k, 0)
    for label, t in own_ns(program, lo, hi).items():
        out[_phase(label)]["self_s"] += t / 1e9
    return dict(out)


def products(t: trace.Trace, lo: float, hi: float) -> list:
    """The ``bench/product`` spans inside ``[lo, hi]`` as ``(start, end)``."""
    return [(s, e) for n, s, e in t.spans
            if n.startswith(PRODUCT_SPAN) and lo <= s and e <= hi]


def metrics(t: trace.Trace, program) -> dict:
    """Per product of the window, what the program-span metrics read: the own
    seconds of ``symbolic``, ``stage``, and ``fetch`` plus ``assemble``, and
    the megabytes copied each way."""
    lo, hi = t.window()
    n = len(products(t, lo, hi))
    if n == 0:
        raise ValueError("no bench/product span inside the window")
    ph = phases(program, lo, hi)

    def total(key, *names):
        return sum(ph.get(name, {}).get(key, 0) for name in names or ph) / n

    d2h, h2d = total("d2h_bytes") / 1e6, total("h2d_bytes") / 1e6
    return {
        "window_symbolic_s": {"value": total("self_s", "symbolic")},
        "stage_s": {"value": total("self_s", "stage")},
        "assemble_s": {"value": total("self_s", "fetch", "assemble")},
        "host_device_mb": {"value": d2h + h2d, "d2h_mb": d2h, "h2d_mb": h2d},
    }


def label(gap, spans, program) -> str | None:
    """``<bench span>: <program span with the most own time in the gap>``,
    or ``None`` where no program span overlaps the gap."""
    g0, g1 = gap
    own = own_ns(program, g0, g1)
    if not own:
        return None
    mid = (g0 + g1) / 2
    around = [(e - s, n) for n, s, e in spans if s <= mid <= e and n != trace.WINDOW_SPAN]
    where = min(around)[1][len(trace.SPAN_PREFIX):] if around else "window"
    return f"{where}: {own.most_common(1)[0][0]}"


def _measure(intervals) -> float:
    return sum(e - s for s, e in trace.union(intervals))


def _intersect(a, b) -> list:
    """Intersection of two interval sets, each merged first."""
    a, b = trace.union(a), trace.union(b)
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _first_device(t: trace.Trace, lo: float, hi: float) -> list:
    ran = sorted(p for p, ops in t.device_ops.items() if trace.busy_ns(ops, lo, hi) > 0)
    if not ran:
        raise ValueError("no device operation ran inside the window")
    return t.device_ops[ran[0]]


def covered_share(t: trace.Trace, program) -> float | None:
    """Share of the first device's idle time inside ``bench/product`` spans
    that falls under some program span; ``None`` without idle time there."""
    lo, hi = t.window()
    idle = _intersect(trace.gaps(_first_device(t, lo, hi), lo, hi), products(t, lo, hi))
    total = _measure(idle)
    if total <= 0:
        return None
    return _measure(_intersect(idle, [(s, e) for _, s, e, _, _ in program])) / total


def summarize(t: trace.Trace, program, top: int = 10) -> dict:
    """The metrics, the phases of the window, the covered share, and the
    ``top`` longest idle gaps, labelled."""
    lo, hi = t.window()
    idle = sorted(trace.gaps(_first_device(t, lo, hi), lo, hi),
                  key=lambda g: g[0] - g[1])[:top]
    return {
        "metrics": metrics(t, program),
        "phases": phases(program, lo, hi),
        "idle_covered_share": covered_share(t, program),
        "idle_gaps": [[label(g, t.spans, program) or trace._label(g, t.spans, t.host),
                       (g[1] - g[0]) / 1e9] for g in idle],
    }


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python3 bench/program_spans.py <file.xplane.pb>", file=sys.stderr)
        return 2
    print(json.dumps(summarize(trace.load(argv[0]), load(argv[0]))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
