"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A trace is read into lists of ``(name, start_ns, end_ns)`` on one clock:
the operations of each device (the ``XLA Ops`` line of every
``/device:TPU:<n>`` plane, each named after the program that ran it, from
the ``XLA Modules`` line), the benchmark's own host spans (``bench/...``
annotations) and the host's Python calls (the profiler's Python tracer,
``$file:line function``). The window is the ``bench/window`` span.

Busy time is the union of a device's operation intervals inside the window,
averaged over the devices that ran anything. Operations nest (a ``while``
holds its body's operations), so an operation's own time is its duration
less that of the operations inside it; the top operations are ranked by
their own time. Each idle gap is labelled with the benchmark span around it
and the innermost Python call that spans the whole gap, with the call inside
it that covers most of the gap.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses

DEVICE_PLANE_PREFIX = "/device:TPU:"
DEVICE_OPS_LINE = "XLA Ops"
DEVICE_MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench/"
WINDOW_SPAN = "bench/window"
PYTHON_PREFIX = "$"


@dataclasses.dataclass
class Trace:
    device_ops: dict            # plane name -> [(name, start_ns, end_ns)]
    spans: list                 # [(name, start_ns, end_ns)] of bench/ spans
    host: list                  # [(name, start_ns, end_ns)] Python calls

    def window(self) -> tuple:
        found = [(s, e) for n, s, e in self.spans if n == WINDOW_SPAN]
        if len(found) != 1:
            raise ValueError(f"trace holds {len(found)} {WINDOW_SPAN} spans")
        return found[0]


def op_name(hlo: str) -> str:
    """``%fusion.3 = f32[8]{0} fusion(...)`` -> ``%fusion.3 fusion f32[8]``
    (a tuple-shaped result keeps only its opcode)."""
    lhs, _, rhs = hlo.partition(" = ")
    depth = 0
    for i, ch in enumerate(rhs):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            opcode = rhs[i + 1:].split("(", 1)[0]
            shape = "" if rhs.startswith("(") else " " + rhs[:i].split("{", 1)[0]
            return f"{lhs} {opcode}{shape}"
    return hlo


def _named(ops, modules) -> list:
    """Each op named ``<program>/<op>`` after the module running around it."""
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    out = []
    for name, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        prog = modules[i][0].split("(", 1)[0] if i >= 0 and modules[i][2] >= e else "?"
        out.append((f"{prog}/{op_name(name)}", s, e))
    return out


def load(path) -> Trace:
    """Read an ``.xplane.pb`` file written by ``jax.profiler``."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    device_ops, spans, host = {}, [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            lines = {line.name: [(e.name, e.start_ns, e.end_ns) for e in line.events]
                     for line in plane.lines
                     if line.name in (DEVICE_OPS_LINE, DEVICE_MODULES_LINE)}
            device_ops[plane.name] = _named(lines.get(DEVICE_OPS_LINE, []),
                                            lines.get(DEVICE_MODULES_LINE, []))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns, e.end_ns))
                    elif e.name.startswith(PYTHON_PREFIX):
                        host.append((e.name[1:], e.start_ns, e.end_ns))
    return Trace(device_ops, spans, host)


def _clip(ops, lo: float, hi: float) -> list:
    """``ops`` cut to ``[lo, hi]``; those outside it dropped."""
    return [(n, max(s, lo), min(e, hi)) for n, s, e in ops if e > lo and s < hi]


def union(intervals) -> list:
    """Merge overlapping ``(start, end)`` intervals, sorted."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


def busy_ns(ops, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union((s, e) for _, s, e in _clip(ops, lo, hi)))


def gaps(ops, lo: float, hi: float) -> list:
    """Idle ``(start, end)`` intervals of one device inside ``[lo, hi]``."""
    out, t = [], lo
    for s, e in union((s, e) for _, s, e in _clip(ops, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def self_ns(ops, lo: float, hi: float) -> collections.Counter:
    """Own time of each op name inside ``[lo, hi]``: its clipped duration
    less that of the ops nested inside it."""
    own = collections.Counter()
    stack = []                      # [name, end, child time, duration]
    for name, s, e in sorted(_clip(ops, lo, hi), key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            done = stack.pop()
            own[done[0]] += done[3] - done[2]
        if stack:
            stack[-1][2] += e - s
        stack.append([name, e, 0.0, e - s])
    for name, _, child, dur in stack:
        own[name] += dur - child
    return own


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float               # averaged over the devices that ran anything
    device_ops: list            # [[name, seconds of own time]], most first
    idle_gaps: list             # [[label, seconds]], longest first


def _label(gap, spans, host) -> str:
    g0, g1 = gap
    mid = (g0 + g1) / 2
    around = [(e - s, n) for n, s, e in spans if s <= mid <= e and n != WINDOW_SPAN]
    label = min(around)[1][len(SPAN_PREFIX):] if around else "window"
    enclosing = min(((e - s, n, s, e) for n, s, e in host if s <= g0 and e >= g1),
                    default=None)
    if enclosing is None:
        return label
    _, name, es, ee = enclosing
    part = max(((min(e, g1) - max(s, g0), n) for n, s, e in host
                if es <= s and e <= ee and (s, e) != (es, ee)
                and min(e, g1) > max(s, g0)), default=None)
    return f"{label}: {name}" + (f" > {part[1]}" if part else "")


def summarize(trace: Trace, top: int = 10) -> Summary:
    lo, hi = trace.window()
    ran = {p: ops for p, ops in trace.device_ops.items() if busy_ns(ops, lo, hi) > 0}
    if not ran:
        raise ValueError("no device operation ran inside the window")
    busy = sum(busy_ns(ops, lo, hi) for ops in ran.values()) / len(ran)
    own = collections.Counter()
    for ops in ran.values():
        for name, t in self_ns(ops, lo, hi).items():
            own[name] += t / 1e9 / len(ran)
    first = sorted(ran)[0]
    idle = sorted(gaps(ran[first], lo, hi), key=lambda g: g[0] - g[1])[:top]
    return Summary(
        window_s=(hi - lo) / 1e9,
        busy_s=busy / 1e9,
        device_ops=[[n, t] for n, t in own.most_common(top)],
        idle_gaps=[[_label(g, trace.spans, trace.host), (g[1] - g[0]) / 1e9]
                   for g in idle],
    )
