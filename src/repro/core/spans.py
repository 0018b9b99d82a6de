"""Host spans of the SpGEMM program, written into the JAX profiler's trace.

``span(phase, **args)`` opens ``jax.profiler.TraceAnnotation("spgemm/<phase>",
**args)``: an event on the calling thread's host line, on the same clock as
the device planes, written out when the trace stops. Spans nest by time, so
a span's parent is the span around it. The body adds byte counts through the
``Counts`` it is handed; they become the event's stats when the span closes.
The profiler is the only sink: while none records, a span is one inactive
annotation and no count is computed.
"""

from __future__ import annotations

import contextlib

import jax

PREFIX = "spgemm/"


def _crosses(x) -> bool:
    """Whether reading ``x`` on the host copies it from the device. A
    ``jax.Array`` keeps the host copy that its first read made and serves
    later reads from it; on the CPU a read is a view and nothing is kept, so
    every read counts there."""
    return isinstance(x, jax.Array) and getattr(x, "_npy_value", None) is None


class Counts:
    """What an open span adds to its event when it closes."""

    def __init__(self, on: bool):
        self.on = on
        self.stats = {}

    def add(self, **counts) -> None:
        """Add each count, a number or a callable returning one; a callable
        runs only while the profiler records."""
        if self.on:
            for k, v in counts.items():
                self.stats[k] = self.stats.get(k, 0) + (v() if callable(v) else v)

    def note(self, **args) -> None:
        """Arguments known only inside the span, such as a resolved backend."""
        if self.on:
            self.stats.update(args)

    def get(self, tree):
        """``tree`` read to the host in one transfer; counts as ``d2h_bytes``
        the device arrays whose host copy is not already held."""
        self.add(d2h_bytes=lambda: sum(
            x.nbytes for x in jax.tree.leaves(tree) if _crosses(x)))
        return jax.device_get(tree)

    def put(self, tree):
        """``tree``'s host arrays put on the default device in one transfer;
        counts their bytes as ``h2d_bytes``."""
        self.add(h2d_bytes=lambda: sum(
            x.nbytes for x in jax.tree.leaves(tree) if not isinstance(x, jax.Array)))
        return jax.device_put(tree)


@contextlib.contextmanager
def span(phase: str, **args):
    """``spgemm/<phase>`` around the body, which receives its ``Counts``.
    Also a decorator: ``@span("symbolic")``."""
    with jax.profiler.TraceAnnotation(PREFIX + phase, **args) as event:
        counts = Counts(jax.profiler.TraceAnnotation.is_enabled())
        yield counts
        if counts.stats:
            event.set_metadata(**counts.stats)
