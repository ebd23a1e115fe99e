"""Pacing of the load and the traced part of a window."""
from __future__ import annotations

import shutil
import tempfile
import time
from typing import Optional

from harness import trace as tr

# The traced span: the last TRACE_SECONDS of the window, so that stopping
# the profiler, which blocks the thread that stops it, falls after the
# last send. Short, because a trace of every device operation grows fast.
TRACE_SECONDS = 2.0


def wait_until(t: float) -> None:
    """Sleep until host time ``t``; the last half millisecond yields
    instead of sleeping, since a sleep overshoots by about that much."""
    while True:
        dt = t - time.perf_counter()
        if dt <= 0:
            return
        time.sleep(dt - 0.0005 if dt > 0.001 else 0)


class TracedSpan:
    """Starts the profiler once the window reaches its last
    ``TRACE_SECONDS`` and reduces the trace when stopped. ``on_edge`` reads
    the engine's cumulative counters at the start and the stop."""

    def __init__(self, enabled: bool, t0: float, t1: float, on_edge):
        self.enabled = enabled
        self.t_start = max(t0, t1 - TRACE_SECONDS)
        self.on_edge = on_edge
        self.started = False
        self.t0 = self.t1 = 0.0
        self.edges = []
        self.summary: Optional[tr.TraceSummary] = None
        self._dir = None
        self._tracer = None

    def poll(self, now: float) -> None:
        if self.enabled and not self.started and now >= self.t_start:
            self._dir = tempfile.mkdtemp(prefix="bench_trace_")
            self._tracer = tr.Tracer(self._dir)
            self._tracer.start()
            self.started = True
            self.edges.append(self.on_edge())
            self.t0 = time.perf_counter()

    def stop(self) -> None:
        if not self.started or self.t1:
            return
        self.t1 = time.perf_counter()
        self.edges.append(self.on_edge())
        self._tracer.stop()

    def counters(self) -> dict:
        """How far each of the engine's integer counters moved over the
        traced span."""
        a, b = self.edges
        return {k: b[k] - a[k] for k, v in b.items()
                if isinstance(v, int) and isinstance(a.get(k), int)}

    def reduce(self) -> None:
        """Read the trace: after the window has drained, since parsing
        holds the interpreter that the serving threads need."""
        if not self.started:
            return
        try:
            self.summary = tr.reduce_xspace(tr.find_xspace(self._dir))
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
