"""Reduce a profiler trace (``.xplane.pb``) to the device's busy time, its
idle share, the device operations that took most time, and the longest
idle gaps named by what the host was doing in them.

Busy time is the union of the intervals in which an operation ran on a
device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), clipped to
the traced window and averaged over the chips. The window is the host span
named ``WINDOW_SPAN`` when the trace holds one, else the extent of the
device's operations. A gap is named after the host event that overlaps it
most, leaving out spans that enclose the whole window.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
from typing import List, Optional, Tuple

WINDOW_SPAN = "bench.window"
TOP = 10


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]
    n_device_ops: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xspace(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _union(intervals):
    """Merge (start, end) intervals; returns the sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce_xspace(path: str) -> Optional[TraceSummary]:
    """Summarise one trace file; None when it holds no device operation."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    per_chip, host, window = [], [], None
    op_time = collections.Counter()
    n_ops = 0
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and "NON_CORE" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            line = lines.get("XLA Ops") or lines.get("XLA Modules")
            if line is None:
                continue
            ops = []
            for ev in line.events:
                if ev.duration_ns <= 0:
                    continue
                ops.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                op_time[ev.name] += ev.duration_ns
            if ops:
                per_chip.append(ops)
                n_ops += len(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.duration_ns > 0:
                        host.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                     ev.name))
    if not per_chip:
        return None
    if window is None:
        window = (min(s for ops in per_chip for s, _ in ops),
                  max(e for ops in per_chip for _, e in ops))
    lo, hi = window
    busy, gaps = 0.0, []
    for ops in per_chip:
        merged = _union(_clip(ops, lo, hi))
        busy += sum(e - s for s, e in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    busy /= len(per_chip)
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [(_name_gap(g, host, window), (g[1] - g[0]) / 1e9)
             for g in gaps[:TOP]]
    top_ops = [(name, ns / 1e9) for name, ns in op_time.most_common(TOP)]
    return TraceSummary(window_s=(hi - lo) / 1e9, busy_s=busy / 1e9,
                        device_ops=top_ops, idle_gaps=named,
                        n_device_ops=n_ops)


def _name_gap(gap, host, window) -> str:
    """The host event overlapping ``gap`` the most, leaving out events that
    enclose the whole window; ``host:untraced`` when none does."""
    best, best_overlap = "host:untraced", 0.0
    for s, e, name in host:
        if s <= window[0] and e >= window[1]:
            continue
        overlap = min(e, gap[1]) - max(s, gap[0])
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best


class Tracer:
    """The profiler around part of a window: Python tracer off (it would
    slow the host it measures), host spans and device operations on.
    ``start`` and ``stop`` run on one thread; the window span between them
    is what ``reduce_xspace`` clips to."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self._span = None

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()

    def stop(self) -> None:
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()


def annotate(targets) -> None:
    """Wrap program methods in host spans for a traced run, so that idle
    gaps can be named by the layer the host was in. ``targets`` holds
    ``(class, method name, span name)``; a method the program no longer
    has is skipped."""
    import functools
    import jax
    for cls, meth, span in targets:
        fn = getattr(cls, meth, None)
        if fn is None or getattr(fn, "_bench_span", None):
            continue

        def wrap(fn=fn, span=span):
            @functools.wraps(fn)
            def inner(*a, **kw):
                with jax.profiler.TraceAnnotation(span):
                    return fn(*a, **kw)
            inner._bench_span = span
            return inner
        setattr(cls, meth, wrap())
