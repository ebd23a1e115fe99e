"""Phase clock of a serving worker thread.

The worker is always in exactly one phase. Entering a phase opens a
``jax.profiler.TraceAnnotation`` of the phase's name, so the span lands on
the profiler's host plane beside the device trace, and charges the time
since the last switch to the phase it leaves. Phases nest as context
managers: on exit the outer phase resumes, so each counter holds self time
and the counters tile the worker's wall time. Time in no named phase goes
to ``<prefix>_other_ns``.

The counters are Python ``int`` nanoseconds under pre-created keys of the
engine's stats dict, so ``metrics()`` reads and ``window()`` diffs them
like the engine's other counters. ``read()`` adds the phase in progress,
so a stalled copy shows while it lasts.
"""
from __future__ import annotations

import time
from typing import Dict, Iterable

import jax


class WorkerClock:
    def __init__(self, stats: Dict[str, int], prefix: str,
                 phases: Iterable[str]):
        self._stats = stats                              # guarded-by: init
        self._keys = {p: f"{prefix}_{p}_ns" for p in (*phases, "other")}  # guarded-by: init
        self._names = {p: f"{prefix}.{p}" for p in phases}  # guarded-by: init
        for key in self._keys.values():
            stats[key] = 0
        # seqlock: the worker makes _seq odd while it moves time between
        # counters, and read() retries a snapshot that saw it odd or moving
        self._seq = 0                                    # guarded-by: threadsafe
        self._cur = self._keys["other"]                  # guarded-by: threadsafe
        self._t = time.perf_counter_ns()                 # guarded-by: threadsafe

    def phase(self, name: str) -> "_Phase":
        return _Phase(self, self._keys[name], self._names[name])

    def _switch(self, key: str) -> str:  # holds: worker
        """Charge the time since the last switch to the current phase and
        make ``key`` current; returns the phase left."""
        now = time.perf_counter_ns()
        self._seq += 1
        self._stats[self._cur] += now - self._t
        prev, self._cur, self._t = self._cur, key, now
        self._seq += 1
        return prev

    def read(self) -> Dict[str, int]:
        """A copy of the stats dict with the phase in progress charged up
        to now. Safe from any thread."""
        while True:
            seq = self._seq
            if not seq & 1:
                snap = dict(self._stats)
                cur, t = self._cur, self._t
                now = time.perf_counter_ns()
                if self._seq == seq:
                    snap[cur] += now - t
                    return snap
            time.sleep(0)        # let the worker finish its switch


class _Phase:
    __slots__ = ("_wc", "_key", "_span", "_prev")

    def __init__(self, clock: WorkerClock, key: str, name: str):
        self._wc = clock
        self._key = key
        self._span = jax.profiler.TraceAnnotation(name)

    def __enter__(self):  # holds: worker
        self._prev = self._wc._switch(self._key)
        self._span.__enter__()

    def __exit__(self, *exc):  # holds: worker
        self._span.__exit__(*exc)
        self._wc._switch(self._prev)
