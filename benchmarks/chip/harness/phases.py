"""Shares of the traced span that a serving worker spent in named phases.

The program's phase clock (``repro.serving.clock.WorkerClock``) keeps one
integer-nanosecond counter of self time per phase at the top level of
``engine.metrics()``: ``batch_<phase>_ns`` for the encoder's batch worker,
``cont_<phase>_ns`` for the continuous scheduler. ``loop.TracedSpan``
diffs them over the traced span into ``win.trace_engine``. The counters
tile the worker's wall time, so their summed movement is exactly the
interval the diff covers, and it is the share's denominator.
"""


def share(win, prefix, *phases):
    """100 x the worker's time in ``phases`` over its time in all its
    phases (``<prefix>_<phase>_ns``), both over the traced span; None
    without a trace, or where the program keeps no such counter."""
    eng = win.trace_engine
    if win.trace is None or not eng:
        return None
    keys = [f"{prefix}_{p}_ns" for p in phases]
    total = sum(v for k, v in eng.items()
                if k.startswith(prefix + "_") and k.endswith("_ns"))
    if total <= 0 or any(k not in eng for k in keys):
        return None
    return 100.0 * sum(eng[k] for k in keys) / total
