"""sent_p95_ms (ms): 95th percentile, over every answered sentence sent in
the window, of the time from when it was due to when its answer resolved
on the client."""
from harness.record import p95


def read(win):
    v = p95([r.done - r.due for r in win.finished()])
    return None if v is None else 1e3 * v
