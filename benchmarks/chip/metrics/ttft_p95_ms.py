"""ttft_p95_ms (ms): 95th percentile, over every request sent in the window
that was answered, of the time from when it was due to its first streamed
token, as the client's thread saw it."""
from harness.record import p95


def read(win):
    v = p95([r.first - r.due for r in win.finished()])
    return None if v is None else 1e3 * v
