"""dec_queue_p95_ms (ms): 95th percentile of the time requests waited in
the continuous scheduler before their prefill started, from the engine's
own span (``GenerationResult.timing.queue_s``), over the window's answered
requests."""
from harness.record import p95


def read(win):
    v = p95([r.queue_s for r in win.finished()])
    return None if v is None else 1e3 * v
