"""dec_prefill_p95_ms (ms): 95th percentile of the prefill time of the
window's answered requests, from the engine's own span
(``GenerationResult.timing.prefill_s``: prefill call to first token on the
host)."""
from harness.record import p95


def read(win):
    v = p95([r.prefill_s for r in win.finished()])
    return None if v is None else 1e3 * v
