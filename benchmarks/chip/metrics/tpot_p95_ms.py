"""tpot_p95_ms (ms): 95th percentile, over every answered request of two
tokens or more, of (last token - first token) / (tokens - 1), from the
client's stream timestamps."""
from harness.record import p95


def read(win):
    v = p95([(r.done - r.first) / (r.n_out - 1) for r in win.finished()
             if r.n_out >= 2])
    return None if v is None else 1e3 * v
