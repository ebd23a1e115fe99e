"""mfu.enc (%): model FLOPs of the sentences answered inside the traced
span, each counted at its real length (no padding) with both heads, over
the chip's busy seconds in the span times its peak bf16 FLOP/s: the whole
encoder step's share of the chip while it computes."""
from harness import flops


def read(win):
    if win.trace is None or not win.trace.busy_s:
        return None
    conf = win.conf
    n = sum(flops.encoder_flops(conf, r.length, conf["n_tags"] + 2)
            for r in win.finished() if win.trace_t0 <= r.done < win.trace_t1)
    if not n:
        return None
    return 100.0 * n / (win.trace.busy_s * win.peaks["bf16_flops_per_s"])
