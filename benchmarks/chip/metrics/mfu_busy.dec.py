"""mfu_busy.dec (%): model FLOPs of the work done in the traced span
(prefills that started in it, at their real prompt lengths; decoded
tokens that reached the client in it, each attending its real context)
over the chip's busy seconds in the span times its peak bf16 FLOP/s: the
whole decoder step's share of the chip while it computes."""
from harness.work import decode_work


def read(win):
    w = decode_work(win)
    if w is None or not win.trace.busy_s:
        return None
    return 100.0 * w.flops / (win.trace.busy_s * win.peaks["bf16_flops_per_s"])
