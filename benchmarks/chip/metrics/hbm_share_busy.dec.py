"""hbm_share_busy.dec (%): bytes the decoder's algorithm reads in the
traced span (every weight once per decode step and per prefill call, each
decoded token's valid keys and values, each prefilled token's keys and
values, in the configuration's dtype) over the chip's busy seconds in the
span times its peak HBM bandwidth: the whole step's share of the memory
roofline."""
from harness.work import decode_work


def read(win):
    w = decode_work(win)
    if w is None or not win.trace.busy_s:
        return None
    return 100.0 * w.bytes / (win.trace.busy_s * win.peaks["hbm_bytes_per_s"])
