"""enc_overlap_share (%): share of the encoder batches served over the
traced span that the batch worker launched while another batch was in
flight (``batch_overlapped`` over ``batch_served``, diffed over the span),
so that their dispatch and forward ran beside the last batch's copy to
the host; from the program's counters. It confirms the pipelined path
ran, not that a copy ran beside device work: in burst512 it reads 15/16
(the first batch of each 512-sentence burst has none before it) however
much the overlap saves. None without a trace, or where the program keeps
no such counter."""


def read(win):
    eng = win.trace_engine
    if win.trace is None or not eng:
        return None
    served, overlapped = eng.get("batch_served"), eng.get("batch_overlapped")
    if not served or overlapped is None:
        return None
    return 100.0 * overlapped / served
