"""sent_per_s (sentences/s): sentences answered inside the window, over
the window's seconds. Closed-loop bursts: the rate that sets the cost of a
million corrected sentences."""


def read(win):
    done = sum(1 for r in win.finished() if r.done <= win.t1)
    return done / win.seconds
