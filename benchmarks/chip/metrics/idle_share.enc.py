"""idle_share.enc (%): share of the traced span in which no operation ran
on the chip (1 - busy union / span), in an encoder cell."""


def read(win):
    return None if win.trace is None else 100.0 * win.trace.idle_share
