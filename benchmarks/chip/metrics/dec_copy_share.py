"""dec_copy_share (%): share of the worker's time over the traced span in
which the continuous scheduler copied its programs' outputs to the host
(``cont.copy``), from the program's phase clock."""
from harness.phases import share


def read(win):
    return share(win, "cont", "copy")
