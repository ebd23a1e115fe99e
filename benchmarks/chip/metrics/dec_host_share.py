"""dec_host_share (%): share of the worker's time over the traced span in
which the continuous scheduler did host work between its programs:
admission (``cont.admit``), dispatch (``cont.dispatch``) and publishing
tokens and results (``cont.publish``), from the program's phase clock."""
from harness.phases import share


def read(win):
    return share(win, "cont", "admit", "dispatch", "publish")
