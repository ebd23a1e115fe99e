"""enc_host_share (%): share of the worker's time over the traced span in
which the encoder's batch worker did host work around the forward:
padding the batch (``batch.pack``), dispatching it (``batch.dispatch``)
and resolving its futures (``batch.fanout``), from the program's phase
clock."""
from harness.phases import share


def read(win):
    return share(win, "batch", "pack", "dispatch", "fanout")
