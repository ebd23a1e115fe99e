"""enc_wait_share (%): share of the worker's time over the traced span in
which the encoder's batch worker waited for sentences: on an empty queue
(``batch.wait``) or filling a batch within its window
(``batch.collect``), from the program's phase clock."""
from harness.phases import share


def read(win):
    return share(win, "batch", "wait", "collect")
