"""enc_copy_share (%): share of the worker's time over the traced span in
which the encoder's batch worker copied a batch's outputs to the host
(``batch.copy``, the ``jax.device_get`` of the tag logits), from the
program's phase clock."""
from harness.phases import share


def read(win):
    return share(win, "batch", "copy")
