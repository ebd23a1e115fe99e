"""enc_batch_mean (sentences): mean size of the batches the engine's
encoder worker served over the window and its drain, from the engine's
own counter (``window()['batch_size_mean']``)."""


def read(win):
    return win.engine.get("batch_size_mean") or None
