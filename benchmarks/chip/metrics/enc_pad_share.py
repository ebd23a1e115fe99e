"""enc_pad_share (%): of the positions the encoder computed for sentences
answered in the window, the share that was padding: 1 - sum of sentence
lengths / sum of returned logit rows (each answer has its batch's bucket
width). Layer: the engine's batcher, which pads a batch to the bucket of
its longest sentence."""


def read(win):
    done = [r for r in win.finished() if r.done <= win.t1]
    rows = sum(r.n_out for r in done)
    if not rows:
        return None
    return 100.0 * (1.0 - sum(r.length for r in done) / rows)
