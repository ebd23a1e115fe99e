"""Synthetic grammatical-error-correction sentences with NUCLE-like
statistics: the paper's corpus (1,312 sentences, about 23 tokens each, low
error frequency), which is licensed and so generated here.

Copied from the program's ``core/corpus.py`` (``GECCorpus``) so that the
traffic cannot change with the program; only the source side of each
sentence is kept, since a served request is just its tokens. Clean
sentences come from a phrase bank mixed with Zipf-distributed free words;
corruptions drop, substitute or insert editable words inside phrases.
"""
from __future__ import annotations

from typing import List

import numpy as np


def gec_sentences(n: int, *, vocab_size: int, edit_words: int,
                  mean_len: float, error_rate: float, seed: int
                  ) -> List[np.ndarray]:
    """``n`` source sentences (int32 token ids in [0, vocab_size))."""
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
    cdf = np.cumsum(1.0 / ranks)
    cdf /= cdf[-1]
    phrases = rng.integers(2, 2 + edit_words, (256, 6))
    out = []
    for _ in range(n):
        length = max(5, int(rng.normal(mean_len, 6)))
        toks, corr, total = [], [], 0
        while total < length:
            if rng.random() < 0.8:
                ph = phrases[rng.integers(len(phrases))]
                c = np.zeros(len(ph), bool)
                c[2:] = True
            else:
                k = int(rng.integers(2, 6))
                ph = np.minimum(np.searchsorted(cdf, rng.random(k)),
                                vocab_size - 1)
                c = np.zeros(k, bool)
            toks.append(ph)
            corr.append(c)
            total += len(ph)
        clean = np.concatenate(toks)[:length]
        corruptible = np.concatenate(corr)[:length]
        src, i, last_keep = [], 0, False
        while i < len(clean):
            tok = int(clean[i])
            r = rng.random()
            editable = corruptible[i] and 2 <= tok < 2 + edit_words
            if r < error_rate / 3 and editable and src and last_keep:
                i += 1                   # a dropped word
                last_keep = False
                continue
            if r < 2 * error_rate / 3 and editable:
                src.append(int(rng.integers(2, 2 + edit_words)))
                i += 1                   # a substituted word
                last_keep = False
                continue
            if r < error_rate and editable:
                src.append(int(rng.integers(2, 2 + edit_words)))
                last_keep = False        # a spurious word
                continue
            src.append(tok)
            last_keep = True
            i += 1
        out.append(np.asarray(src, np.int32))
    return out
