"""Host buffers that stay mapped from one encoder batch to the next.

glibc's malloc serves a request above its mmap threshold with a fresh
mapping and unmaps it on free. The threshold follows what the process
frees, but only up to 32 MiB (``DEFAULT_MMAP_THRESHOLD_MAX`` on 64-bit
Linux). So a larger buffer made once a batch, such as the host copy of an
encoder batch's outputs, is mapped afresh, faulted in page by page while
the copy writes it, and unmapped again, every batch. ``keep(nbytes)``
raises the mmap and trim thresholds above ``nbytes``: such buffers then
come from malloc's heap, and their pages stay mapped for the next batch.
A thread other than the main one allocates from an arena of heaps of at
most 64 MiB, and glibc unmaps such a heap once it is empty unless the
arena's top pad covers it, so the top pad is raised to 64 MiB as well.
Off glibc it does nothing.
"""
from __future__ import annotations

import ctypes
import ctypes.util
import threading

_M_TRIM_THRESHOLD = -1
_M_TOP_PAD = -2
_M_MMAP_THRESHOLD = -3
_DYNAMIC_MAX = 32 << 20      # glibc keeps buffers up to this size itself
_HEAP_MAX = 64 << 20         # an arena heap's largest size (HEAP_MAX_SIZE)
_INT_MAX = 2**31 - 1         # mallopt takes an int
# the mmap threshold sits at twice the largest buffer kept; the trim
# threshold at four times, so that freeing the buffers of the batch in
# flight, the batch being finished and one the client still holds does
# not hand their pages back
_MMAP_TIMES, _TRIM_TIMES = 2, 4


def _load_mallopt():
    try:
        fn = ctypes.CDLL(ctypes.util.find_library("c") or None).mallopt
    except (OSError, AttributeError):
        return None
    fn.argtypes = [ctypes.c_int, ctypes.c_int]
    fn.restype = ctypes.c_int
    return fn


_mallopt = _load_mallopt()
# malloc's thresholds are state of the whole process, so what has been
# set is too: the largest buffer size kept so far, raised and never lowered
_kept = 0
_lock = threading.Lock()


def keep(nbytes: int) -> bool:
    """Keep host buffers of up to ``nbytes`` mapped once freed, so the next
    one of that size faults in no page. True when they are kept (glibc's
    own threshold already covers up to 32 MiB); False off glibc or where
    ``nbytes`` is too large to be set."""
    global _kept
    if nbytes <= max(_kept, _DYNAMIC_MAX):
        return True
    with _lock:
        if nbytes <= _kept:
            return True
        trim = _TRIM_TIMES * nbytes
        if _mallopt is None or trim > _INT_MAX:
            return False
        if not (_mallopt(_M_MMAP_THRESHOLD, _MMAP_TIMES * nbytes)
                and _mallopt(_M_TRIM_THRESHOLD, trim)
                and _mallopt(_M_TOP_PAD, _HEAP_MAX)):
            return False
        _kept = nbytes
        return True
