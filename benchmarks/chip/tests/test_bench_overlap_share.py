"""The reader of the encoder worker's overlap counter: the share of the
batches served over the traced span that were launched while another was
in flight, and nothing without a trace or where the program keeps no
such counter."""
import pytest

from smoke import BENCH  # noqa: F401  (puts the harness on sys.path)

from harness import spec  # noqa: E402
from harness.record import Window  # noqa: E402

COUNTERS = {                     # a traced span's counter movements
    "batch_wait_ns": 100_000_000, "batch_copy_ns": 900_000_000,
    "batch_served": 80, "batch_overlapped": 75,
}


def _window(trace=object(), counters=COUNTERS):
    return Window(conf={}, traffic={}, reference=None, peaks={}, t0=0.0,
                  t1=10.0, requests=[], engine={}, trace=trace,
                  trace_t0=7.5, trace_t1=9.5, trace_engine=counters)


def _read(win):
    return spec.load_module(BENCH / "metrics" / "enc_overlap_share.py").read(
        win)


def test_overlap_share_is_overlapped_over_served_batches():
    assert _read(_window()) == pytest.approx(100 * 75 / 80, rel=1e-12)
    assert _read(_window(counters=dict(COUNTERS, batch_overlapped=0))) == 0


@pytest.mark.parametrize("counters", [
    None,                                            # no engine counters
    {"decode_segments": 12, "jit_compiles": 0},      # the decoder's worker
    {k: v for k, v in COUNTERS.items() if k != "batch_overlapped"},  # parent
    dict(COUNTERS, batch_served=0, batch_overlapped=0),  # nothing served
])
def test_overlap_share_reads_nothing_without_its_counters(counters):
    assert _read(_window(trace=None)) is None
    assert _read(_window(counters=counters)) is None
