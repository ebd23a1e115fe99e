"""The five readers of the program's phase clock: each is the share of a
serving worker's time over the traced span that its named phases took,
from the counters diffed over the span, and reads nothing without a trace
or where the program keeps no such counter."""
import pytest

from smoke import BENCH  # noqa: F401  (puts the harness on sys.path)

from harness import spec  # noqa: E402
from harness.record import Window  # noqa: E402

COUNTERS = {                     # a traced span's counter movements, in ns
    "batch_wait_ns": 100_000_000, "batch_collect_ns": 20_000_000,
    "batch_pack_ns": 30_000_000, "batch_dispatch_ns": 40_000_000,
    "batch_device_ns": 250_000_000, "batch_copy_ns": 900_000_000,
    "batch_fanout_ns": 50_000_000, "batch_other_ns": 10_000_000,
    "batch_served": 90,
    "cont_wait_ns": 0, "cont_admit_ns": 60_000_000,
    "cont_dispatch_ns": 300_000_000, "cont_device_ns": 1_300_000_000,
    "cont_copy_ns": 80_000_000, "cont_publish_ns": 140_000_000,
    "cont_other_ns": 20_000_000,
}
BATCH_NS = 1_400_000_000         # the batch worker's counters, summed
CONT_NS = 1_900_000_000          # the scheduler's counters, summed
READERS = [
    ("enc_copy_share", 100 * 900e6 / BATCH_NS),
    ("enc_host_share", 100 * (30e6 + 40e6 + 50e6) / BATCH_NS),
    ("enc_wait_share", 100 * (100e6 + 20e6) / BATCH_NS),
    ("dec_copy_share", 100 * 80e6 / CONT_NS),
    ("dec_host_share", 100 * (60e6 + 300e6 + 140e6) / CONT_NS),
]


def _window(trace=object(), counters=COUNTERS):
    return Window(conf={}, traffic={}, reference=None, peaks={}, t0=0.0,
                  t1=10.0, requests=[], engine={}, trace=trace,
                  trace_t0=7.5, trace_t1=9.5,
                  trace_engine=counters)


def _reader(name):
    return spec.load_module(BENCH / "metrics" / f"{name}.py")


@pytest.mark.parametrize("name,share", READERS)
def test_reader_gives_its_phases_share_of_the_traced_span(name, share):
    assert sum(v for k, v in COUNTERS.items()
               if k.startswith("batch_") and k.endswith("_ns")) == BATCH_NS
    assert sum(v for k, v in COUNTERS.items()
               if k.startswith("cont_") and k.endswith("_ns")) == CONT_NS
    assert _reader(name).read(_window()) == pytest.approx(share, rel=1e-12)


@pytest.mark.parametrize("name", [n for n, _ in READERS])
def test_reader_reads_nothing_without_a_trace_or_its_counters(name):
    read = _reader(name).read
    assert read(_window(trace=None)) is None
    assert read(_window(counters=None)) is None
    # a program without the phase clock: only the older counters moved
    assert read(_window(counters={"decode_segments": 12,
                                  "jit_compiles": 0})) is None
