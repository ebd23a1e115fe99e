"""The encoder worker's two-deep pipeline (``ServingEngine._run_encoder``).

Batch k+1 is collected, packed and dispatched before batch k is finished,
so k's copy to the host runs beside k+1's dispatch and forward. The
answers are those of the serial ``_serve_batch``, bit for bit; futures
resolve in launch order; a failure stays inside its own batch; ``close()``
strands nothing; and the phase counters still tile the worker's time.
"""
import ctypes
import resource
import threading
import time
from concurrent.futures import Future

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models import init_params
from repro.serving import EngineConfig, ServingEngine, hostmem
from repro.serving.engine import BATCH_PHASES, _Request

CFG = get_config("gector-base", smoke=True)
PARAMS = init_params(CFG, jax.random.PRNGKey(0))
B = 4                       # max_batch: every test sends whole batches
BUCKETS = (16, 32)
# lengths of the requests, in submission order: four batches of four,
# the second and fourth padded to 32, the others to 16
LENGTHS = [12, 3, 16, 9, 20, 5, 11, 14, 7, 16, 1, 10, 32, 8, 17, 2]


def _head(params, hidden, mask):
    """Two leaves, as the benchmark's tag and detect heads give."""
    hid = hidden.astype(jnp.float32)
    return hid, jnp.where(mask, hid[..., 0], 0.0)


def _engine(window_ms=2000.0):
    """A warmed engine whose long batch window makes every batch exactly
    ``B`` consecutive requests."""
    eng = ServingEngine(CFG, PARAMS, EngineConfig(
        mode="encoder", max_batch=B, pad_buckets=BUCKETS,
        batch_window_ms=window_ms), head_fn=_head)
    eng.warmup(batch_sizes=[1, B])
    eng.discard_samples()
    return eng


def _tokens(seed=0, lengths=LENGTHS):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, CFG.vocab_size, (n,)).astype(np.int32)
            for n in lengths]


def _hold_first_launch(eng):
    """Make the worker's first launch wait for the returned event, so that
    every request the test submits meanwhile is queued behind it."""
    go = threading.Event()
    launch = eng._launch_batch
    calls = []

    def held(*a, **kw):
        calls.append(1)
        if len(calls) == 1:
            go.wait(60)
        return launch(*a, **kw)

    eng._launch_batch = held
    return go


def _submit_all(eng, toks):
    order, lock = [], threading.Lock()

    def note(i):
        def cb(_f):
            with lock:
                order.append(i)
        return cb

    futs = []
    for i, t in enumerate(toks):
        f = eng.submit(t)
        f.add_done_callback(note(i))
        futs.append(f)
    return futs, order


def _serial(eng, toks):
    """The serial path's answers: ``_serve_batch`` on the test's thread,
    one batch of ``B`` consecutive requests at a time."""
    outs = []
    for j in range(0, len(toks), B):
        reqs = [_Request(t, Future(), time.perf_counter())
                for t in toks[j:j + B]]
        eng._serve_batch(reqs)
        outs += [r.future.result(timeout=0) for r in reqs]
    return outs


def test_answers_match_the_serial_path_bit_for_bit_in_submission_order():
    eng = _engine()
    try:
        toks = _tokens()
        go = _hold_first_launch(eng)
        futs, order = _submit_all(eng, toks)
        go.set()
        got = [f.result(timeout=120) for f in futs]
        w = eng.window()
        want = _serial(eng, toks)
    finally:
        eng.close()
    assert order == list(range(len(toks)))
    widths = {g[0].shape[0] for g in got}
    assert widths == set(BUCKETS)             # both buckets were served
    for g, r in zip(got, want):
        for a, b in zip(g, r):
            assert a.dtype == b.dtype and a.shape == b.shape
            assert np.array_equal(a, b)
    # every batch after the first was launched with one in flight
    assert w["batch_served"] == len(toks) // B
    assert w["batch_overlapped"] == w["batch_served"] - 1
    assert type(w["batch_overlapped"]) is int


def test_at_most_one_batch_is_in_flight():
    eng = _engine()
    inflight, peak = [0], [0]
    launch, finish = eng._launch_batch, eng._finish_batch

    def counted_launch(*a, **kw):
        peak[0] = max(peak[0], inflight[0])
        b = launch(*a, **kw)
        inflight[0] += b is not None
        return b

    def counted_finish(*a, **kw):
        inflight[0] -= 1
        return finish(*a, **kw)

    eng._launch_batch, eng._finish_batch = counted_launch, counted_finish
    go = _hold_first_launch(eng)
    try:
        for seed in range(3):
            futs, _ = _submit_all(eng, _tokens(seed))
            go.set()
            for f in futs:
                f.result(timeout=120)
        m = eng.metrics()
    finally:
        eng.close()
    assert peak[0] <= 1                     # a launch finds at most one
    assert inflight[0] == 0
    assert 0 < m["batch_overlapped"] <= m["batch_served"]
    assert m["batch_served"] == 3 * len(LENGTHS) // B


def test_a_lone_request_resolves_without_company():
    """With nothing queued behind it, the batch in flight is finished, not
    kept waiting for a next batch that may never come."""
    eng = _engine(window_ms=2.0)
    try:
        tok, = _tokens(lengths=[9])
        out = eng.submit(tok).result(timeout=30)
        w = eng.window()
    finally:
        eng.close()
    assert out[0].shape == (16, CFG.d_model)
    assert w["batch_served"] == 1 and w["batch_overlapped"] == 0


def test_a_batch_in_flight_takes_the_place_of_the_collect_window():
    """With a batch in flight, the next takes what is queued and goes: a
    request queued behind a full batch waits for no collect window."""
    window_s = 5.0
    eng = _engine(window_ms=window_s * 1e3)
    try:
        toks = _tokens(lengths=LENGTHS[:B + 1])
        go = _hold_first_launch(eng)
        futs, _ = _submit_all(eng, toks)
        t0 = time.perf_counter()
        go.set()
        for f in futs:
            f.result(timeout=120)
        elapsed = time.perf_counter() - t0
        w = eng.window()
    finally:
        eng.close()
    assert elapsed < window_s / 2, elapsed
    assert w["batch_served"] == 2 and w["batch_overlapped"] == 1
    assert w["batch_collect_ns"] < window_s / 2 * 1e9


def test_the_worker_keeps_its_largest_output_buffer_mapped(monkeypatch):
    kept = []
    monkeypatch.setattr(hostmem, "keep", lambda n: kept.append(n) or True)
    eng = _engine()
    try:
        toks = _tokens()
        got = [f.result(timeout=120) for f in _submit_all(eng, toks)[0]]
    finally:
        eng.close()
    # warm-up's batches and the served ones each report their largest leaf
    assert max(kept) == B * BUCKETS[-1] * CFG.d_model * 4
    assert got[0][0].nbytes * B in kept


class _MallInfo2(ctypes.Structure):
    _fields_ = [(n, ctypes.c_size_t) for n in (
        "arena", "ordblks", "smblks", "hblks", "hblkhd", "usmblks",
        "fsmblks", "uordblks", "fordblks", "keepcost")]


def test_kept_host_buffers_come_from_the_heap_and_stay_mapped():
    """Above glibc's 32 MiB dynamic limit a buffer is mapped afresh and
    faulted in on every use. Once kept, the buffers of a worker thread
    that makes batch k+1's before it frees batch k's, as the pipeline
    does, are reused from its arena's heaps and fault in (almost) no
    page."""
    libc = ctypes.CDLL(None)
    libc.mallinfo2.argtypes = []
    libc.mallinfo2.restype = _MallInfo2
    n = 41 << 20                 # one encoder batch's tag logits
    assert hostmem.keep(n)
    got = {}

    def pipeline():
        prev = None
        for k in range(24):
            if k == 8:           # settled: count from here
                f0 = resource.getrusage(resource.RUSAGE_THREAD).ru_minflt
            cur = np.empty(n, np.uint8)
            cur.fill(1)
            got["mapped"] = libc.mallinfo2().hblkhd
            prev = cur           # noqa: F841 - batch k freed here
        got["faults"] = (resource.getrusage(resource.RUSAGE_THREAD).ru_minflt
                         - f0) / 16

    t = threading.Thread(target=pipeline)
    t.start()
    t.join(timeout=120)
    assert not t.is_alive()
    assert got["mapped"] < n     # not an mmap chunk of its own
    # a fresh mapping of n bytes takes at least n / 2 MiB faults, huge
    # pages and all
    assert got["faults"] < 8, got["faults"]


def test_keep_leaves_small_buffers_to_glibc_and_refuses_what_mallopt_cannot_take():
    before = hostmem._kept
    assert hostmem.keep(1 << 20) and hostmem._kept == before
    assert not hostmem.keep(1 << 30)         # four times is past an int
    assert hostmem._kept == before


def test_a_failed_launch_fails_only_its_own_batch():
    eng = _engine()
    toks = _tokens()
    want = _serial(eng, toks)
    eng.discard_samples()
    fn, calls = eng._encoder_fn, []

    def failing_fn(bucket):
        calls.append(bucket)
        if len(calls) == 2:             # the second batch's dispatch
            def boom(*a):
                raise RuntimeError("dispatch failed")
            return boom
        return fn(bucket)

    eng._encoder_fn = failing_fn
    try:
        go = _hold_first_launch(eng)
        futs, order = _submit_all(eng, toks)
        go.set()
        for f in futs:
            f.exception(timeout=120)
        w = eng.window()
    finally:
        eng.close()
    bad = set(range(B, 2 * B))
    for i, f in enumerate(futs):
        if i in bad:
            assert "dispatch failed" in str(f.exception())
        else:
            assert f.exception() is None
            assert all(np.array_equal(a, b)
                       for a, b in zip(f.result(), want[i]))
    # the answered futures resolved in submission order, the batch in
    # flight when the second failed among them
    assert [i for i in order if i not in bad] == [
        i for i in range(len(toks)) if i not in bad]
    assert w["batch_served"] == len(toks) // B - 1


def test_close_with_a_batch_in_flight_strands_no_future():
    eng = _engine()
    at_second = threading.Event()
    launch, calls = eng._launch_batch, []

    def held(*a, **kw):
        b = launch(*a, **kw)
        calls.append(1)
        if len(calls) == 2:             # batch 2 launched, batch 1 pending
            at_second.set()
            eng._stop.wait(60)
        return b

    eng._launch_batch = held
    toks = _tokens()
    go = _hold_first_launch(eng)      # wraps held: queue everything first
    futs, _ = _submit_all(eng, toks)
    go.set()
    try:
        assert at_second.wait(60)
    finally:
        eng.close()
    assert all(f.done() for f in futs)
    for f in futs[:2 * B]:              # the finished and the in-flight
        assert f.exception() is None
    for f in futs[2 * B:]:              # still queued at close
        assert isinstance(f.exception(), RuntimeError)


def test_phase_counters_tile_the_worker_wall_time_under_the_pipeline():
    eng = _engine()
    keys = [f"batch_{p}_ns" for p in (*BATCH_PHASES, "other")]
    try:
        go = _hold_first_launch(eng)
        t0 = time.perf_counter_ns()
        eng.window()
        futs, _ = _submit_all(eng, _tokens())
        go.set()
        for f in futs:
            f.result(timeout=120)
        time.sleep(0.1)
        w = eng.window()
        t1 = time.perf_counter_ns()
    finally:
        eng.close()
    assert w["batch_overlapped"] > 0
    for k in keys:
        assert type(w[k]) is int, k
    for p in ("pack", "dispatch", "device", "copy", "fanout"):
        assert w[f"batch_{p}_ns"] > 0, p
    share = sum(w[k] for k in keys) / (t1 - t0)
    assert 0.97 <= share <= 1.0, share


def test_the_batch_at_a_time_decoder_keeps_its_serial_loop():
    """The pipeline is the encoder's: the batch-at-a-time decoder serves
    one batch at a time and never counts an overlapped batch."""
    cfg = get_config("qwen2-0.5b", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, EngineConfig(
        mode="decoder", continuous=False, max_batch=2,
        max_new_tokens=3, pad_buckets=(16,)))
    try:
        rng = np.random.RandomState(1)
        futs = [eng.submit(rng.randint(0, cfg.vocab_size, (8,)))
                for _ in range(6)]
        assert all(f.result(timeout=180).shape == (3,) for f in futs)
        m = eng.metrics()
    finally:
        eng.close()
    assert m["batch_served"] >= 3 and m["batch_overlapped"] == 0
