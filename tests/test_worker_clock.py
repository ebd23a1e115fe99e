"""The serving workers' phase clock (``serving.clock.WorkerClock``) and the
names of the engine's jitted programs.

Both workers — the encoder's batch worker and the continuous decode
scheduler — charge their wall time to named phases: integer-nanosecond
self-time counters at the top level of ``metrics()`` and ``window()``
that tile the worker's wall time, and host spans of the same names in a
profiler trace. Every jitted serving program carries a stable name, so a
device trace says which program ran.
"""
import dataclasses
import glob
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_params, make_caches
from repro.serving import EngineConfig, ServingEngine
from repro.serving.clock import WorkerClock
from repro.serving.continuous import ContinuousScheduler
from repro.serving.engine import BATCH_PHASES

ENC_CFG = get_config("gector-base", smoke=True)
ENC_PARAMS = init_params(ENC_CFG, jax.random.PRNGKey(0))
DEC_CFG = get_config("qwen2-0.5b", smoke=True)
DEC_PARAMS = init_params(DEC_CFG, jax.random.PRNGKey(0))
DRAFT_CFG = dataclasses.replace(DEC_CFG, name="qwen2-0.5b-smoke-draft",
                                n_layers=1, d_model=112, n_heads=7,
                                n_kv_heads=1, d_ff=256)
DRAFT_PARAMS = init_params(DRAFT_CFG, jax.random.PRNGKey(1))

PHASES = {"batch": BATCH_PHASES, "cont": ContinuousScheduler.PHASES}


def _keys(mode):
    return [f"{mode}_{p}_ns" for p in (*PHASES[mode], "other")]


def _engine(mode):
    """A warmed engine at smoke widths whose worker is ``mode``."""
    if mode == "batch":
        eng = ServingEngine(ENC_CFG, ENC_PARAMS, EngineConfig(
            mode="encoder", max_batch=4, pad_buckets=(16, 32)))
        eng.warmup(buckets=(16,))
    else:
        eng = ServingEngine(DEC_CFG, DEC_PARAMS, EngineConfig(
            mode="decoder", max_batch=4, max_new_tokens=4,
            pad_buckets=(16,), decode_segment=2))
        eng.warmup()
    return eng


def _serve(eng, mode, n=8):
    """Serve ``n`` prompts of one bucket, wait for all of them, then leave
    the worker idle a moment so its wait phase shows."""
    rng = np.random.RandomState(5)
    if mode == "batch":
        futs = [eng.submit(rng.randint(0, ENC_CFG.vocab_size, (12,)))
                for _ in range(n)]
    else:
        futs = [eng.generate(rng.randint(0, DEC_CFG.vocab_size, (10,))).future
                for _ in range(n)]
    for f in futs:
        f.result(timeout=300)
    time.sleep(0.2)


@pytest.mark.parametrize("mode", ["batch", "cont"])
def test_phase_counters_are_ints_in_metrics_and_window(mode):
    eng = _engine(mode)
    try:
        eng.window()
        n_batches = len(eng.batch_sizes)
        _serve(eng, mode)
        m, w = eng.metrics(), eng.window()
        other = "cont" if mode == "batch" else "batch"
        for d in (m, w):
            for k in _keys(mode):
                assert type(d[k]) is int, (k, d[k])
            assert not [k for k in d if k.startswith(other + "_")
                        and k.endswith("_ns")]
        for p in PHASES[mode]:
            assert w[f"{mode}_{p}_ns"] > 0, p
        if mode == "batch":
            assert w["batch_served"] == len(eng.batch_sizes) - n_batches > 0
            assert type(m["batch_served"]) is int
    finally:
        eng.close()


@pytest.mark.parametrize("mode", ["batch", "cont"])
def test_phase_counters_tile_the_worker_wall_time(mode):
    eng = _engine(mode)
    try:
        t0 = time.perf_counter_ns()
        eng.window()
        _serve(eng, mode)
        w = eng.window()
        t1 = time.perf_counter_ns()
        share = sum(w[k] for k in _keys(mode)) / (t1 - t0)
        assert 0.97 <= share <= 1.0, share
    finally:
        eng.close()


@pytest.mark.parametrize("mode,span", [("batch", "batch.copy"),
                                       ("cont", "cont.publish")])
def test_phase_spans_reach_the_profiler_host_plane(mode, span, tmp_path):
    from jax.profiler import ProfileData
    eng = _engine(mode)
    try:
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 2
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            _serve(eng, mode, n=4)
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.close()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {ev.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert span in names
    assert {f"{mode}.{p}" for p in PHASES[mode] if p != "wait"} <= names


def test_read_is_consistent_while_the_worker_switches():
    """Readers on other threads never see time counted twice or lost: a
    snapshot's counters always sum to the clock's age at the read."""
    stats = {}
    t_a = time.perf_counter_ns()
    clock = WorkerClock(stats, "x", ("a", "b"))
    t_b = time.perf_counter_ns()
    stop = threading.Event()
    bad = []

    def worker():
        while not stop.is_set():
            with clock.phase("a"):
                with clock.phase("b"):
                    pass

    def reader():
        last = 0
        for _ in range(2000):
            lo = time.perf_counter_ns() - t_b
            total = sum(clock.read().values())
            hi = time.perf_counter_ns() - t_a
            if not lo <= total <= hi or total < last:
                bad.append((lo, total, hi, last))
            last = total

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        w = threading.Thread(target=worker)
        w.start()
        readers = [threading.Thread(target=reader) for _ in range(12)]
        for t in readers:
            t.start()
        for t in readers:
            t.join(timeout=120)
        stop.set()
        w.join(timeout=30)
    finally:
        sys.setswitchinterval(interval)
    assert not w.is_alive() and not any(t.is_alive() for t in readers)
    assert not bad, bad[:3]


def test_batch_served_off_the_worker_leaves_the_worker_clock_alone():
    """A batch served on another thread (warm-up, or a caller that times
    the serve path directly) keeps ``_serve_batch(reqs)``'s signature and
    moves none of the worker's busy phases."""
    from concurrent.futures import Future
    from repro.serving.engine import _Request
    eng = _engine("batch")
    try:
        busy = [f"batch_{p}_ns" for p in BATCH_PHASES
                if p not in ("wait", "collect")]
        before = {k: eng._stats[k] for k in busy}
        rng = np.random.RandomState(2)
        reqs = [_Request(rng.randint(0, ENC_CFG.vocab_size, (12,)),
                         Future(), time.perf_counter()) for _ in range(3)]
        eng._serve_batch(reqs)
        assert all(r.future.result(timeout=0) is not None for r in reqs)
        assert {k: eng._stats[k] for k in busy} == before
    finally:
        eng.close()


def test_encoder_window_counts_compiles():
    eng = ServingEngine(ENC_CFG, ENC_PARAMS, EngineConfig(
        mode="encoder", max_batch=4, pad_buckets=(16, 32)))
    try:
        eng.warmup(batch_sizes=[1], buckets=(16,))
        eng.window()
        rng = np.random.RandomState(0)
        eng.submit(rng.randint(0, ENC_CFG.vocab_size, (10,))).result(
            timeout=300)
        assert eng.window()["jit_compiles"] == 0       # warmed shape
        eng.submit(rng.randint(0, ENC_CFG.vocab_size, (20,))).result(
            timeout=300)
        assert eng.window()["jit_compiles"] > 0        # bucket 32 is new
        assert eng.metrics()["jit_compiles"] > 0
    finally:
        eng.close()


# --------------------------------------------------------- program names
@pytest.fixture(scope="module")
def engines():
    enc = ServingEngine(ENC_CFG, ENC_PARAMS, EngineConfig(mode="encoder"))
    dec = ServingEngine(
        DEC_CFG, DEC_PARAMS,
        EngineConfig(mode="decoder", max_batch=2, max_new_tokens=4,
                     pad_buckets=(16,), decode_segment=2, prefill_chunk=8,
                     spec_decode=True, spec_k=2),
        draft=(DRAFT_CFG, DRAFT_PARAMS))
    yield enc, dec
    enc.close()
    dec.close()


def _lowered(name, enc, dec):
    """The program ``name`` lowered at batch 1, bucket 16."""
    i32 = jnp.int32
    toks, one = jnp.zeros((1, 16), i32), jnp.ones((1,), i32)
    tok = jnp.zeros((1, 1), i32)
    caches = make_caches(DEC_CFG, 1, 22, dtype=jnp.float32)
    dcaches = make_caches(DRAFT_CFG, 1, 22, dtype=jnp.float32)
    none3 = (None, None, None)
    p, dp = dec.params, dec.draft_params
    calls = {
        "enc_forward": lambda: enc._encoder_fn(16).lower(
            enc.params, toks, jnp.ones((1, 16), bool)),
        "dec_scan": lambda: dec._decode_scan_fn().lower(
            p, toks, one, caches, *none3),
        "dec_prefill_legacy": lambda: dec._decode_fns()[0].lower(
            p, toks, caches),
        "dec_step_legacy": lambda: dec._decode_fns()[1].lower(
            p, tok, tok, caches),
        "dec_prefill": lambda: dec._prefill_fn().lower(
            p, toks, one, caches, *none3),
        "dec_chunk": lambda: dec._chunk_fn().lower(
            p, jnp.zeros((1, 8), i32), jnp.zeros((1,), i32), one, caches,
            *none3),
        "dec_segment": lambda: dec._segment_fn().lower(
            p, tok, tok, caches, jnp.ones((1,), bool), one, -one, *none3),
        "spec_dprefill": lambda: dec._draft_prefill_fn().lower(
            dp, toks, dcaches),
        "spec_round": lambda: dec._spec_round_fn().lower(
            p, dp, tok, tok, caches, dcaches, *none3),
    }
    return calls[name]()


@pytest.mark.parametrize("name", [
    "enc_forward", "dec_scan", "dec_prefill_legacy", "dec_step_legacy",
    "dec_prefill", "dec_chunk", "dec_segment", "spec_dprefill",
    "spec_round"])
def test_jitted_programs_carry_their_names(name, engines):
    assert f"jit_{name}" in _lowered(name, *engines).as_text()
