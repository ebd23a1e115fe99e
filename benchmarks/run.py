"""Benchmark harness — one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows (derived = the table's headline
quantity for that benchmark).

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run --only kernels
  PYTHONPATH=src python -m benchmarks.run --json     # + BENCH_<name>.json
  PYTHONPATH=src python -m benchmarks.run --smoke    # CI: tiny shapes

``--json`` writes one ``BENCH_<name>.json`` per row (fields: name,
us_per_call, derived) so successive PRs leave a machine-readable perf
trajectory to diff against. ``--smoke`` runs every benchmark at reduced
shapes/iterations — the numbers are meaningless but every perf-path import
and compile is exercised (the CI rot check).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

SMOKE = False    # set by --smoke: tiny shapes, import/compile check only


def _timeit(fn, *args, warmup=2, iters=5):
    for _ in range(warmup):
        fn(*args)
    t0 = time.perf_counter()
    for _ in range(iters):
        fn(*args)
    return (time.perf_counter() - t0) / iters * 1e6  # us


def bench_tables_2_to_4() -> list:
    """Paper Tables 2-4: per-provider latency ladders. us_per_call times one
    full 21-machine model fit; derived = mean latency MAPE vs the paper's
    630 published cells (the reproduction fidelity number)."""
    from repro.core import perfsim
    us = _timeit(perfsim.fit_all, warmup=1, iters=3)
    summary = perfsim.validation_summary()
    rows = [("table2_4_perfsim_fit", us, f"mape={summary['mean_mape']:.3f}")]
    # per-provider derived values: worst machine MAPE
    for prov in ("AWS", "GCP", "Azure"):
        worst = max(v for k, v in summary["per_machine_mape"].items()
                    if k.startswith(prov))
        rows.append((f"table{2 + ['AWS', 'GCP', 'Azure'].index(prov)}"
                     f"_{prov.lower()}_ladder", us / 3,
                     f"worst_mape={worst:.3f}"))
    return rows


def bench_table5_cost() -> list:
    """Paper Table 5: cost analysis. derived = overall GPU/CPU cost ratio
    (paper headline: '300% more' ~ measured 2.5x)."""
    from repro.core import costmodel
    us = _timeit(costmodel.gpu_cost_premium, iters=10)
    prem = costmodel.gpu_cost_premium()
    rows = [("table5_gpu_premium", us, f"ratio={prem['overall']:.3f}")]
    us2 = _timeit(costmodel.cost_per_million_sentences, iters=10)
    cpm = costmodel.cost_per_million_sentences()
    best = min((v, f"{p}/{m}") for p, d in cpm.items()
               for m, v in d.items())
    rows.append(("table5_usd_per_1m_sentences", us2,
                 f"best={best[1]}@{best[0]:.2f}"))
    return rows


def bench_findings() -> list:
    """§4 findings validation (the paper's headline claims)."""
    from repro.core import analysis
    t0 = time.perf_counter()
    f = analysis.all_findings()
    us = (time.perf_counter() - t0) * 1e6
    n_hold = sum(1 for v in f.values()
                 if isinstance(v, dict) and v.get("holds"))
    return [("findings_validation", us, f"holds={n_hold}/5")]


def bench_kernels() -> list:
    """Pallas kernels (interpret mode on CPU — correctness-path timing) vs
    the XLA reference; derived = max |err| vs oracle."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.kernels.ref import flash_attention_ref, matmul_ref

    rows = []
    x = jax.random.normal(jax.random.PRNGKey(0), (256, 256), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 256), jnp.float32)
    f = jax.jit(lambda a, b: ops.matmul(a, b))
    us = _timeit(lambda: jax.block_until_ready(f(x, w)))
    err = float(abs(np.asarray(f(x, w)) - np.asarray(matmul_ref(x, w))).max())
    rows.append(("kernel_cache_matmul_256", us, f"maxerr={err:.2e}"))

    B, S, H, D = 1, 256, 4, 64
    q = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, D), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(3), (B, S, 2, D), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(4), (B, S, 2, D), jnp.float32)
    fa = jax.jit(lambda a, b, c: ops.mha_prefill(a, b, c, bq=128, bk=128))
    us = _timeit(lambda: jax.block_until_ready(fa(q, k, v)))
    ref = flash_attention_ref(
        q.transpose(0, 2, 1, 3).reshape(B * H, S, D),
        k.transpose(0, 2, 1, 3).reshape(B * 2, S, D),
        v.transpose(0, 2, 1, 3).reshape(B * 2, S, D)).reshape(
            B, H, S, D).transpose(0, 2, 1, 3)
    err = float(abs(np.asarray(fa(q, k, v)) - np.asarray(ref)).max())
    rows.append(("kernel_flash_attention_256", us, f"maxerr={err:.2e}"))

    from repro.kernels.ref import rglru_scan_ref
    a = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(5),
                                         (2, 256, 128)))
    bb = jax.random.normal(jax.random.PRNGKey(6), (2, 256, 128)) * 0.1
    fl = jax.jit(lambda x, y: ops.lru_scan(x, y, bs=128))
    us = _timeit(lambda: jax.block_until_ready(fl(a, bb)))
    err = float(abs(np.asarray(fl(a, bb))
                    - np.asarray(rglru_scan_ref(a, bb))).max())
    rows.append(("kernel_rglru_scan_256", us, f"maxerr={err:.2e}"))
    return rows


def bench_engine_ladder() -> list:
    """The POC itself (miniature): engine latency at NS=1 vs NS=16 —
    derived = the concurrency slowdown factor (the paper's core curve)."""
    import jax
    from repro.configs import get_config
    from repro.core.loadtest import run_ladder
    from repro.models import init_params
    from repro.serving import EngineConfig, ServingEngine

    cfg = get_config("gector-base", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params,
                        EngineConfig(mode="encoder", max_batch=8,
                                     pad_buckets=(32,)))
    try:
        sents = [np.random.randint(0, cfg.vocab_size, (16,))
                 for _ in range(64)]
        t0 = time.perf_counter()
        cells = run_ladder(eng, sents, ladder=(1, 16), repeats=1)
        us = (time.perf_counter() - t0) * 1e6
    finally:
        eng.close()
    slow = cells[1].latency_s / max(cells[0].latency_s, 1e-9)
    return [("engine_ladder_1_to_16", us, f"slowdown={slow:.2f}x")]


def bench_decode_hotpath() -> list:
    """PR 'fast decode hot path' before/after numbers: KV blocks visited by
    the block-skipping flash kernel (causal + windowed), engine decode
    latency with the fused scan+pool path vs the seed's per-token loop, and
    the per-batch cache-acquisition cost (pool reset-on-assign vs a fresh
    make_caches allocation sweep)."""
    import jax
    import jax.numpy as jnp
    from concurrent.futures import Future
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.kernels.flash_attention import flash_attention
    from repro.models import init_params, make_caches
    from repro.serving import EngineConfig, ServingEngine
    from repro.serving.engine import _Request
    from repro.serving.kvcache import CachePool

    rows = []

    # --- kernel: fraction of KV blocks actually scored ------------------
    S, bq, bk = 512, 64, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (4, S, 32), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (2, S, 32), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (2, S, 32), jnp.float32)
    for label, window in (("causal", None), ("window64", 64)):
        fa = jax.jit(lambda a, b, c, w=window: flash_attention(
            a, b, c, causal=True, window=w, bq=bq, bk=bk,
            return_visits=True, interpret=not ops._on_tpu()))
        us = _timeit(lambda: jax.block_until_ready(fa(q, k, v)[0]))
        vis = int(np.asarray(fa(q, k, v)[1]).sum()) // q.shape[0]
        total = (S // bq) * (S // bk)
        rows.append((f"flash_block_skip_{label}", us,
                     f"visited={vis}/{total}"))

    # --- engine: fused scan+pool decode vs seed per-token loop ----------
    cfg = get_config("qwen2-0.5b", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    B, T = 4, (4 if SMOKE else 16)
    prompts = [rng.randint(0, cfg.vocab_size, (rng.randint(4, 20),))
               for _ in range(B)]

    def serve_once(eng):
        reqs = [_Request(np.asarray(p, np.int32), Future(),
                         time.perf_counter()) for p in prompts]
        eng._serve_batch(reqs)    # measure the serve path, not queue wait
        return [r.future.result() for r in reqs]

    timings = {}
    for label, scan, pool in (("loop", False, False),
                              ("scan_pool", True, True)):
        eng = ServingEngine(cfg, params, EngineConfig(
            mode="decoder", max_batch=B, max_new_tokens=T,
            pad_buckets=(32,), use_scan_decode=scan, use_cache_pool=pool))
        try:
            timings[label] = _timeit(lambda: serve_once(eng), warmup=1,
                                     iters=5)
        finally:
            eng.close()
    speedup = timings["loop"] / timings["scan_pool"]
    rows.append(("engine_decode_loop_b4_t16", timings["loop"],
                 f"us_per_tok={timings['loop'] / (B * T):.0f}"))
    rows.append(("engine_decode_scan_b4_t16", timings["scan_pool"],
                 f"us_per_tok={timings['scan_pool'] / (B * T):.0f};"
                 f"speedup={speedup:.2f}x"))

    # --- memory: per-batch cache acquisition ----------------------------
    L = 32 + T
    us_alloc = _timeit(lambda: jax.block_until_ready(
        jax.tree.leaves(make_caches(cfg, B, L, dtype=jnp.float32))[0]),
        warmup=1, iters=10)
    cpool = CachePool(cfg, B, L, dtype=jnp.float32)

    def pool_acquire():
        slots, view = cpool.acquire(range(B))
        cpool.release_many(slots)
        return jax.block_until_ready(jax.tree.leaves(view)[0])

    us_pool = _timeit(pool_acquire, warmup=1, iters=10)
    rows.append(("cache_acquire_make_caches", us_alloc, f"batch={B}"))
    rows.append(("cache_acquire_pool", us_pool,
                 f"ratio={us_alloc / max(us_pool, 1e-9):.2f}x"))
    return rows


def bench_continuous_batching() -> list:
    """Staggered-arrival (open-loop) serving with heterogeneous per-request
    token budgets: p95 per-request latency and tokens/s, step-level
    continuous batching vs batch-at-a-time at the same offered load. The
    arrival gap is a fraction of one full decode so requests land
    mid-decode; budgets are mixed (short and long requests) — the regime
    step granularity exists for: a mid-decode arrival joins the in-flight
    batch instead of queueing behind it, and a short row retires (freeing
    its slot) the step it finishes instead of riding out the batch's full
    max_new_tokens. derived = p95 latency + throughput; the continuous row
    also reports its p95 speedup over the batch row."""
    import jax
    import jax.numpy as jnp
    from concurrent.futures import Future
    from repro.configs import get_config
    from repro.core.loadtest import run_staggered
    from repro.models import init_params
    from repro.serving import EngineConfig, SamplingParams, ServingEngine
    from repro.serving.engine import _Request

    cfg = get_config("qwen2-0.5b", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    MB, BUCKET = 4, 16
    T = 16 if SMOKE else 64
    n_req = 8 if SMOKE else 24
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, cfg.vocab_size, (rng.randint(4, 14),))
               for _ in range(n_req)]
    # requests mostly stop well before the engine cap (the real-serving
    # shape: eos fires early) — batch-at-a-time still decodes T steps for
    # every batch, step-level stops each row at its own budget
    budgets = [int(b) for b in rng.randint(T // 8, T // 2 + 1, n_req)]
    sampling = [SamplingParams(max_new_tokens=b) for b in budgets]

    def warm(eng, continuous):
        """Compile every shape the run can hit, deterministically — a
        mid-run jit compile would swamp the scheduling effect."""
        if continuous:
            pool = eng._get_pool(BUCKET)
            pf = eng._prefill_fn()
            for B in range(1, MB + 1):       # prefill-into-slot per join size
                slots, view = pool.acquire([f"w{B}.{i}" for i in range(B)],
                                           gather=True)
                tks = jnp.zeros((B, BUCKET), jnp.int32)
                lns = jnp.full((B,), 5, jnp.int32)
                tok, caches = pf(eng.params, tks, lns, view,
                                 None, None, None)
                pool.write_back(slots, caches)
                jax.block_until_ready(tok)
                pool.release_many(slots)
        else:
            for B in range(1, MB + 1):       # fused serve per batch size
                eng._serve_batch([
                    _Request(np.asarray(prompts[i % n_req], np.int32),
                             Future(), time.perf_counter())
                    for i in range(B)])
        # end-to-end worker path (continuous: + the decode segment fn);
        # median of 3 so the load knob derived from it is stable vs noise
        serve = [eng.generate(prompts[0]).result(timeout=600).timing.total_s
                 for _ in range(3)]
        return float(np.median(serve))

    def measure(continuous, gap_s=None):
        eng = ServingEngine(cfg, params, EngineConfig(
            mode="decoder", max_batch=MB, max_new_tokens=T,
            pad_buckets=(BUCKET,), decode_segment=8, continuous=continuous))
        try:
            one_req_s = warm(eng, continuous)
            if gap_s is None:
                # ~2.5 arrivals per full-budget decode: the backlog regime
                # where per-request budgets decide capacity (batch-at-a-
                # time pays T steps for every request; step-level retires
                # rows at their own budget)
                gap_s = one_req_s / 2.5
            best = None
            for _ in range(3):               # best-of-3 vs host noise
                eng.discard_samples()
                r = run_staggered(eng, prompts, gap_s=gap_s,
                                  sampling=sampling)
                if best is None or r.latency_p95_s < best.latency_p95_s:
                    best = r
        finally:
            eng.close()
        return best, gap_s

    batch, gap = measure(False)          # the same offered load for both
    cont, _ = measure(True, gap_s=gap)
    rows = [("continuous_batching_batch", batch.wall_s * 1e6,
             f"p95={batch.latency_p95_s:.3f}s;"
             f"tok_s={batch.tokens_per_s:.1f}"),
            ("continuous_batching_cont", cont.wall_s * 1e6,
             f"p95={cont.latency_p95_s:.3f}s;"
             f"tok_s={cont.tokens_per_s:.1f};"
             f"p95_speedup={batch.latency_p95_s / cont.latency_p95_s:.2f}x")]
    return rows


def bench_multi_bucket() -> list:
    """Mixed-bucket staggered arrivals: per-request p95 latency with
    per-bucket lanes vs the legacy single-set scheduler at the same
    offered load. The workload is the shape the paper's corpus has —
    a stream of short interactive requests (bucket 32) with occasional
    long-decode requests in another bucket (16). The single-set baseline
    recreates the cross-bucket head-of-line cliff: every interactive
    request arriving during a long decode waits for that set to drain,
    so the interactive tail inflates to the long request's service time;
    lanes admit them into their own bucket's free slots immediately. The
    long requests themselves decode slower under lanes (their segments
    round-robin with the busy interactive lane — the fixed-width-segment
    occupancy trade the ROADMAP tracks), which is why p95 is taken over
    the workload including the interactive tail, not the max. derived =
    p95 + throughput; the lanes row also reports its p95 speedup."""
    import jax
    from repro.configs import get_config
    from repro.core.loadtest import run_staggered
    from repro.models import init_params
    from repro.serving import EngineConfig, SamplingParams, ServingEngine

    cfg = get_config("qwen2-0.5b", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    MB, BUCKETS = 4, (16, 32)
    T = 24 if SMOKE else 64              # long-request budget (the hog)
    n_req = 12 if SMOKE else 40
    hog_every = n_req // 2 if SMOKE else 20
    rng = np.random.default_rng(7)
    prompts, sampling = [], []
    for i in range(n_req):
        if i % hog_every == hog_every // 2:   # rare long decode, bucket 16
            prompts.append(rng.integers(0, cfg.vocab_size,
                                        (int(rng.integers(4, 17)),)))
            sampling.append(SamplingParams(max_new_tokens=T))
        else:                                 # interactive, bucket 32
            prompts.append(rng.integers(0, cfg.vocab_size,
                                        (int(rng.integers(17, 33)),)))
            sampling.append(SamplingParams(max_new_tokens=4))

    def measure(multi_lane, gap_s=None):
        eng = ServingEngine(cfg, params, EngineConfig(
            mode="decoder", max_batch=MB, max_new_tokens=T,
            pad_buckets=BUCKETS, decode_segment=4, multi_lane=multi_lane))
        try:
            eng.warmup()     # every bucket: join sizes + segments
            serve = [eng.generate(prompts[0],
                                  SamplingParams(max_new_tokens=4)).result(
                timeout=600).timing.total_s for _ in range(3)]
            if gap_s is None:
                # one interactive arrival per interactive service time:
                # the regime where a long decode in the other bucket
                # otherwise traps a train of interactive requests
                gap_s = float(np.median(serve))
            best = None
            for _ in range(3):               # best-of-3 vs host noise
                eng.discard_samples()
                r = run_staggered(eng, prompts, gap_s=gap_s,
                                  sampling=sampling)
                if best is None or r.latency_p95_s < best.latency_p95_s:
                    best = r
        finally:
            eng.close()
        return best, gap_s

    single, gap = measure(False)         # the same offered load for both
    lanes, _ = measure(True, gap_s=gap)
    return [("multi_bucket_single", single.wall_s * 1e6,
             f"p95={single.latency_p95_s:.3f}s;"
             f"tok_s={single.tokens_per_s:.1f}"),
            ("multi_bucket_lanes", lanes.wall_s * 1e6,
             f"p95={lanes.latency_p95_s:.3f}s;"
             f"tok_s={lanes.tokens_per_s:.1f};"
             f"p95_speedup="
             f"{single.latency_p95_s / lanes.latency_p95_s:.2f}x")]


def bench_segment_width() -> list:
    """Occupancy-adaptive decode-segment widths vs always-max_batch, on the
    bench_multi_bucket staggered scenario (interactive bucket-32 stream +
    rare long bucket-16 decodes) at the same offered load. Under
    ``segment_width='fixed'`` the long request decodes at width max_batch
    even though it is alone in its lane — the occupancy trade
    bench_multi_bucket exposed; 'adaptive' compacts each lane's segment to
    the smallest power-of-two tier that fits its live rows, so the lone
    long request runs width-1/2 segments (and the interactive lane's
    segments shrink too, cutting the long class's round-robin waits).
    derived = the long-request class's decode-phase mean latency (the
    quantity the ROADMAP flagged) + workload p95/tok_s; the adaptive row
    adds its long-class speedup and a greedy token-identity check against
    the fixed run."""
    import jax
    from repro.configs import get_config
    from repro.core.loadtest import run_staggered
    from repro.models import init_params
    from repro.serving import EngineConfig, SamplingParams, ServingEngine

    cfg = get_config("qwen2-0.5b", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    MB, BUCKETS = (4 if SMOKE else 8), (16, 32)
    T = 24 if SMOKE else 64              # long-request budget (the hog)
    n_req = 12 if SMOKE else 40
    hog_every = n_req // 2 if SMOKE else 20
    rng = np.random.default_rng(7)
    prompts, sampling, hogs = [], [], []
    for i in range(n_req):
        if i % hog_every == hog_every // 2:   # rare long decode, bucket 16
            hogs.append(i)
            prompts.append(rng.integers(0, cfg.vocab_size,
                                        (int(rng.integers(4, 17)),)))
            sampling.append(SamplingParams(max_new_tokens=T))
        else:                                 # interactive, bucket 32
            prompts.append(rng.integers(0, cfg.vocab_size,
                                        (int(rng.integers(17, 33)),)))
            sampling.append(SamplingParams(max_new_tokens=4))

    def measure(width_mode, gap_s=None):
        eng = ServingEngine(cfg, params, EngineConfig(
            mode="decoder", max_batch=MB, max_new_tokens=T,
            pad_buckets=BUCKETS, decode_segment=4,
            segment_width=width_mode))
        try:
            eng.warmup()     # every bucket x join size x width tier
            serve = [eng.generate(prompts[0],
                                  SamplingParams(max_new_tokens=4)).result(
                timeout=600).timing.total_s for _ in range(3)]
            if gap_s is None:
                # one interactive arrival per interactive service time —
                # the interactive lane stays busy while the hog decodes
                gap_s = float(np.median(serve))
            best = None
            for _ in range(3):               # best-of-3 vs host noise
                r = run_staggered(eng, prompts, gap_s=gap_s,
                                  sampling=sampling, keep_results=True)
                cand = {                     # per-class split from the
                    "long_dec": float(np.mean(        # per-request results
                        [r.results[i].timing.decode_s for i in hogs])),
                    "p95": r.latency_p95_s,
                    "wall": r.wall_s,
                    "tok_s": r.tokens_per_s,
                    "tokens": [x.tokens.tolist() for x in r.results]}
                if best is None or cand["long_dec"] < best["long_dec"]:
                    best = cand
        finally:
            eng.close()
        return best, gap_s

    fixed, gap = measure("fixed")        # the same offered load for both
    adaptive, _ = measure("adaptive", gap_s=gap)
    identical = fixed["tokens"] == adaptive["tokens"]
    return [("segment_width_fixed", fixed["wall"] * 1e6,
             f"long_decode_mean={fixed['long_dec']:.3f}s;"
             f"p95={fixed['p95']:.3f}s;tok_s={fixed['tok_s']:.1f}"),
            ("segment_width_adaptive", adaptive["wall"] * 1e6,
             f"long_decode_mean={adaptive['long_dec']:.3f}s;"
             f"p95={adaptive['p95']:.3f}s;tok_s={adaptive['tok_s']:.1f};"
             f"long_decode_speedup="
             f"{fixed['long_dec'] / max(adaptive['long_dec'], 1e-9):.2f}x;"
             f"tokens_identical={identical}")]


def bench_prefix_cache() -> list:
    """Shared-prompt KV reuse: staggered streams that all resend one long
    system prompt plus a short unique suffix — the traffic shape whose
    prefill cost the prefix store amortizes — with ``prefix_cache`` off vs
    on at the same offered load. A warm hit claims a lane slot, gathers the
    stored KV into it in one fused load, and prefills only the suffix
    chunk, so the warm-request prefill mean (every request after the first;
    the first populates the store) is the quantity the store exists to cut.
    derived = warm prefill mean + p95/tok_s; the on row adds its warm
    prefill speedup, the lane hit/miss counters, a greedy token-identity
    check against the off run, and the measured window's jit-compile count
    (must be 0: warm hits at arbitrary matched offsets re-use the chunk
    program, never specialize)."""
    import jax
    from repro.configs import get_config
    from repro.core.loadtest import run_staggered
    from repro.models import init_params
    from repro.serving import EngineConfig, SamplingParams, ServingEngine

    cfg = get_config("qwen2-0.5b", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    BUCKET = 32 if SMOKE else 128
    CHUNK = BUCKET // 4
    T = 4 if SMOKE else 8
    n_req = 6 if SMOKE else 12
    rng = np.random.default_rng(11)
    # system prompt fills 3/4 of the bucket; suffixes stay under one chunk
    # so every warm request prefills exactly one chunk instead of the
    # whole prompt
    sysprompt = rng.integers(0, cfg.vocab_size, (BUCKET * 3 // 4,))
    lo, hi = (2, 6) if SMOKE else (4, 12)
    prompts = [np.concatenate([
        sysprompt, rng.integers(0, cfg.vocab_size,
                                (int(rng.integers(lo, hi + 1)),))])
        for _ in range(n_req)]
    sampling = [SamplingParams(max_new_tokens=T) for _ in range(n_req)]

    def measure(prefix_cache, gap_s=None):
        eng = ServingEngine(cfg, params, EngineConfig(
            mode="decoder", max_batch=4, max_new_tokens=T,
            pad_buckets=(BUCKET,), decode_segment=2, prefill_chunk=CHUNK,
            prefix_cache=prefix_cache))
        try:
            eng.warmup()
            serve = [eng.generate(prompts[0], SamplingParams(
                max_new_tokens=T)).result(timeout=600).timing.total_s
                for _ in range(3)]
            if gap_s is None:
                # ~2 arrivals per full service time: requests overlap, so
                # warm hits join mid-flight the way shared-prompt traffic
                # actually lands
                gap_s = float(np.median(serve)) / 2
            best = None
            for _ in range(3):               # best-of-3 vs host noise
                eng.window()                 # counters cover this run only
                r = run_staggered(eng, prompts, gap_s=gap_s,
                                  sampling=sampling, keep_results=True)
                win = eng.window()
                lanes = win.get("lanes", {})
                cand = {
                    "warm_prefill": float(np.mean(
                        [x.timing.prefill_s for x in r.results[1:]])),
                    "p95": r.latency_p95_s, "wall": r.wall_s,
                    "tok_s": r.tokens_per_s,
                    "compiles": win.get("jit_compiles", -1),
                    "hits": sum(s.get("prefix_hits", 0)
                                for s in lanes.values()),
                    "misses": sum(s.get("prefix_misses", 0)
                                  for s in lanes.values()),
                    "tokens": [x.tokens.tolist() for x in r.results]}
                if (best is None
                        or cand["warm_prefill"] < best["warm_prefill"]):
                    best = cand
        finally:
            eng.close()
        return best, gap_s

    off, gap = measure(False)            # the same offered load for both
    on, _ = measure(True, gap_s=gap)
    identical = off["tokens"] == on["tokens"]
    return [("prefix_cache_off", off["wall"] * 1e6,
             f"warm_prefill_mean={off['warm_prefill'] * 1e3:.2f}ms;"
             f"p95={off['p95']:.3f}s;tok_s={off['tok_s']:.1f}"),
            ("prefix_cache_on", on["wall"] * 1e6,
             f"warm_prefill_mean={on['warm_prefill'] * 1e3:.2f}ms;"
             f"p95={on['p95']:.3f}s;tok_s={on['tok_s']:.1f};"
             f"warm_prefill_speedup="
             f"{off['warm_prefill'] / max(on['warm_prefill'], 1e-9):.2f}x;"
             f"hits={on['hits']};misses={on['misses']};"
             f"window_compiles={on['compiles']};"
             f"tokens_identical={identical}")]


def _train_lm(cfg, steps, data, seed=0):
    """Brief deterministic training of ``cfg`` on ``data``'s batches —
    returns the trained params (fresh init from ``seed``)."""
    import jax
    import jax.numpy as jnp
    from repro.models import init_params
    from repro.training import OptConfig, adamw_init, train_step

    params = init_params(cfg, jax.random.PRNGKey(seed))
    oc = OptConfig(lr=3e-3, warmup_steps=5, total_steps=steps)
    state = adamw_init(params)
    step_fn = jax.jit(lambda p, s, b: train_step(cfg, oc, p, s, b))
    for batch in data.batches(steps):
        params, state, _ = step_fn(params, state,
                                   {"tokens": jnp.asarray(batch["tokens"])})
    return params


def _trained_smoke_lm(steps=60):
    """qwen2-0.5b smoke briefly trained on the synthetic phrase corpus.

    The quant benches measure token drift against the bf16 baseline, and a
    random-init model's logit margins are near-ties — any perturbation
    flips argmax, so agreement there measures init noise, not
    quantization. A minute of training on SyntheticLM's recurring phrases
    gives trained-scale margins (median top-2 gap grows ~4x), which is the
    regime the paper's deployments serve in. Deterministic (fixed seeds).
    Returns (cfg, params, data)."""
    from repro.configs import get_config
    from repro.training.data import DataConfig, SyntheticLM

    cfg = get_config("qwen2-0.5b", smoke=True)
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                  batch_size=16, seed=0))
    params = _train_lm(cfg, steps, data, seed=0)
    return cfg, params, data


def bench_quant() -> list:
    """int8 weights + int8 KV cache vs the bf16/f32 baseline.

    Two measurements on a briefly-trained smoke model (same engine config
    both arms, warmup before every measured window):

    * drift — teacher-forced greedy top-1 agreement: the quantized model
      decodes the bf16 arm's token stream (prefill + per-step decode
      through the int8 KV cache) and its per-step argmax is compared
      position-wise. Teacher forcing isolates per-step drift from the
      cascade a single early flip causes in free-running generation.
    * footprint + serving — a staggered load at identical offered rate,
      off vs on; footprint = (weight_bytes + lane kv_bytes) ratio from the
      engine gauges, and the measured window must stay compile-clean
      (``window_compiles=0``) in both arms: warmup primes the quantized
      variants, nothing specializes mid-measurement.

    derived: off row = footprint bytes + p95/tok_s; on row adds
    footprint_ratio, top1_agreement (and drift_ok, the >= 0.99 bound CI
    greps), and both arms' window compile counts."""
    import jax
    import jax.numpy as jnp
    from repro.core.loadtest import run_staggered
    from repro.models import decode_step, make_caches, prefill
    from repro.quant import quantize_params
    from repro.serving import EngineConfig, SamplingParams, ServingEngine

    cfg, params, data = _trained_smoke_lm()
    qparams = quantize_params(params)

    # ---- drift: teacher-forced per-step top-1 agreement
    B, T_drift, Lp = 8, 16, 24
    dr = np.random.default_rng(1)
    data.rng = dr                       # decouple from training draws
    prompts_d = np.stack([data._doc(Lp) for _ in range(B)]).astype(np.int32)

    def forced(p, kv_quant, teacher=None):
        caches = make_caches(cfg, B, Lp + T_drift, dtype=jnp.float32,
                             kv_quant=kv_quant)
        logits, caches, _ = prefill(cfg, p, jnp.asarray(prompts_d), caches)
        preds = [np.asarray(jnp.argmax(logits[:, -1], -1))]
        pos = jnp.full((B,), Lp, jnp.int32)
        for t in range(T_drift - 1):
            tok = jnp.asarray(preds[t] if teacher is None else teacher[:, t])
            logits, caches, _ = decode_step(cfg, p, tok[:, None],
                                            pos[:, None], caches)
            preds.append(np.asarray(jnp.argmax(logits[:, 0], -1)))
            pos = pos + 1
        return np.stack(preds, 1)

    base_tok = forced(params, None)
    agreement = float((forced(qparams, "int8", base_tok) == base_tok).mean())

    # ---- footprint + serving A/B
    BUCKET = 32 if SMOKE else 128
    T = 4 if SMOKE else 16
    n_req = 6 if SMOKE else 12
    MB = 4 if SMOKE else 8
    rng = np.random.default_rng(13)
    data.rng = rng
    lo, hi = (BUCKET // 2, BUCKET - 2)
    prompts = [data._doc(int(rng.integers(lo, hi + 1))) for _ in range(n_req)]
    sampling = [SamplingParams(max_new_tokens=T) for _ in range(n_req)]

    def measure(quant, gap_s=None):
        eng = ServingEngine(cfg, params, EngineConfig(
            mode="decoder", max_batch=MB, max_new_tokens=T,
            pad_buckets=(BUCKET,), decode_segment=2,
            prefill_chunk=BUCKET // 4,
            weight_quant="int8" if quant else None,
            kv_quant="int8" if quant else None))
        try:
            eng.warmup()
            serve = [eng.generate(prompts[0], SamplingParams(
                max_new_tokens=T)).result(timeout=600).timing.total_s
                for _ in range(3)]
            if gap_s is None:
                gap_s = float(np.median(serve)) / 2
            best, compiles = None, 0     # compiles: worst across all runs
            for _ in range(3):               # best-of-3 vs host noise
                eng.window()                 # counters cover this run only
                r = run_staggered(eng, prompts, gap_s=gap_s,
                                  sampling=sampling, keep_results=True)
                win = eng.window()
                m = eng.metrics()
                compiles = max(compiles, win.get("jit_compiles", -1))
                cand = {
                    "p95": r.latency_p95_s, "wall": r.wall_s,
                    "tok_s": r.tokens_per_s,
                    "weight_bytes": m["weight_bytes"],
                    "kv_bytes": sum(s.get("kv_bytes", 0)
                                    for s in m.get("lanes", {}).values())}
                if best is None or cand["p95"] < best["p95"]:
                    best = cand
            best["compiles"] = compiles
        finally:
            eng.close()
        return best, gap_s

    off, gap = measure(False)            # the same offered load for both
    on, _ = measure(True, gap_s=gap)
    foot_off = off["weight_bytes"] + off["kv_bytes"]
    foot_on = on["weight_bytes"] + on["kv_bytes"]
    ratio = foot_off / max(foot_on, 1)
    return [("quant_off", off["wall"] * 1e6,
             f"weight_bytes={off['weight_bytes']};"
             f"kv_bytes={off['kv_bytes']};"
             f"p95={off['p95']:.3f}s;tok_s={off['tok_s']:.1f};"
             f"window_compiles={off['compiles']}"),
            ("quant_on", on["wall"] * 1e6,
             f"weight_bytes={on['weight_bytes']};"
             f"kv_bytes={on['kv_bytes']};"
             f"p95={on['p95']:.3f}s;tok_s={on['tok_s']:.1f};"
             f"footprint_ratio={ratio:.2f}x;"
             f"top1_agreement={agreement:.4f};"
             f"drift_ok={agreement >= 0.99};"
             f"window_compiles={on['compiles']}")]


def bench_spec_decode() -> list:
    """Speculative decoding (draft-and-verify) vs plain continuous decode.

    Target = the briefly-trained qwen2-0.5b smoke model; draft = a 4x
    smaller single-layer model trained on the same synthetic phrase
    corpus (the regime speculation needs: the corpus is predictable
    enough that the draft's greedy continuations usually match the
    target's). Both arms serve the same greedy closed batch at
    bench_decode_hotpath shapes (B=4, prompt lens 4..20, T new tokens,
    bucket 32); the spec arm proposes ``spec_k`` draft tokens per round
    and the target verifies them in one chunked forward, committing the
    agreed prefix plus one corrected token.

    derived: off row = us/token; on row adds speedup (the acceptance
    criterion: >= 2x at temperature=0), the measured accept rate,
    tokens_identical (greedy spec decode must reproduce the plain arm's
    streams bit-for-bit — speculation is a latency optimization, never a
    sampler change) and window_compiles (must be 0: warmup primes the
    draft/verify/rollback variants)."""
    import dataclasses
    from repro.configs import get_config
    from repro.serving import EngineConfig, SamplingParams, ServingEngine
    from repro.training.data import DataConfig, SyntheticLM

    steps = 20 if SMOKE else 60
    cfg = get_config("qwen2-0.5b", smoke=True)
    if not SMOKE:
        # speculation pays when the target forward is flops-bound (the
        # draft's advantage is its 16x flops discount; at smoke width both
        # forwards sit on the dispatch-overhead floor and the discount
        # vanishes) — widen the target to the smallest shape where compute
        # dominates. Smoke keeps the stock width: CI only checks identity
        # and compile-cleanliness there, not the speedup.
        cfg = dataclasses.replace(cfg, d_model=640, n_heads=10,
                                  n_kv_heads=2, d_ff=1536)
    cfg = dataclasses.replace(cfg, name="qwen2-smoke-spec-target")
    dcfg = dataclasses.replace(cfg, name="qwen2-smoke-spec-draft",
                               n_layers=1, d_model=112, n_heads=7,
                               n_kv_heads=1, d_ff=256)
    dcf = DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                     batch_size=16, seed=0)
    data = SyntheticLM(dcf)
    params = _train_lm(cfg, steps, data, seed=0)
    dparams = _train_lm(dcfg, steps, SyntheticLM(dcf), seed=1)

    B, T = 4, (8 if SMOKE else 16)
    rng = np.random.default_rng(5)
    data.rng = rng                      # decouple from training draws
    prompts = [data._doc(int(rng.integers(4, 20))) for _ in range(B)]
    sampling = [SamplingParams(max_new_tokens=T) for _ in range(B)]

    def measure(spec):
        eng = ServingEngine(cfg, params, EngineConfig(
            mode="decoder", max_batch=B, max_new_tokens=T,
            pad_buckets=(32,), decode_segment=8,
            spec_decode=spec, spec_k=7),
            draft=(dcfg, dparams) if spec else None)
        try:
            eng.warmup()
            eng.window()                # measured span starts compile-clean

            def serve():
                hs = [eng.generate(p, s) for p, s in zip(prompts, sampling)]
                return [h.result(timeout=600).tokens for h in hs]

            us = _timeit(serve, warmup=1, iters=1 if SMOKE else 5)
            toks = serve()
            win = eng.window()
            lanes = win.get("lanes", {})
            prop = sum(s.get("spec_proposed", 0) for s in lanes.values())
            acc = sum(s.get("spec_accepted", 0) for s in lanes.values())
        finally:
            eng.close()
        return {"us": us, "tokens": [t.tolist() for t in toks],
                "compiles": win.get("jit_compiles", -1),
                "accept": acc / prop if prop else 0.0}

    off = measure(False)
    on = measure(True)
    identical = off["tokens"] == on["tokens"]
    speedup = off["us"] / max(on["us"], 1e-9)
    return [("spec_decode_off", off["us"],
             f"us_per_tok={off['us'] / (B * T):.0f};"
             f"window_compiles={off['compiles']}"),
            ("spec_decode_on", on["us"],
             f"us_per_tok={on['us'] / (B * T):.0f};"
             f"speedup={speedup:.2f}x;"
             f"accept_rate={on['accept']:.3f};"
             f"tokens_identical={identical};"
             f"window_compiles={on['compiles']}")]


def bench_deploy_lab() -> list:
    """Deployment-lab harness: one profile x one ladder scenario through
    ExperimentRunner + drift_report. us_per_call times the whole grid;
    derived = records emitted + findings ledger coverage (must list every
    paper finding) — the rot check for the experiment subsystem."""
    import jax
    from repro.configs import get_config
    from repro.deploy.profiles import profile
    from repro.deploy.report import PAPER_FINDINGS, drift_report
    from repro.deploy.runner import ExperimentRunner, WorkloadScenario
    from repro.models import init_params
    from repro.serving import EngineConfig, ServingEngine

    cfg = get_config("gector-base", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))

    def factory(scenario):
        eng = ServingEngine(cfg, params,
                            EngineConfig(mode="encoder", max_batch=4,
                                         pad_buckets=(32,)))
        rng = np.random.RandomState(0)
        sents = [rng.randint(0, cfg.vocab_size, (16,)) for _ in range(32)]
        return eng, sents, None

    scenario = WorkloadScenario(name="bench", ladder=(1, 2), repeats=1)
    runner = ExperimentRunner(factory)
    t0 = time.perf_counter()
    records = runner.run_grid([profile("AWS", "C")], [scenario])
    report = drift_report(records)
    us = (time.perf_counter() - t0) * 1e6
    listed = sum(1 for k in PAPER_FINDINGS if k in report["findings"])
    return [("deploy_lab_grid", us,
             f"records={len(records)};"
             f"findings={listed}/{len(PAPER_FINDINGS)}")]


def bench_roofline_summary() -> list:
    """Dry-run roofline (from benchmarks/dryrun_single_pod.json if present);
    derived = count of pairs by dominant term."""
    import json
    import os
    path = os.path.join(os.path.dirname(__file__), "dryrun_single_pod.json")
    if not os.path.exists(path):
        return [("roofline_summary", 0.0,
                 "no dryrun json (run dryrun --all)")]
    t0 = time.perf_counter()
    with open(path) as f:
        data = json.load(f)
    doms = {}
    for r in data["results"]:
        if "roofline" in r:
            doms[r["roofline"]["dominant"]] = \
                doms.get(r["roofline"]["dominant"], 0) + 1
    us = (time.perf_counter() - t0) * 1e6
    return [("roofline_summary", us,
             ";".join(f"{k}={v}" for k, v in sorted(doms.items())))]


ALL = {
    "tables_2_to_4": bench_tables_2_to_4,
    "table5": bench_table5_cost,
    "findings": bench_findings,
    "kernels": bench_kernels,
    "engine": bench_engine_ladder,
    "decode_hotpath": bench_decode_hotpath,
    "continuous_batching": bench_continuous_batching,
    "multi_bucket": bench_multi_bucket,
    "segment_width": bench_segment_width,
    "prefix_cache": bench_prefix_cache,
    "quant": bench_quant,
    "spec_decode": bench_spec_decode,
    "deploy_lab": bench_deploy_lab,
    "roofline": bench_roofline_summary,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=sorted(ALL), default=None)
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_<name>.json per row (perf trajectory)")
    ap.add_argument("--json-dir", default=".",
                    help="directory for --json output files")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes/iterations: exercise every perf-path "
                         "import and compile without the full timings")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.smoke:
        global SMOKE
        SMOKE = True
    names = [args.only] if args.only else list(ALL)
    print("name,us_per_call,derived")
    ok = True
    for n in names:
        try:
            rows = ALL[n]()
            if not rows:
                raise RuntimeError("benchmark returned no rows")
        except Exception as e:  # noqa: BLE001
            # a failed/empty run writes no JSON: the BENCH_* files are the
            # perf trajectory across PRs, and clobbering a good datapoint
            # with nothing would erase it from the diff
            ok = False
            print(f"{n},nan,ERROR:{e}", file=sys.stderr)
            if args.json:
                print(f"{n}: wrote no BENCH_*.json — any existing "
                      f"datapoints for this benchmark are preserved",
                      file=sys.stderr)
            continue
        for row in rows:
            print(f"{row[0]},{row[1]:.1f},{row[2]}")
            if args.json:
                path = os.path.join(args.json_dir, f"BENCH_{row[0]}.json")
                with open(path, "w") as f:
                    json.dump({"name": row[0],
                               "us_per_call": round(row[1], 1),
                               "derived": row[2]}, f, indent=2)
                    f.write("\n")
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
