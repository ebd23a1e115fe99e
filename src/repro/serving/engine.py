"""MLaaS serving engine — the JAX-native stand-in for the paper's
Nginx + Flask + GECToR stack (Fig. 6).

Two modes, matching the two model kinds in the repo:
  * 'encoder' — one bidirectional forward per request batch (GECToR: the
    paper's workload). Requests are token sequences; responses are the
    model's per-token outputs (edit tags for GECToR). The worker keeps
    up to two batches going: the next batch is dispatched while the
    last one's outputs are still on their way to the host.
  * 'decoder' — prefill + autoregressive decode with a KV-cache pool.

Decoder requests go through the typed v2 lifecycle (``serving.api``):
``engine.generate(GenerationRequest | tokens)`` returns a
``RequestHandle`` (streaming iterator + future) that resolves to a
``GenerationResult`` (tokens, finish_reason, per-phase timing). The default
decoder worker is the step-driven continuous scheduler
(``serving.continuous``): decode runs in short jitted scan segments over a
fixed slot batch; between segments finished rows retire (per-row eos /
max_new_tokens stop in-graph, see ``models.decode_segment``) and newly
admitted requests prefill straight into free ``CachePool`` slots — a
request submitted mid-decode joins the in-flight batch instead of waiting
behind it. ``continuous=False`` keeps the PR-1 batch-at-a-time worker for
A/B equivalence runs: a background thread drains the queue and forms
batches (up to ``max_batch``, waiting at most ``batch_window_ms``), serving
prefill + first-token + the remaining steps as one jitted
``models.decode_segment`` call (``use_scan_decode=False`` further falls
back to the seed's per-token Python loop).

An optional ``AdmissionQueue`` bounds in-flight work (the paper's proposed
§4 mitigation): submit try-acquires a slot and, when saturated, parks the
request on a priority-ordered overflow queue; a finishing request hands its
slot to the best parked one. Submission never blocks and no dispatcher
thread is spawned per request. Per-request wall latency, per-phase timing,
and batch-occupancy stats are recorded so the load-test client can tabulate
the paper's metrics — and the per-phase split it cannot see.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from concurrent.futures import Future
from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import (decode_segment, decode_step, forward, make_caches,
                          prefill_chunk, sample_logits,
                          spec_round as _spec_round)
from repro.quant import params_bytes, quantize_params, validate_kv_quant
from repro.serving.api import (FINISH_CANCELLED, FINISH_EOS, FINISH_LENGTH,
                               GenerationRequest, GenerationResult, HeadFn,
                               RequestHandle, RequestTiming, SamplingParams)
from repro.serving import hostmem, kvcache
from repro.serving.clock import WorkerClock
from repro.serving.kvcache import CachePool, _take_slots
from repro.serving.scheduler import (AdmissionQueue, RequestQueue,
                                     pick_tier, width_tiers)


# the batch worker's phases (``WorkerClock``): counters batch_<phase>_ns
BATCH_PHASES = ("wait", "collect", "pack", "dispatch", "device", "copy",
                "fanout")


class RequestTooLong(ValueError):
    """Raised (into the request's future) when a request exceeds the largest
    pad bucket — rejecting beats the silent truncation it replaces."""


@dataclasses.dataclass
class EngineConfig:
    mode: str = "encoder"             # 'encoder' | 'decoder'
    max_batch: int = 32
    batch_window_ms: float = 2.0
    pad_buckets: tuple = (32, 64, 128, 256, 512)
    max_inflight: Optional[int] = None   # admission control; None = off
    max_new_tokens: int = 16             # decoder: per-request budget cap
    use_scan_decode: bool = True         # fused lax.scan decode hot path
    use_cache_pool: bool = True          # pooled KV slots vs per-batch alloc
    # step-level continuous batching (decoder mode; requires scan + pool —
    # otherwise the engine falls back to the batch-at-a-time worker).
    # False = batch-at-a-time, kept for A/B equivalence runs.
    continuous: bool = True
    decode_segment: int = 4              # decode steps per jitted segment
    # per-bucket lanes: requests admit into their own bucket's slot set
    # immediately instead of waiting for another bucket's set to drain.
    # False = legacy single-set admission gate, kept for A/B runs
    # (bench_multi_bucket's baseline).
    multi_lane: bool = True
    # chunked prefill: a join whose prompt exceeds this many tokens
    # prefills in chunks of this size, interleaved with decode segments,
    # instead of stalling every in-flight row for the whole prompt's
    # forward. None = whole-prompt prefill (token-identical either way).
    prefill_chunk: Optional[int] = None
    # occupancy-adaptive decode segments: 'adaptive' compacts each lane's
    # live rows into the smallest width tier (powers of two up to
    # max_batch) before every segment, so a lane at occupancy 1 decodes at
    # width 1 instead of max_batch; 'fixed' keeps the full-width segment,
    # the A/B baseline (bench_segment_width). Token-identical either way.
    segment_width: str = "adaptive"
    # prefix cache: store completed prompts' KV at prefill_chunk-granular
    # boundaries; a joining request sharing a stored prefix copies it into
    # its slot (one fused gather/scatter) and prefills only the suffix.
    # Requires the continuous path + prefill_chunk, and a pure
    # global-attention pattern (no sliding-window rings / recurrent state
    # — those cannot be replayed at an absolute offset). Token-identical
    # to the cold path either way.
    prefix_cache: bool = False
    # per-bucket byte budget for stored prefix KV; None sizes the store to
    # max_batch slots' worth (LRU eviction keeps it under budget)
    prefix_cache_bytes: Optional[int] = None
    # weight quantization: "int8" quantizes the matmul layer classes
    # (attn projections + MLP; see quant/policy.py) to symmetric
    # per-channel int8 at engine init — projections then run the
    # dequant-fused matmul with no stored float weight copy. None (the
    # default) keeps the bf16 path bit-identical.
    weight_quant: Optional[str] = None
    # KV-cache quantization: "int8" stores pool slots as int8 K/V with
    # per-(position, head) f32 scale planes — quantize at scatter,
    # dequantize at gather; lanes, width tiers and the prefix cache carry
    # the scale planes unchanged. Decoder mode only.
    kv_quant: Optional[str] = None
    # speculative decoding: each scheduler turn a small draft model
    # proposes spec_k tokens per row and the target verifies all of them
    # in one fused forward, committing the leading agreements plus one
    # target-selected token (>= 1 token/round/row). Requires the
    # continuous path, a pure global-attention pattern on both models,
    # and a ``draft=(draft_cfg, draft_params)`` pair at engine
    # construction. Token-identical to plain decode, greedy or sampled.
    spec_decode: bool = False
    # draft tokens proposed per round; the verify chunk covers
    # spec_k + 1 positions, so each slot carries spec_k positions of ring
    # headroom beyond bucket + max_new_tokens
    spec_k: int = 4


@dataclasses.dataclass
class _Request:
    """Internal carrier. Legacy paths (encoder mode, raw benchmarks) build
    it with the three positional fields; v2 decoder requests also carry
    sampling params, priority, and the client handle."""
    tokens: np.ndarray
    future: Future
    t_submit: float
    sampling: Optional[SamplingParams] = None
    budget: int = 0                   # effective max_new_tokens
    priority: int = 0
    handle: Optional[RequestHandle] = None
    t_start: float = 0.0              # worker picked it up (prefill start)
    t_prefill_done: float = 0.0


class _Launched(NamedTuple):
    """An encoder batch dispatched by ``_launch_batch`` and not finished."""
    reqs: List[_Request]
    out: object                       # enc_forward's outputs, on the device
    on_worker: bool


def _trim_host(gen: np.ndarray, eos: np.ndarray, budget: np.ndarray):
    """Host-side emission trim for the batch-at-a-time path: a row's output
    ends at its budget or just after its first eos token. Token-identical
    to the in-graph retirement the continuous path does (sampling is
    counter-based per position, so tokens after a row's stop point never
    influence the kept prefix)."""
    B, T = gen.shape
    emits = np.zeros((B, T), bool)
    eos_hit = np.zeros(B, bool)
    for i in range(B):
        n = int(min(budget[i], T))
        if eos[i] >= 0:
            where = np.where(gen[i, :n] == eos[i])[0]
            if where.size:
                n = int(where[0]) + 1
                eos_hit[i] = True
        emits[i, :n] = True
    return emits, eos_hit


class ServingEngine:
    def __init__(self, cfg, params, engine_cfg: EngineConfig,
                 head_fn: Optional[HeadFn] = None, draft=None):
        """``head_fn(params, hidden, mask)`` — see ``serving.api.HeadFn``:
        called inside the jitted encoder function with the full parameter
        tree, final hidden states (B, S, d_model) and the validity mask
        (B, S); returns the per-request payload. Defaults to hidden states
        (encoder) / generated tokens (decoder). ``draft`` is the
        ``(draft_cfg, draft_params)`` pair speculative decoding proposes
        with (required iff ``spec_decode`` is on)."""
        self.cfg = cfg                    # guarded-by: init
        self.params = params              # guarded-by: init
        self.ec = engine_cfg              # guarded-by: init
        self.head_fn = head_fn            # guarded-by: init
        self.draft_cfg, self.draft_params = draft or (None, None)  # guarded-by: init
        if engine_cfg.weight_quant not in (None, "int8"):
            raise ValueError(f"weight_quant must be None or 'int8', got "
                             f"{engine_cfg.weight_quant!r}")
        validate_kv_quant(engine_cfg.kv_quant)
        if engine_cfg.kv_quant and engine_cfg.mode != "decoder":
            raise ValueError("kv_quant requires mode='decoder' (the KV "
                             "cache only exists on the decode path)")
        if engine_cfg.weight_quant == "int8":
            # one-time at init: the matmul layer classes go int8 (policy in
            # quant/policy.py); everything downstream — warmup, jitted
            # prefill/segments — traces against the quantized tree, so the
            # measured windows stay compile-clean with no extra priming
            self.params = quantize_params(self.params)
        self._weight_bytes = params_bytes(self.params)   # guarded-by: init
        self._q: "queue.Queue[_Request]" = queue.Queue()  # guarded-by: threadsafe
        self._admission = (AdmissionQueue(engine_cfg.max_inflight)  # guarded-by: threadsafe
                           if engine_cfg.max_inflight else None)
        self.latencies: List[float] = []          # guarded-by: worker
        self.batch_sizes: List[int] = []          # guarded-by: worker
        self.timings: List[RequestTiming] = []    # guarded-by: worker — v2 per-phase breakdowns
        self.continuous_active = (        # guarded-by: init
            engine_cfg.mode == "decoder" and engine_cfg.continuous
            and engine_cfg.use_scan_decode and engine_cfg.use_cache_pool)
        # counters metrics() reports at the top level; the worker's phase
        # clock adds its <prefix>_<phase>_ns keys
        self._stats = ({"decode_segments": 0, "joins_mid_flight": 0,  # guarded-by: worker
                        "prefill_batches": 0, "prefill_chunks": 0}
                       if self.continuous_active
                       else {"batch_served": 0, "batch_overlapped": 0})
        self.lane_stats = {}              # guarded-by: worker — per-lane counters
        self._stop = threading.Event()            # guarded-by: threadsafe
        # reentrant: a done-callback attached under the lock can fire
        # synchronously (future cancelled in the attach window) and re-enter
        self._submit_lock = threading.RLock()  # guarded-by: threadsafe — orders submit vs close
        self._overflow = RequestQueue()        # guarded-by: _submit_lock — admission overflow
        self._parked_cancelled = 0             # guarded-by: _submit_lock — phantoms in heap
        self._compiled = {}               # guarded-by: worker
        self._pools = {}                  # guarded-by: worker — bucket -> CachePool
        if engine_cfg.segment_width not in ("adaptive", "fixed"):
            raise ValueError(
                f"segment_width must be 'adaptive' or 'fixed', got "
                f"{engine_cfg.segment_width!r}")
        # the width ladder compacted segments may run at (see scheduler.
        # width_tiers); 'fixed' degenerates to the max_batch-only ladder
        self._tiers = (width_tiers(engine_cfg.max_batch)  # guarded-by: init
                       if engine_cfg.segment_width == "adaptive"
                       else (engine_cfg.max_batch,))
        C = engine_cfg.prefill_chunk
        if self.continuous_active and C is not None:
            if C < 1:
                raise ValueError(f"prefill_chunk must be >= 1, got {C}")
            for b in engine_cfg.pad_buckets:
                # the final chunk is padded up to a multiple of C; those
                # padded positions land in the slot's KV ring and must not
                # wrap past its length (bucket + max_new_tokens), or they
                # would silently overwrite the prompt prefix's KV
                if -(-b // C) * C > b + engine_cfg.max_new_tokens:
                    raise ValueError(
                        f"prefill_chunk={C} rounds bucket {b} prompts up "
                        f"to {-(-b // C) * C} cache positions, past the "
                        f"slot's {b + engine_cfg.max_new_tokens}; pick a "
                        f"chunk dividing the bucket or raise "
                        f"max_new_tokens")
        self._prefix_stores = {}          # guarded-by: worker — bucket -> PrefixStore
        if engine_cfg.prefix_cache:
            if not self.continuous_active:
                raise ValueError(
                    "prefix_cache requires the continuous decoder path "
                    "(mode='decoder', continuous/use_scan_decode/"
                    "use_cache_pool all on)")
            if C is None:
                raise ValueError(
                    "prefix_cache requires prefill_chunk: chunk boundaries "
                    "define the prefix granularity")
            bad = [k for k in cfg.pattern if k not in ("attn", "attn_global")]
            if bad or getattr(cfg, "enc_layers", 0):
                raise ValueError(
                    f"prefix_cache requires a pure global-attention "
                    f"pattern: sliding-window rings and recurrent states "
                    f"cannot be replayed at an absolute KV offset "
                    f"(pattern={cfg.pattern!r})")
        self._draft_pools = {}            # guarded-by: worker — bucket -> draft CachePool
        if engine_cfg.spec_decode:
            if not self.continuous_active:
                raise ValueError(
                    "spec_decode requires the continuous decoder path "
                    "(mode='decoder', continuous/use_scan_decode/"
                    "use_cache_pool all on)")
            if engine_cfg.spec_k < 1:
                raise ValueError(
                    f"spec_k must be >= 1, got {engine_cfg.spec_k}")
            if self.draft_cfg is None or self.draft_params is None:
                raise ValueError(
                    "spec_decode requires draft=(draft_cfg, draft_params) "
                    "at engine construction")
            for role, c in (("target", cfg), ("draft", self.draft_cfg)):
                bad = [k for k in c.pattern
                       if k not in ("attn", "attn_global")]
                if bad or getattr(c, "enc_layers", 0):
                    raise ValueError(
                        f"spec_decode requires a pure global-attention "
                        f"{role} pattern: per-row KV rollback cannot "
                        f"rewind sliding-window rings or recurrent state "
                        f"(pattern={c.pattern!r})")
            if self.draft_cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab_size {self.draft_cfg.vocab_size} != "
                    f"target {cfg.vocab_size}: proposed ids must be "
                    f"scoreable by the target")
        if self.continuous_active:
            for b in engine_cfg.pad_buckets:
                self._lane_stat(b)   # fixed key set: metrics() iterates
                                     # lane_stats without a lock
            from repro.serving.continuous import ContinuousScheduler
            self._scheduler = ContinuousScheduler(self)  # guarded-by: init
            self._clock = self._scheduler.clock  # guarded-by: init
            target = self._scheduler.run
        else:
            self._clock = WorkerClock(self._stats, "batch",  # guarded-by: init
                                      BATCH_PHASES)
            target = self._run
        # window() cursors: list lengths + counter values at the last snap
        self._win_cursor = {"latencies": 0,       # guarded-by: client
                            "batch_sizes": 0, "timings": 0,
                            "stats": self._clock.read(), "lanes": {}}
        self._worker = threading.Thread(target=target, daemon=True)  # guarded-by: init
        self._worker.start()

    # ------------------------------------------------------------- client
    def generate(self, request, sampling: Optional[SamplingParams] = None,
                 *, priority: int = 0,
                 request_id: Optional[str] = None) -> RequestHandle:
        """Submit a typed generation request (decoder mode).

        ``request`` is a ``GenerationRequest`` or a raw token array (then
        ``sampling``/``priority``/``request_id`` build one). Returns a
        ``RequestHandle`` immediately; validation errors (``RequestTooLong``,
        bad sampling params) resolve the handle's future exceptionally
        rather than raising here, so submission never throws mid-burst.
        """
        if self.ec.mode != "decoder":
            raise ValueError("generate() requires mode='decoder'; encoder "
                             "mode serves via submit()")
        if not isinstance(request, GenerationRequest):
            request = GenerationRequest(
                tokens=np.asarray(request, np.int32),
                sampling=sampling or SamplingParams(),
                priority=priority, request_id=request_id)
        fut: Future = Future()
        handle = RequestHandle(request, fut)
        toks = np.asarray(request.tokens, np.int32)
        try:
            if self._stop.is_set():
                raise RuntimeError("engine is closed")
            if toks.ndim != 1 or toks.size < 1:
                # an empty prompt would flow lens - 1 == -1 into the
                # prefill's take_along_axis, wrapping to the last padded
                # position — the first token would sample from garbage
                raise ValueError(
                    f"prompt must be a non-empty 1-D token sequence, got "
                    f"shape {toks.shape}")
            budget = request.sampling.validate(self.ec.max_new_tokens)
            if (request.sampling.temperature > 0
                    and not self.ec.use_scan_decode):
                raise ValueError("sampling (temperature > 0) requires "
                                 "use_scan_decode=True")
            self._bucket(len(toks))
        except Exception as e:  # surfaced through the handle
            fut.set_exception(e)
            return handle
        req = _Request(toks, fut, time.perf_counter(),
                       sampling=request.sampling, budget=budget,
                       priority=request.priority, handle=handle)
        self._submit_req(req)
        return handle

    def submit(self, tokens: np.ndarray) -> Future:
        """v1 shim, kept for the seed API: untyped tokens in, future out.

        Encoder mode: unchanged. Decoder mode: deprecated — delegates to
        ``generate()`` with default (greedy) ``SamplingParams`` and returns
        a future resolving to the bare token array; cancelling the returned
        future does not cancel the underlying request (use the handle API).
        """
        if self.ec.mode == "decoder":
            h = self.generate(tokens)
            out: Future = Future()

            def relay(f):
                if f.cancelled():
                    out.cancel()
                elif f.exception() is not None:
                    out.set_exception(f.exception())
                else:
                    out.set_result(f.result().tokens)

            h.future.add_done_callback(relay)
            return out
        fut: Future = Future()
        toks = np.asarray(tokens, np.int32)
        if self._stop.is_set():
            fut.set_exception(RuntimeError("engine is closed"))
            return fut
        try:
            self._bucket(len(toks))
        except RequestTooLong as e:
            fut.set_exception(e)
            return fut
        self._submit_req(_Request(toks, fut, time.perf_counter()))
        return fut

    def _submit_req(self, req: _Request) -> None:
        """Admission + enqueue, shared by submit() and generate()."""
        if self._admission is not None:
            with self._submit_lock:
                if self._stop.is_set():
                    req.future.set_exception(RuntimeError("engine is closed"))
                    return
                if self._admission.try_acquire():
                    self._enqueue_admitted(req)
                else:
                    # saturated: park without blocking the submitter; a
                    # finishing request's done-callback transfers its slot
                    # to the best-priority parked request. The reported
                    # depth excludes requests cancelled while parked
                    # (they sit in the heap until a pop scans past them,
                    # but wait for nothing): a cancelled future can only
                    # have been parked — running ones refuse cancel — so
                    # a done-callback counts them in O(1) per submit
                    self._overflow.push(req, req.priority)
                    req.future.add_done_callback(self._on_parked_done)
                    self._admission.note_queued(
                        len(self._overflow) - self._parked_cancelled)
            return
        # the lock orders this enqueue against close()'s drain: either the
        # request lands before the drain (and is failed by it) or it sees
        # _stop and is rejected here — it can never be silently stranded
        with self._submit_lock:
            if self._stop.is_set():
                req.future.set_exception(RuntimeError("engine is closed"))
                return
            self._q.put(req)

    def _enqueue_admitted(self, req: _Request) -> None:  # holds: _submit_lock
        """Put an admitted request on the worker queue; its slot is held
        until the future resolves, then handed to the next parked request.
        Caller holds _submit_lock. If the future is already done (a cancel
        won a race), add_done_callback fires synchronously in this thread —
        safe because _submit_lock is reentrant."""
        req.future.add_done_callback(self._on_admitted_done)
        self._q.put(req)

    def _on_parked_done(self, fut) -> None:
        if fut.cancelled():
            with self._submit_lock:
                self._parked_cancelled += 1

    def _drop_parked(self, r) -> bool:  # holds: _submit_lock
        """Pop predicate: discard done (cancelled-while-parked) entries,
        reconciling the phantom counter as they physically leave the heap.
        Caller holds _submit_lock; pop discards a matched entry exactly
        once."""
        if r.future.done():
            if r.future.cancelled():
                self._parked_cancelled -= 1
            return True
        return False

    def _on_admitted_done(self, _fut) -> None:
        with self._submit_lock:
            if not self._stop.is_set():
                # requests cancelled while parked hold no slot: drop them
                nxt = self._overflow.pop(drop=self._drop_parked)
                if nxt is not None:
                    self._admission.admit_transfer(
                        time.perf_counter() - nxt.t_submit)
                    self._enqueue_admitted(nxt)
                    return
            self._admission.release()

    def warmup(self, batch_sizes=None, *, buckets=None, sampled: bool = False,
               timeout: float = 600) -> None:
        """Compile every batch shape a workload can hit, so jit compiles
        land here instead of inside the first measured request.

        Every bucket in ``buckets`` (default: all ``pad_buckets`` — a
        mixed-length workload pays a first-request compile per bucket it
        touches, not just ``pad_buckets[0]``) is primed for every batch
        size in ``batch_sizes`` (default ``1..max_batch``). Encoder and
        batch-at-a-time decoder modes serve one synthetic batch per
        (bucket, size) through the serve path; the continuous decoder
        primes each bucket's prefill-into-slot join sizes, its chunked-
        prefill shapes (when ``prefill_chunk`` is set) and its decode
        segment directly against the bucket's pool — with
        ``segment_width='adaptive'``, the segment is primed per (bucket x
        width tier), plus the compact-gather and scatter-back variants
        each occupancy in ``batch_sizes`` maps to, so tier switches
        mid-serve stay compile-clean — deterministic, unlike
        a burst of real requests whose join sizes depend on timing, and
        without adding request samples to ``metrics()``. It must run
        before serving traffic (it touches the pools the worker uses;
        raises once requests are in flight). ``metrics()['jit_compiles']``
        counts compiled serving variants (engine fns + the shared cache-
        pool helpers); ``window()`` diffs it, so a measured span can
        assert it stayed compile-clean. Encoder / batch-at-a-time warmup
        serves real synthetic batches, which count into the cumulative
        ``metrics()`` — callers measuring afterwards should attribute via
        ``window()``.

        ``sampled=True`` additionally primes the temperature>0 variant of
        every continuous-path shape (prefill, chunk, segments at every
        tier) — sampling keys a separate jit specialization (the top-k
        sort and PRNG enter the graph), so workloads measuring sampled
        traffic need it to stay compile-clean. Off by default: it roughly
        doubles warmup compile work and greedy-only callers never hit
        those variants.
        """
        buckets = tuple(buckets) if buckets else self.ec.pad_buckets
        sizes = sorted(set(batch_sizes or range(1, self.ec.max_batch + 1)))
        if self.continuous_active:
            self._warmup_continuous(buckets, sizes, sampled=sampled)
            return
        for bucket in buckets:
            tok = np.ones(bucket, np.int32)    # full width -> this bucket
            for b in sizes:
                self._serve_batch([
                    _Request(tok.copy(), Future(), time.perf_counter())
                    for _ in range(b)])

    def _warmup_continuous(self, buckets, sizes, sampled=False) -> None:
        """Prime the continuous scheduler's jitted shapes per bucket:
        prefill-into-slot per join size (gather acquire, as the scheduler
        uses), prefill chunks per fill-batch size, the full-slot decode
        segment (donating and swapping the pool caches exactly as a live
        segment does), and — under ``segment_width='adaptive'`` — one
        compact-gather -> tier-width segment -> scatter-back cycle per
        occupancy in ``sizes``, compiling exactly the variants those
        occupancies map to (gather and segment specialize per tier,
        scatter-back per (tier, occupancy)). With the prefix cache on,
        the store->slot load per hit-batch size and the store's
        truncating insert copy are primed too (suffix prefill reuses the
        chunk shapes). ``sampled=True`` repeats prefill/chunk/segments
        with temperature>0 arrays — the sampling jit variants.

        Beyond compiles, this also fronts the first-traffic allocation
        work the lazy paths used to pay mid-serve (the ~20x first-request
        warm-in, invisible to ``jit_compiles``): each bucket's chunked-
        prefill staging pool and prefix store are created (device
        allocations) here, and inputs are staged host-side first so the
        first measured request pays no first-transfer setup either."""
        if (self.latencies or not self._q.empty()
                or any(l.busy for l in self._scheduler.lanes.values())):
            # the worker would race these direct pool mutations (both
            # sides donate pool.caches); the old request-burst warmup was
            # traffic-safe, so fail loudly rather than corrupt quietly
            raise RuntimeError("warmup() must run before serving traffic")
        n = self.ec.max_batch
        chunk = self.ec.prefill_chunk

        def svariants(b):
            out = [(None, None, None)]
            if sampled:
                out.append((jnp.asarray(np.full(b, 0.5, np.float32)),
                            jnp.asarray(np.zeros(b, np.int32)),
                            jnp.asarray(np.zeros(b, np.int32))))
            return out

        for bucket in buckets:
            pool = self._get_pool(bucket)
            spec = self.ec.spec_decode
            dpool = self._get_draft_pool(bucket) if spec else None
            if spec:       # draft device pool allocs front-loaded too
                jax.block_until_ready(jax.tree.leaves(dpool.caches)[0])
            chunked = chunk is not None and bucket > chunk
            if chunked:
                # create the fill path's staging pool now — first-traffic
                # device allocs otherwise land inside the first request
                lane = self._scheduler.lanes[bucket]
                jax.block_until_ready(lane.get_staging(self).caches)
            store = self._prefix_store(bucket)
            for b in sizes:
                for sargs in svariants(b):
                    slots, view = pool.acquire(
                        [f"warm{bucket}.{i}" for i in range(b)], gather=True)
                    toks = jnp.asarray(np.zeros((b, bucket), np.int32))
                    lens = jnp.full((b,), min(4, bucket), jnp.int32)
                    tok, caches = self._prefill_fn()(
                        self.params, toks, lens, view, *sargs)
                    if spec:
                        # the live spec install path truncates the padded
                        # prefill tail in the same fused scatter (verify
                        # chunks attend the whole ring, so positions past
                        # a row's frontier must hold the empty sentinel)
                        pool.scatter_rollback(slots, caches,
                                              [min(4, bucket)] * b)
                    else:
                        pool.write_back(slots, caches)
                    jax.block_until_ready(tok)
                    pool.release_many(slots)
                    if chunked:
                        slots = pool.assign_many(
                            [f"warmc{bucket}.{i}" for i in range(b)])
                        # the fill path gathers fragmented staging slots via
                        # _take_slots; batch_view on this fresh pool would
                        # take the slice path and leave the gather uncompiled
                        view = _take_slots(pool.caches,
                                           jnp.asarray(slots, jnp.int32))
                        ctok, caches = self._chunk_fn()(
                            self.params,
                            jnp.asarray(np.zeros((b, chunk), np.int32)),
                            jnp.zeros((b,), jnp.int32),
                            jnp.full((b,), chunk, jnp.int32), view,
                            *sargs)
                        pool.write_back(slots, caches)
                        if spec:
                            # mid-fill chunks write_back to staging (primed
                            # above — same leaf shapes); the fill-complete
                            # install additionally rolls back, so prime
                            # that variant too
                            pool.scatter_rollback(
                                slots, pool.batch_view(slots, gather=True),
                                [chunk] * b)
                        jax.block_until_ready(ctok)
                        pool.release_many(slots)
                if spec:
                    # draft whole-prompt prefill + rollback per join size,
                    # driven with the module helpers at lane slot indices
                    # exactly as the scheduler does (no claim/release)
                    sl = jnp.asarray(list(range(b)), jnp.int32)
                    dpool.caches, dview = kvcache._reset_and_view(
                        dpool.caches, dpool._template, sl)
                    dcaches = self._draft_prefill_fn()(
                        self.draft_params,
                        jnp.asarray(np.zeros((b, bucket), np.int32)), dview)
                    dpool.caches = kvcache._scatter_rollback(
                        dpool.caches, dcaches, sl,
                        jnp.full((b,), min(4, bucket), jnp.int32))
                    jax.block_until_ready(jax.tree.leaves(dpool.caches)[0])
                if store is not None:
                    # hit path: claimed (unreset) slots + fused store->lane
                    # copy, per hit-batch size; the suffix chunk call and
                    # write_back reuse shapes primed above
                    slots = pool.claim(
                        [f"warmp{bucket}.{i}" for i in range(b)])
                    pool.caches = kvcache._load_slots(
                        pool.caches, store.pool.caches,
                        jnp.asarray(slots, jnp.int32),
                        jnp.asarray(np.zeros(b, np.int32)))
                    jax.block_until_ready(jax.tree.leaves(pool.caches)[0])
                    pool.release_many(slots)
            if store is not None:    # insert-on-complete's truncating copy
                store.pool.caches = kvcache._store_prefix(
                    store.pool.caches, pool.caches,
                    jnp.zeros(1, jnp.int32), jnp.zeros(1, jnp.int32),
                    jnp.asarray(chunk, jnp.int32))
                jax.block_until_ready(
                    jax.tree.leaves(store.pool.caches)[0])
            if spec:
                # spec lanes never run decode segments — every turn is a
                # compacted draft-and-verify round (even 'fixed' runs the
                # gather path at width max_batch), so prime the round per
                # width tier plus the (occupancy, width) rollback variants
                for occ in sizes:
                    width = pick_tier(occ, self._tiers)
                    for sargs_w in svariants(width):
                        slots = list(range(occ))
                        _, view = pool.compact_view(slots, width)
                        _, dview = dpool.compact_view(slots, width)
                        _, verify, seg, dseg = self._spec_round_fn()(
                            self.params, self.draft_params,
                            jnp.zeros((width, 1), jnp.int32),
                            jnp.zeros((width, 1), jnp.int32),
                            view, dview, *sargs_w)
                        pool.scatter_rollback(slots, seg, [1] * occ)
                        dpool.scatter_rollback(slots, dseg, [1] * occ)
                        jax.block_until_ready(verify)
                continue
            for sargs_n in svariants(n):
                toks, _, _, caches = self._segment_fn()(
                    self.params, jnp.zeros((n, 1), jnp.int32),
                    jnp.zeros((n, 1), jnp.int32), pool.caches,
                    jnp.zeros((n,), bool), jnp.ones((n,), jnp.int32),
                    jnp.full((n,), -1, jnp.int32), *sargs_n)
                pool.caches = caches
                jax.block_until_ready(toks)
            for occ in sizes:        # compacted segments per width tier
                width = pick_tier(occ, self._tiers)
                if width >= n:       # occupancy maps to the full segment
                    continue
                for sargs_w in svariants(width):
                    slots = list(range(occ))
                    _, view = pool.compact_view(slots, width)
                    toks, _, _, seg = self._segment_fn()(
                        self.params, jnp.zeros((width, 1), jnp.int32),
                        jnp.zeros((width, 1), jnp.int32), view,
                        jnp.zeros((width,), bool),
                        jnp.ones((width,), jnp.int32),
                        jnp.full((width,), -1, jnp.int32), *sargs_w)
                    pool.scatter_back(slots, seg)
                    jax.block_until_ready(toks)

    def discard_samples(self) -> None:
        """Drop the accumulated per-request samples (wall latencies, batch
        sizes, phase timings) and re-sync the ``window()`` cursor — the
        one way to discard warmup traffic so later ``metrics()`` /
        ``window()`` spans cover only measured requests. Counters
        (segments, joins, compiles, lane stats) are cumulative by design
        and are not touched; attribute those via ``window()``."""
        self.latencies.clear()
        self.batch_sizes.clear()
        self.timings.clear()
        self.window()

    def close(self):
        self._stop.set()
        self._worker.join(timeout=5)
        # fail everything still parked or queued: resolves client futures
        # (and, via the done-callbacks, frees any held admission slots)
        with self._submit_lock:
            pending = self._overflow.drain()
        while True:
            try:
                pending.append(self._q.get_nowait())
            except queue.Empty:
                break
        for req in pending:
            if not req.future.done():
                req.future.set_exception(RuntimeError("engine is closed"))

    # ------------------------------------------------------------- server
    def _bucket(self, n: int) -> int:
        for b in self.ec.pad_buckets:
            if n <= b:
                return b
        raise RequestTooLong(
            f"request of {n} tokens exceeds the largest pad bucket "
            f"({self.ec.pad_buckets[-1]}); split the request or configure "
            f"larger pad_buckets")

    def _encoder_fn(self, bucket: int):  # holds: worker
        if ("enc", bucket) not in self._compiled:
            def enc_forward(params, tokens, mask):
                pos = jnp.broadcast_to(
                    jnp.arange(tokens.shape[1], dtype=jnp.int32),
                    tokens.shape)
                # GECToR-style param trees nest the encoder under 'encoder'
                enc_params = params.get("encoder", params)
                hid, _, _ = forward(self.cfg, enc_params, tokens=tokens,
                                    positions=pos, causal=False,
                                    return_hidden=True)
                if self.head_fn is not None:
                    return self.head_fn(params, hid, mask)
                return hid
            self._compiled[("enc", bucket)] = jax.jit(enc_forward)
        return self._compiled[("enc", bucket)]

    # --------------------------------------------------- decoder hot path
    def _sampling_arrays(self, reqs: List[_Request]):  # holds: worker
        """Per-row sampling/stop arrays from a request batch; legacy
        requests (no SamplingParams) default to greedy full-budget rows."""
        T = self.ec.max_new_tokens
        B = len(reqs)
        temp = np.zeros(B, np.float32)
        topk = np.zeros(B, np.int32)
        seed = np.zeros(B, np.int32)
        eos = np.full(B, -1, np.int32)
        budget = np.full(B, T, np.int32)
        any_sample = False
        for i, r in enumerate(reqs):
            sp = r.sampling
            if sp is None:
                continue
            budget[i] = r.budget
            if sp.eos_id is not None:
                eos[i] = sp.eos_id
            if sp.temperature > 0:
                any_sample = True
                temp[i] = sp.temperature
                topk[i] = sp.top_k or 0
                seed[i] = sp.seed
        return temp, topk, seed, eos, budget, any_sample

    def _decode_scan_fn(self):  # holds: worker
        """One fused jitted function: prefill -> per-row last-position
        first-token selection -> ``decode_segment`` over the remaining
        steps. jit specializes it per (batch, bucket) shape — and per
        sampling-on/off (greedy batches pass None and keep the sort/PRNG
        out of the graph); one dispatch serves the whole batch."""
        if "dec_scan" not in self._compiled:
            T = self.ec.max_new_tokens

            def dec_scan(params, toks, lens, caches, temp, topk, seed):
                logits, caches, _ = forward(self.cfg, params, tokens=toks,
                                            caches=caches, mode="full")
                # first generated token: per-row logits at the row's real
                # last position (padded rows must not sample from garbage)
                last = jnp.take_along_axis(
                    logits, (lens - 1)[:, None, None], axis=1)[:, 0]
                tok = sample_logits(last, temperature=temp, top_k=topk,
                                    seed=seed, positions=lens)[:, None]
                if T == 1:
                    return tok, caches
                rest, _, _, caches = decode_segment(
                    self.cfg, params, tok, lens[:, None], caches,
                    n_steps=T - 1, temperature=temp, top_k=topk, seed=seed)
                return jnp.concatenate([tok, rest], axis=1), caches

            self._compiled["dec_scan"] = jax.jit(dec_scan)
        return self._compiled["dec_scan"]

    def _decode_fns(self):  # holds: worker
        """Legacy per-token path (kept for A/B benchmarks + equivalence
        tests; ``use_scan_decode=False`` selects it; greedy only).
        unroll_periods=False reproduces the seed's scanned-period step
        structure exactly."""
        if "dec" not in self._compiled:
            def dec_prefill_legacy(p, t, c):
                return forward(self.cfg, p, tokens=t, caches=c, mode="full")

            def dec_step_legacy(p, t, pos, c):
                return decode_step(self.cfg, p, t, pos, c,
                                   unroll_periods=False)

            self._compiled["dec"] = (jax.jit(dec_prefill_legacy),
                                     jax.jit(dec_step_legacy))
        return self._compiled["dec"]

    def _prefill_fn(self):  # holds: worker
        """Continuous-batching prefill-into-slot: fill the rows' pool-slot
        caches and select each row's first token. jit specializes per
        (n_new, bucket) shape."""
        if "cont_prefill" not in self._compiled:
            def dec_prefill(params, toks, lens, caches, temp, topk, seed):
                logits, caches, _ = forward(self.cfg, params, tokens=toks,
                                            caches=caches, mode="full")
                last = jnp.take_along_axis(
                    logits, (lens - 1)[:, None, None], axis=1)[:, 0]
                tok = sample_logits(last, temperature=temp, top_k=topk,
                                    seed=seed, positions=lens)
                return tok, caches
            self._compiled["cont_prefill"] = jax.jit(dec_prefill)
        return self._compiled["cont_prefill"]

    def _chunk_fn(self):  # holds: worker
        """Chunked-prefill step: run one prompt chunk against the rows'
        staged caches (``models.prefill_chunk``) and select each row's
        next-token candidate at its last valid chunk position — only
        meaningful for rows whose prompt completes this chunk; the
        scheduler ignores it for the rest. ``start`` is each row's
        absolute chunk offset, ``nvalid`` its real tokens this chunk (all
        chunks except a prompt's last are completely filled). jit
        specializes per (n_fills, chunk_len) shape."""
        if "cont_chunk" not in self._compiled:
            def dec_chunk(params, toks, start, nvalid, caches, temp, topk,
                          seed):
                C = toks.shape[1]
                positions = start[:, None] + jnp.arange(C, dtype=jnp.int32)
                logits, caches, _ = prefill_chunk(
                    self.cfg, params, toks, positions, caches)
                last = jnp.take_along_axis(
                    logits, (nvalid - 1)[:, None, None], axis=1)[:, 0]
                # PRNG counter = the position the sampled token occupies
                # (the prompt length) — identical to whole-prompt prefill
                tok = sample_logits(last, temperature=temp, top_k=topk,
                                    seed=seed, positions=start + nvalid)
                return tok, caches
            self._compiled["cont_chunk"] = jax.jit(dec_chunk)
        return self._compiled["cont_chunk"]

    def _segment_fn(self):  # holds: worker
        """One jitted decode segment over the full slot batch (the
        continuous scheduler's step core). The pool caches are donated:
        the segment updates them in place and the scheduler swaps in the
        returned tree."""
        if "cont_segment" not in self._compiled:
            seg = self.ec.decode_segment

            def dec_segment(params, tok, pos, caches, active, budget, eos,
                            temp, topk, seed):
                return decode_segment(self.cfg, params, tok, pos, caches,
                                      n_steps=seg, active=active,
                                      budget=budget, eos_id=eos,
                                      temperature=temp, top_k=topk,
                                      seed=seed)

            self._compiled["cont_segment"] = jax.jit(dec_segment,
                                                     donate_argnums=3)
        return self._compiled["cont_segment"]

    def _draft_prefill_fn(self):  # holds: worker
        """Whole-prompt prefill into the draft pool's slot caches. No
        token selection — the round's first draft step samples from the
        prompt's last position — and ``return_hidden`` keeps the draft
        lm_head out of the graph. jit specializes per (n_new, bucket)."""
        if "spec_dprefill" not in self._compiled:
            def spec_dprefill(dparams, toks, caches):
                _, caches, _ = forward(self.draft_cfg, dparams, tokens=toks,
                                       caches=caches, mode="full",
                                       return_hidden=True)
                return caches
            self._compiled["spec_dprefill"] = jax.jit(spec_dprefill)
        return self._compiled["spec_dprefill"]

    def _spec_round_fn(self):  # holds: worker
        """One fused draft-and-verify round (``models.spec_round``): spec_k
        draft decode steps + one target verify chunk, one dispatch. Both
        cache views are donated — the scheduler scatter-rollbacks the
        returned trees to each row's commit boundary."""
        if "spec_round" not in self._compiled:
            k = self.ec.spec_k

            def spec_round(params, dparams, tok, pos, caches, dcaches,
                           temp, topk, seed):
                return _spec_round(self.cfg, params, self.draft_cfg,
                                   dparams, tok, pos, caches, dcaches, k=k,
                                   temperature=temp, top_k=topk, seed=seed)

            self._compiled["spec_round"] = jax.jit(spec_round,
                                                   donate_argnums=(4, 5))
        return self._compiled["spec_round"]

    def _slot_len(self, bucket: int) -> int:  # holds: worker
        """KV ring length for the bucket's slots. Spec-decode rounds write
        a verify chunk of spec_k + 1 positions starting at the row's
        frontier, so a row one token short of its budget still reaches
        position bucket + max_new_tokens - 1 + spec_k — without the
        headroom the chunk would wrap the ring and overwrite the prompt's
        KV (the over-provisioned tail is rolled back, never committed)."""
        return (bucket + self.ec.max_new_tokens
                + (self.ec.spec_k if self.ec.spec_decode else 0))

    def _get_pool(self, bucket: int) -> CachePool:  # holds: worker
        pool = self._pools.get(bucket)
        if pool is None:
            pool = CachePool(self.cfg, self.ec.max_batch,
                             self._slot_len(bucket),
                             dtype=jnp.float32,
                             kv_quant=self.ec.kv_quant)
            self._pools[bucket] = pool
            if self.continuous_active:
                self._lane_stat(bucket)["kv_bytes"] = int(
                    sum(x.nbytes for x in jax.tree.leaves(pool.caches)))
        return pool

    def _get_draft_pool(self, bucket: int) -> CachePool:  # holds: worker
        """The bucket's draft-model KV pool. Slot i mirrors lane slot i
        (same indices, same ring length), but the pool bypasses slot
        bookkeeping entirely — the scheduler drives it with the module
        helpers at the lane's slot indices, so claim/release state lives
        only on the lane pool. Draft KV stays float even under kv_quant:
        its logits only gate proposals (never committed tokens), and the
        small draft's cache is not the residency bottleneck."""
        pool = self._draft_pools.get(bucket)
        if pool is None:
            pool = CachePool(self.draft_cfg, self.ec.max_batch,
                             self._slot_len(bucket), dtype=jnp.float32)
            self._draft_pools[bucket] = pool
        return pool

    def _prefix_store(self, bucket: int):  # holds: worker
        """The bucket's prefix store, or None when the prefix cache is off
        or the bucket cannot hold a full chunk-aligned prefix (a stored
        prefix is strictly shorter than the prompt, so buckets <= chunk
        can never match). Store slots share the lane pool's max_len, so
        loads are shape-identical full-slot copies."""
        if not self.ec.prefix_cache:
            return None
        C = self.ec.prefill_chunk
        if bucket <= C:
            return None
        store = self._prefix_stores.get(bucket)
        if store is None:
            store = kvcache.PrefixStore(
                self.cfg, self.ec.max_batch,
                self._slot_len(bucket), C,
                capacity_bytes=self.ec.prefix_cache_bytes,
                dtype=jnp.float32, kv_quant=self.ec.kv_quant)
            self._prefix_stores[bucket] = store
        return store

    def _acquire_caches(self, B: int, bucket: int):  # holds: worker
        """Batch-sized decode caches: pooled slots (reset-on-assign, no
        per-batch allocation sweep) or a fresh make_caches tree."""
        if not self.ec.use_cache_pool:
            L = bucket + self.ec.max_new_tokens
            return make_caches(self.cfg, B, L, dtype=jnp.float32,
                               kv_quant=self.ec.kv_quant), None
        pool = self._get_pool(bucket)
        slots, view = pool.acquire([f"b{bucket}.{i}" for i in range(B)])
        return view, (pool, slots)

    @staticmethod
    def _release_caches(handle):
        if handle is not None:
            pool, slots = handle
            pool.release_many(slots)

    def _serve_decoder(self, toks, lens, bucket, reqs):  # holds: worker
        """Batch-at-a-time decode. Returns (gen (B, T), emits (B, T) bool,
        eos_hit (B,) bool) — emits marks each row's kept prefix (its budget
        / first-eos trim)."""
        B = len(lens)
        temp, topk, seed, eos, budget, any_sample = \
            self._sampling_arrays(reqs)
        lens_a = jnp.asarray(np.array(lens, np.int32))
        caches, handle = self._acquire_caches(B, bucket)
        try:
            if self.ec.use_scan_decode:
                sargs = ((jnp.asarray(temp), jnp.asarray(topk),
                          jnp.asarray(seed)) if any_sample
                         else (None, None, None))
                gen, _ = self._decode_scan_fn()(
                    self.params, jnp.asarray(toks), lens_a, caches, *sargs)
                gen = np.asarray(gen)
            else:
                if any_sample:
                    raise ValueError("sampling requires use_scan_decode")
                prefill_fn, step_fn = self._decode_fns()
                logits, caches, _ = prefill_fn(self.params,
                                               jnp.asarray(toks), caches)
                last = jnp.take_along_axis(
                    logits, (lens_a - 1)[:, None, None], axis=1)
                tok = jnp.argmax(last[:, 0], axis=-1)[:, None]
                tok = tok.astype(jnp.int32)
                outs = [np.asarray(tok)]
                pos = lens_a[:, None] - 1
                for _ in range(self.ec.max_new_tokens - 1):
                    pos = pos + 1
                    logits, caches, _ = step_fn(self.params, tok, pos, caches)
                    tok = jnp.argmax(logits[:, -1:], axis=-1)
                    tok = tok.astype(jnp.int32)
                    outs.append(np.asarray(tok))
                gen = np.concatenate(outs, axis=1)
            emits, eos_hit = _trim_host(gen, eos, budget)
            return gen, emits, eos_hit
        finally:
            self._release_caches(handle)

    def _serve_batch(self, reqs: List[_Request]):  # holds: worker
        # only the worker moves its clock and counts its batches; a batch
        # served on another thread (warm-up) times its phases on a
        # throwaway clock
        on_worker = threading.current_thread() is self._worker
        clock = (self._clock if on_worker
                 else WorkerClock({}, "batch", BATCH_PHASES))
        if self.ec.mode == "encoder":
            launched = self._launch_batch(reqs, clock, on_worker)
            if launched is not None:
                self._finish_batch(launched, clock)
            return
        packed = self._pack(reqs, clock)
        if packed is None:
            return
        reqs, lens, bucket, toks, _ = packed
        t_serve = time.perf_counter()
        gen, emits, eos_hit = self._serve_decoder(toks, lens, bucket, reqs)
        t_done = time.perf_counter()
        with clock.phase("fanout"):
            timings = []
            for r in reqs:
                timing = RequestTiming(queue_s=t_serve - r.t_submit,
                                       prefill_s=0.0,
                                       decode_s=t_done - t_serve)
                timings.append(timing)
                if r.handle is not None:
                    self.timings.append(timing)
            self._record_batch(reqs, on_worker)
            for i, r in enumerate(reqs):
                if r.handle is None:    # legacy raw-batch caller
                    r.future.set_result(gen[i])
                    continue
                row = np.asarray(gen[i][emits[i]], np.int32)
                if r.handle.cancel_requested:  # cancel landed mid-serve
                    reason = FINISH_CANCELLED
                else:
                    reason = FINISH_EOS if eos_hit[i] else FINISH_LENGTH
                r.handle._push(row)
                r.future.set_result(GenerationResult(
                    tokens=row, finish_reason=reason, timing=timings[i],
                    request_id=r.handle.request.request_id))

    def _pack(self, reqs: List[_Request], clock):  # holds: worker
        """Claim each future and pad the batch to its bucket: ``(reqs,
        lens, bucket, toks, mask)``, or None when every request was
        cancelled."""
        # claim each future (concurrent.futures protocol): a client-side
        # cancel() that won between enqueue and here drops the request
        # instead of poisoning set_result for the whole batch
        reqs = [r for r in reqs if r.future.set_running_or_notify_cancel()]
        if not reqs:
            return None
        with clock.phase("pack"):
            lens = [len(r.tokens) for r in reqs]
            bucket = self._bucket(max(lens))
            toks = np.zeros((len(reqs), bucket), np.int32)
            mask = np.zeros((len(reqs), bucket), bool)
            for i, r in enumerate(reqs):
                toks[i, :len(r.tokens)] = r.tokens
                mask[i, :len(r.tokens)] = True
        return reqs, lens, bucket, toks, mask

    def _launch_batch(self, reqs: List[_Request], clock,  # holds: worker
                      on_worker: bool) -> Optional[_Launched]:
        """Encoder: pack and dispatch ``enc_forward``, and start each
        output's copy to the host, which then runs as soon as the forward
        ends; ``_finish_batch`` resolves the futures."""
        packed = self._pack(reqs, clock)
        if packed is None:
            return None
        reqs, _, bucket, toks, mask = packed
        with clock.phase("dispatch"):
            out = self._encoder_fn(bucket)(
                self.params, jnp.asarray(toks), jnp.asarray(mask))
            for leaf in jax.tree.leaves(out):
                leaf.copy_to_host_async()
        return _Launched(reqs, out, on_worker)

    def _finish_batch(self, b: _Launched, clock) -> None:  # holds: worker
        with clock.phase("device"):
            jax.block_until_ready(b.out)
        with clock.phase("copy"):
            out = jax.device_get(b.out)
            # keep these host buffers mapped once freed, so the next
            # batch's copy faults in no page
            hostmem.keep(max((x.nbytes for x in jax.tree.leaves(out)),
                             default=0))
        with clock.phase("fanout"):
            # record samples BEFORE resolving futures: a client whose
            # .result() returns must find its sample in metrics()/window()
            self._record_batch(b.reqs, b.on_worker)
            for i, r in enumerate(b.reqs):
                r.future.set_result(jax.tree.map(lambda x: x[i], out))

    def _record_batch(self, reqs: List[_Request],  # holds: worker
                      on_worker: bool) -> None:
        now = time.perf_counter()
        if on_worker:
            self._stats["batch_served"] += 1
        self.batch_sizes.append(len(reqs))
        for r in reqs:
            self.latencies.append(now - r.t_submit)

    def _next_batch(self, block: bool):  # holds: worker
        """The next batch off the queue; None when no request came.
        ``block`` waits up to 50 ms for a first request, then fills the
        batch within ``batch_window_ms`` of it. Without ``block`` a batch
        is in flight, and finishing it is the wait: only what is queued
        already is taken, up to ``max_batch``."""
        clock = self._clock
        if not block:
            batch = []
            with clock.phase("collect"):
                while len(batch) < self.ec.max_batch:
                    try:
                        batch.append(self._q.get_nowait())
                    except queue.Empty:
                        break
            return batch or None
        try:
            with clock.phase("wait"):
                first = self._q.get(timeout=0.05)
        except queue.Empty:
            return None
        batch = [first]
        with clock.phase("collect"):
            deadline = time.perf_counter() + self.ec.batch_window_ms / 1e3
            while len(batch) < self.ec.max_batch:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    break
                try:
                    batch.append(self._q.get(timeout=remaining))
                except queue.Empty:
                    break
        return batch

    @staticmethod
    def _fail(reqs: List[_Request], e: Exception) -> None:
        for r in reqs:
            if not r.future.done():
                r.future.set_exception(e)

    def _run(self):  # holds: worker
        if self.ec.mode == "encoder":
            self._run_encoder()
            return
        while not self._stop.is_set():
            batch = self._next_batch(block=True)
            if batch is None:
                continue
            try:
                self._serve_batch(batch)
            except Exception as e:  # pragma: no cover - surfaced to client
                self._fail(batch, e)

    def _run_encoder(self):  # holds: worker
        """Two-deep pipeline: batch k+1 is taken from what is queued,
        packed and dispatched before batch k is finished, so k's copy to
        the host runs beside k+1's dispatch and forward. With nothing
        queued, the batch in flight is finished rather than kept waiting
        for company. Futures resolve in launch order; a batch that fails
        fails only its own."""
        clock = self._clock
        inflight = None

        def finish(b):
            try:
                self._finish_batch(b, clock)
            except Exception as e:  # surfaced to the batch's clients
                self._fail(b.reqs, e)

        try:
            while not self._stop.is_set():
                batch = self._next_batch(block=inflight is None)
                if batch is None:
                    if inflight is not None:
                        finish(inflight)
                        inflight = None
                    continue
                try:
                    launched = self._launch_batch(batch, clock, True)
                except Exception as e:  # surfaced to the batch's clients
                    self._fail(batch, e)
                    launched = None
                if inflight is not None:
                    if launched is not None:
                        self._stats["batch_overlapped"] += 1
                    finish(inflight)
                inflight = launched
        finally:
            if inflight is not None:
                finish(inflight)

    # ------------------------------------------------------------ metrics
    def _lane_stat(self, bucket: int) -> dict:  # holds: worker
        """Per-lane counters (scheduler-side accumulation point)."""
        stat = self.lane_stats.get(bucket)
        if stat is None:
            stat = self.lane_stats[bucket] = {
                "decode_segments": 0, "occupancy_sum": 0, "joins": 0,
                "prefill_chunks": 0, "compact_segments": 0,
                "prefix_hits": 0, "prefix_misses": 0,
                "prefix_hit_tokens": 0, "prefix_inserts": 0,
                "prefix_evictions": 0,
                "spec_rounds": 0,        # draft-and-verify rounds run
                "spec_proposed": 0,      # draft tokens offered (occ * k)
                "spec_accepted": 0,      # draft tokens the target agreed on

                "prefix_bytes": 0,   # gauges (see _LANE_GAUGES), not counters
                "kv_bytes": 0,       # lane pool KV residency (scales incl.)
                # segment width -> segments run at it. Every tier is
                # pre-created (like the outer key set) so the worker only
                # mutates values — metrics() iterates these dicts from
                # client threads without a lock; a lazily inserted key
                # would fault that iteration. Zero counts are dropped
                # from the reported view.
                "tier_hist": {w: 0 for w in self._tiers}}
        return stat

    def _jit_compiles(self) -> int:
        """Compiled specializations across the serving path's jitted
        functions — a counter measured spans can diff (via ``window()``)
        to assert a workload hit only warmed shapes. Includes the
        module-level cache-pool helpers (reset/gather/scatter): they are
        shared process-wide, but the window diff only surfaces compiles
        that happened during the span, which is the quantity a
        single-engine measurement cares about."""
        n = 0
        # snapshot: the worker inserts newly built fns concurrently
        pool_fns = (kvcache._reset_slots, kvcache._reset_and_view,
                    kvcache._reset_and_view_run, kvcache._take_slots,
                    kvcache._write_slots, kvcache._scatter_prefix,
                    kvcache._load_slots, kvcache._store_prefix,
                    kvcache._scatter_rollback)
        for fn in list(self._compiled.values()) + list(pool_fns):
            fns = fn if isinstance(fn, tuple) else (fn,)
            for f in fns:
                size = getattr(f, "_cache_size", None)
                if callable(size):
                    n += size()
        return n

    # lane stats reported as current values, not window-diffed deltas
    _LANE_GAUGES = frozenset({"prefix_bytes", "kv_bytes"})

    @classmethod
    def _lane_view(cls, now: dict, prev: Optional[dict] = None) -> dict:
        """Lane counter dicts (optionally diffed against a window cursor)
        with the occupancy mean derived per span. Dict-valued counters
        (the segment-width ``tier_hist``) diff per key, dropping keys that
        did not move — a window's histogram covers only its span. Gauges
        (``prefix_bytes``) pass through undiffed: a window reports the
        store's current residency, not its movement."""
        out = {}
        for bucket, stat in now.items():
            base = (prev or {}).get(bucket, {})
            d = {}
            for k, v in stat.items():
                if isinstance(v, dict):
                    sub = base.get(k, {})
                    d[k] = {w: c - sub.get(w, 0) for w, c in v.items()
                            if c - sub.get(w, 0)}
                elif k in cls._LANE_GAUGES:
                    d[k] = v
                else:
                    d[k] = v - base.get(k, 0)
            segs = d.get("decode_segments", 0)
            d["occupancy_mean"] = (d.pop("occupancy_sum", 0) / segs
                                   if segs else 0.0)
            prop = d.get("spec_proposed", 0)
            d["spec_accept_rate"] = (d.get("spec_accepted", 0) / prop
                                     if prop else 0.0)
            out[bucket] = d
        return out

    def _aggregate(self, latencies, batch_sizes, timings, stats) -> dict:
        """Reduce one span of serving samples to the metrics dict shape."""
        n = len(latencies)
        m = {"requests": n}
        if n:
            lat = np.array(latencies)
            m.update(latency_mean_s=float(lat.mean()),
                     latency_p50_s=float(np.percentile(lat, 50)),
                     latency_p95_s=float(np.percentile(lat, 95)))
        else:
            m.update(latency_mean_s=None, latency_p50_s=None,
                     latency_p95_s=None)
        m["batch_size_mean"] = (float(np.mean(batch_sizes))
                                if batch_sizes else 0.0)
        if timings:
            m["prefill_mean_s"] = float(
                np.mean([t.prefill_s for t in timings]))
            m["decode_mean_s"] = float(
                np.mean([t.decode_s for t in timings]))
        if self.continuous_active:
            # batch_sizes holds per-segment occupancy in continuous mode
            m["batch_occupancy_mean"] = m["batch_size_mean"]
        m.update(stats)
        return m

    def metrics(self) -> dict:
        """Cumulative serving stats since engine start. With no completed
        requests the latency percentiles are None (never fabricated from a
        zero sample). ``'jit_compiles'`` counts compiled engine
        specializations so far. The worker's phase clock (``WorkerClock``)
        reports integer nanoseconds at the top level, the phase in progress
        included: ``batch_<phase>_ns`` for the batch worker, with
        ``batch_served`` and ``batch_overlapped`` (the encoder batches it
        launched while another was in flight), ``cont_<phase>_ns`` for the
        continuous scheduler. Continuous engines additionally report
        per-lane counters under ``'lanes'`` (bucket -> segments / occupancy
        mean / joins / prefill chunks / compacted-segment count /
        ``tier_hist``, the histogram of decode-segment widths the lane
        actually ran). ``window()`` gives the same shape for the span since
        the previous ``window()`` call."""
        m = self._aggregate(self.latencies, self.batch_sizes, self.timings,
                            self._clock.read())
        m["weight_bytes"] = self._weight_bytes
        m["jit_compiles"] = self._jit_compiles()
        if self.continuous_active:
            m["lanes"] = self._lane_view(self.lane_stats)
        if self._admission is not None:
            adm = self._admission.snapshot()   # consistent read under _lock
            m["admission_peak_queue"] = adm.queued_peak
            m["admission_wait_total_s"] = adm.wait_total_s
        return m

    def window(self) -> dict:
        """Snapshot-style metrics: everything since the previous
        ``window()`` call (or engine start), then reset the window. Lets
        the experiment runner attribute occupancy/join/segment counters to
        one experiment window instead of cumulative totals. The worker
        appends samples, so a concurrent append mid-call only shifts a
        sample into the next window — never loses it. A cursor beyond the
        current length means the caller cleared the sample lists
        (``run_ladder(warmup=True)`` does): that window restarts at the
        clear instead of silently skipping post-clear samples."""
        cur = self._win_cursor
        i_lat, i_bs, i_tim = (len(self.latencies), len(self.batch_sizes),
                              len(self.timings))
        stats_now = self._clock.read()
        # per-key copy: tier_hist is a nested dict the scheduler mutates
        lanes_now = {b: {k: (dict(v) if isinstance(v, dict) else v)
                         for k, v in s.items()}
                     for b, s in self.lane_stats.items()}

        def span(lst, start, stop):
            return lst[start if start <= stop else 0:stop]

        m = self._aggregate(span(self.latencies, cur["latencies"], i_lat),
                            span(self.batch_sizes, cur["batch_sizes"], i_bs),
                            span(self.timings, cur["timings"], i_tim),
                            {k: v - cur["stats"].get(k, 0)
                             for k, v in stats_now.items()})
        m["weight_bytes"] = self._weight_bytes     # gauge, not diffed
        compiles = self._jit_compiles()
        m["jit_compiles"] = compiles - cur.get("jit_compiles", 0)
        if self.continuous_active:
            m["lanes"] = self._lane_view(lanes_now, cur.get("lanes"))
        self._win_cursor = {"latencies": i_lat, "batch_sizes": i_bs,
                            "timings": i_tim, "stats": stats_now,
                            "lanes": lanes_now, "jit_compiles": compiles}
        return m
