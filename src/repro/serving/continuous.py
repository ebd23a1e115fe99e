"""Multi-lane step-driven continuous-batching scheduler.

The single-set predecessor closed the ROADMAP's "continuous batching at
step granularity" item but kept two head-of-line cliffs it opened:

  * **cross-bucket blocking** — all in-flight rows shared one pad bucket,
    so a request padding to a different bucket waited for the entire set
    to drain (and ``_admit`` re-scanned the whole pending heap every
    segment while those foreign-bucket requests sat in it);
  * **prefill stalls** — a join's prefill ran its whole prompt in one
    call between segments, stalling every in-flight row for the full
    prompt's forward.

This scheduler fixes both. Each pad bucket gets its own **lane** — a
``CachePool``-backed slot batch with per-slot decode state, occupancy
counters, and a per-lane pending queue (``scheduler.LaneQueue``: O(log n)
lane-aware pop, no cross-bucket rescans) — and the worker round-robins
jitted decode segments (``models.decode_segment``) across non-empty lanes,
so a bucket-64 request admits into free bucket-64 slots immediately while
the bucket-32 set keeps decoding. Between a lane's segments (a host sync
it needs anyway to stream tokens) the worker:

  * retires rows that finished in-graph (per-row eos / budget stop),
    releasing their slot and resolving their future;
  * retires rows whose client cancelled mid-decode (or mid-prefill);
  * admits the best pending requests per lane (priority order, FIFO
    within a level) via prefill-into-slot;
  * advances **chunked prefills**: a join whose prompt exceeds
    ``EngineConfig.prefill_chunk`` prefills ``models.prefill_chunk``-sized
    chunks into a staging pool slot — one chunk per scheduler turn,
    interleaved with decode segments — and is copied into its reserved
    lane slot (one chunk-granular ``CachePool.write_back``) when the
    prompt completes, so a 512-token join no longer stalls every in-flight
    row for the whole prompt's prefill. The staging slot (not the live
    lane slot) absorbs the chunks because inactive rows idempotently
    re-write their frozen KV every segment — a partially filled live slot
    would be corrupted between chunks.

``EngineConfig.multi_lane=False`` keeps the legacy single-set admission
gate (one bucket serves until it drains) for A/B runs — the
``bench_multi_bucket`` baseline.

Decode segments are **occupancy-adaptive** (the fixed-width follow-on the
ROADMAP tracked): before each segment the scheduler picks the smallest
width tier (powers of two up to ``max_batch`` — ``scheduler.width_tiers``)
that fits the lane's live rows, compacts those rows' KV slots and decode
state into a tier-width view (``CachePool.compact_view`` — one fused
gather), runs ``models.decode_segment`` at that width, and scatters the
results back to the home slots (``CachePool.scatter_back`` — padding rows
are dropped, so untouched slots stay bitwise identical). A lane whose one
long request decodes alone thus pays a width-1-or-2 segment, not
``max_batch``. ``EngineConfig.segment_width='fixed'`` keeps the
always-full-width segment as the A/B baseline (``bench_segment_width``);
either way correctness never depends on occupancy, and each tier is one
compiled function per bucket (primed by ``engine.warmup()``).
Per-segment occupancy lands in ``engine.batch_sizes`` and per-lane
segment/occupancy/join/chunk/compaction counters plus the segment-width
``tier_hist`` in ``engine.metrics()['lanes']``.
"""
from __future__ import annotations

import dataclasses
import queue
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving.api import (FINISH_CANCELLED, FINISH_EOS, FINISH_LENGTH,
                               GenerationResult, RequestTiming)
from repro.serving import kvcache
from repro.serving.clock import WorkerClock
from repro.serving.kvcache import CachePool
from repro.serving.scheduler import LaneQueue, pick_tier


@dataclasses.dataclass(eq=False)     # identity semantics: list.remove /
class _Row:                          # membership must not compare the
    req: "object"                    # engine._Request (np token arrays)
    slot: int
    toks: List[int] = dataclasses.field(default_factory=list)


@dataclasses.dataclass(eq=False)
class _Fill:
    """A join whose prompt is prefilling chunk-by-chunk: ``slot`` is its
    reserved lane slot (written once, when the prompt completes), ``stg``
    its staging-pool slot (written every chunk), ``filled`` the prompt
    tokens staged so far. Sampling/stop arrays are frozen at claim time so
    chunk batches regroup freely across scheduler turns."""
    req: "object"
    slot: int
    stg: int
    filled: int = 0
    matched: int = 0       # prompt tokens reused from the prefix store
    temp: float = 0.0
    topk: int = 0
    seed: int = 0
    eos: int = -1
    budget: int = 0


class _Lane:
    """One pad bucket's in-flight set: pool slots + per-slot decode state.

    State arrays are indexed by pool slot; free and prefilling slots ride
    along inactive (``active=False``) in every segment, idempotently
    re-writing their frozen KV position — correctness never depends on
    occupancy, and reset-on-assign wipes a slot when it is re-acquired.
    """

    def __init__(self, eng, bucket: int):
        self._eng = eng                  # guarded-by: init
        self.bucket = bucket             # guarded-by: init
        self.staging: Optional[CachePool] = None   # guarded-by: worker
        n = eng.ec.max_batch
        self.last_tok = np.zeros(n, np.int32)   # guarded-by: worker — last sampled
        self.pos = np.zeros(n, np.int32)        # guarded-by: worker — abs position
        self.active = np.zeros(n, bool)         # guarded-by: worker
        self.budget = np.zeros(n, np.int32)     # guarded-by: worker — tokens left
        self.eos = np.full(n, -1, np.int32)     # guarded-by: worker
        self.temp = np.zeros(n, np.float32)     # guarded-by: worker
        self.topk = np.zeros(n, np.int32)       # guarded-by: worker
        self.seed = np.zeros(n, np.int32)       # guarded-by: worker
        self.rows: Dict[int, _Row] = {}         # guarded-by: worker — slot -> _Row
        self.fills: List[_Fill] = []            # guarded-by: worker — chunked prefills

    @property
    def busy(self) -> bool:  # holds: worker
        return bool(self.rows or self.fills)

    @property
    def pool(self) -> CachePool:
        """The bucket's slot pool — resolved through the engine's pool
        cache so pre-creating every lane at scheduler construction (the
        worker iterates ``lanes.values()``; lazy insertion from client
        threads raced that) does not eagerly allocate device caches for
        buckets the workload never touches."""
        return self._eng._get_pool(self.bucket)

    def get_staging(self, eng) -> CachePool:  # holds: worker
        if self.staging is None:
            # engine._slot_len keeps staging slots shape-identical to lane
            # slots (full-slot copies at fill-complete install), including
            # spec-decode's verify-chunk ring headroom
            self.staging = CachePool(
                eng.cfg, eng.ec.max_batch,
                eng._slot_len(self.bucket), dtype=jnp.float32,
                kv_quant=eng.ec.kv_quant)
        return self.staging


class ContinuousScheduler:
    # the worker's phases (``WorkerClock``), counters cont_<phase>_ns:
    # wait (idle, blocked on the queue), admit (admission bookkeeping),
    # dispatch (host arrays to the device, calls into jitted programs),
    # device (blocked on a program's outputs), copy (outputs to numpy),
    # publish (streaming tokens, retiring rows, resolving futures)
    PHASES = ("wait", "admit", "dispatch", "device", "copy", "publish")

    def __init__(self, engine):
        self.eng = engine                # guarded-by: init
        self.clock = WorkerClock(engine._stats, "cont",  # guarded-by: init
                                 self.PHASES)
        # every lane exists up front (device pools stay lazy — see
        # _Lane.pool): the worker's idle/busy checks iterate this dict,
        # and lazily inserting lanes from warmup or client threads raced
        # that iteration — part of the first-traffic warm-in
        self.lanes: Dict[int, _Lane] = {  # guarded-by: worker
            b: _Lane(engine, b) for b in engine.ec.pad_buckets}
        self.pending = LaneQueue()              # guarded-by: worker — pending queues
        self._rr = 0                            # guarded-by: worker — round-robin

    def _lane(self, bucket: int) -> _Lane:  # holds: worker
        return self.lanes[bucket]

    # ------------------------------------------------------------ worker
    def run(self):  # holds: worker
        eng = self.eng
        try:
            while not eng._stop.is_set():
                try:
                    idle = not self.pending and not any(
                        l.busy for l in self.lanes.values())
                    if idle:
                        with self.clock.phase("wait"):
                            self._drain(block=True)
                    with self.clock.phase("admit"):
                        if not idle:
                            self._drain(block=False)
                        self._admit()
                    lane = self._next_lane()
                    if lane is not None:
                        self._step(lane)
                except Exception as e:  # surfaced to the affected clients
                    self._fail_inflight(e)
        finally:
            self._shutdown()

    def _drain(self, block: bool) -> None:  # holds: worker
        """Move newly submitted requests into their lane's pending queue;
        when idle, block briefly so the loop doesn't spin."""
        eng = self.eng
        try:
            while True:
                req = (eng._q.get(timeout=0.05) if block
                       else eng._q.get_nowait())
                block = False
                self.pending.push(req, req.priority,
                                  lane=eng._bucket(len(req.tokens)))
        except queue.Empty:
            pass

    def _next_lane(self) -> Optional[_Lane]:  # holds: worker
        """Round-robin over lanes with in-flight work, so no bucket's
        decode starves while another bucket is busy."""
        busy = [l for l in self.lanes.values() if l.busy]
        if not busy:
            return None
        self._rr = (self._rr + 1) % len(busy)
        return busy[self._rr]

    def _step(self, lane: _Lane) -> None:  # holds: worker
        """One scheduler turn for a lane: advance its chunked prefills by
        one chunk, then run one decode segment for its in-flight rows —
        the interleave that bounds how long a join can stall decode."""
        if lane.fills:
            self._fill_chunk(lane)
        if lane.rows:
            if self.eng.ec.spec_decode:
                self._spec_round(lane)
            else:
                self._segment(lane)

    # --------------------------------------------------------- admission
    def _admit(self) -> None:  # holds: worker
        eng = self.eng
        if not self.pending:
            return
        drop = lambda r: r.future.done()    # noqa: E731 — cancelled in queue
        if eng.ec.multi_lane:
            buckets = self.pending.lanes()
        else:
            # legacy single-set gate (A/B baseline): one bucket serves
            # until it fully drains; the next is picked by the globally
            # best pending request — the head-of-line cliff lanes remove
            busy = [b for b, l in self.lanes.items() if l.busy]
            if busy:
                buckets = [b for b in busy if self.pending.lane_len(b)]
            else:
                best = self.pending.best_lane(drop)
                buckets = [] if best is None else [best]
        any_busy = any(l.busy for l in self.lanes.values())
        for bucket in buckets:
            lane = self._lane(bucket)
            claimed = []
            while lane.pool.free_slots > len(claimed):
                r = self.pending.pop(bucket, drop=drop)
                if r is None:
                    break
                claimed.append(r)
            claimed = [r for r in claimed
                       if r.future.set_running_or_notify_cancel()]
            if not claimed:
                continue
            if any_busy:
                eng._stats["joins_mid_flight"] += len(claimed)
                eng._lane_stat(bucket)["joins"] += len(claimed)
            any_busy = True
            chunk = eng.ec.prefill_chunk
            store = eng._prefix_store(bucket)
            whole, hits, fills, fill_entries = [], [], [], []
            for r in claimed:
                entry = store.lookup(r.tokens) if store is not None else None
                if entry is not None:
                    stat = eng._lane_stat(bucket)
                    stat["prefix_hits"] += 1
                    stat["prefix_hit_tokens"] += entry.n_tokens
                    if len(r.tokens) - entry.n_tokens <= chunk:
                        # the unseen suffix fits one chunk: copy the
                        # stored KV into a lane slot and finish the
                        # prompt in a single admission-time chunk call
                        hits.append((r, entry))
                    else:
                        # partial match: the fill starts ``matched``
                        # tokens in instead of at zero
                        fills.append(r)
                        fill_entries.append(entry)
                    continue
                if store is not None:
                    eng._lane_stat(bucket)["prefix_misses"] += 1
                if chunk is None or len(r.tokens) <= chunk:
                    whole.append(r)
                else:
                    fills.append(r)
                    fill_entries.append(None)
            if whole:
                self._prefill(whole, lane)
            if hits:
                self._prefill_hits(hits, lane)
            if fills:
                self._begin_fills(fills, lane, entries=fill_entries)

    # ----------------------------------------------- whole-prompt prefill
    def _prefill(self, claimed, lane: _Lane) -> None:  # holds: worker
        """Prefill-into-slot: fill the new rows' KV straight into pool
        slots and emit their first token; they join the in-flight set for
        the next segment. A failure anywhere (compile error, pool
        exhaustion, ...) must not strand the claimed requests — their
        futures are already RUNNING and outside lane.rows, so run()'s
        _fail_inflight can't see them: fail them here and release any
        slots that never became rows, then keep serving."""
        try:
            self._prefill_inner(claimed, lane)
        except Exception as e:
            live = {id(row.req) for row in lane.rows.values()}
            ids = {id(r) for r in claimed}
            for slot, rid in enumerate(lane.pool.request_of):
                if rid in ids and slot not in lane.rows:
                    lane.pool.release(slot)
            for r in claimed:
                if id(r) not in live and not r.future.done():
                    r.future.set_exception(e)

    def _prefill_inner(self, claimed, lane: _Lane) -> None:  # holds: worker
        eng, clock = self.eng, self.clock
        t0 = time.perf_counter()
        B, bucket, pool = len(claimed), lane.bucket, lane.pool
        with clock.phase("dispatch"):
            # gather acquire: one compiled variant per join size, not per
            # slot run position (joins land at arbitrary offsets mid-serve)
            slots, view = pool.acquire([id(r) for r in claimed], gather=True)
            toks = np.zeros((B, bucket), np.int32)
            lens = np.zeros(B, np.int32)
            for i, r in enumerate(claimed):
                r.t_start = t0
                toks[i, :len(r.tokens)] = r.tokens
                lens[i] = len(r.tokens)
            temp, topk, seed, eos, budget, any_sample = \
                eng._sampling_arrays(claimed)
            sargs = ((jnp.asarray(temp), jnp.asarray(topk),
                      jnp.asarray(seed)) if any_sample else (None, None, None))
            first, caches = eng._prefill_fn()(
                eng.params, jnp.asarray(toks), jnp.asarray(lens), view,
                *sargs)
            if eng.ec.spec_decode:
                # spec install: verify chunks attend the whole ring, so the
                # padded prefill tail (attn_apply stamps valid pos on every
                # bucket position) must go back to the empty sentinel — the
                # rollback fuses that truncation into the write-back
                pool.scatter_rollback(slots, caches, [int(x) for x in lens],
                                      lengths=[int(x) + 1 for x in lens])
                self._draft_prefill(lane, claimed, slots)
            else:
                pool.write_back(slots, caches,
                                lengths=[int(x) + 1 for x in lens])
            for r, s in zip(claimed, slots):
                # whole-prompt joins can still seed the store (a prompt of
                # exactly one chunk is a storable boundary)
                self._insert_prefix(lane, r, 0, s)
        with clock.phase("device"):
            jax.block_until_ready(first)
        with clock.phase("copy"):
            first = np.asarray(first)
        eng._stats["prefill_batches"] += 1
        t1 = time.perf_counter()
        with clock.phase("publish"):
            for i, (r, s) in enumerate(zip(claimed, slots)):
                r.t_prefill_done = t1
                self._start_row(lane, r, s, int(first[i]), int(lens[i]),
                                budget=int(budget[i]), eos=int(eos[i]),
                                temp=float(temp[i]), topk=int(topk[i]),
                                seed=int(seed[i]), now=t1)

    def _start_row(self, lane: _Lane, r, slot: int, tok: int, plen: int, *,  # holds: worker
                   budget: int, eos: int, temp: float, topk: int, seed: int,
                   now: float) -> None:
        """Install a freshly prefilled request as an in-flight decode row
        (its first token already selected at the prompt's last position)."""
        row = _Row(req=r, slot=slot, toks=[tok])
        lane.rows[slot] = row
        r.handle._push([tok])
        lane.last_tok[slot] = tok
        lane.pos[slot] = plen           # first token sits at len(prompt)
        lane.budget[slot] = budget - 1  # the first token spent one
        lane.eos[slot], lane.temp[slot] = eos, temp
        lane.topk[slot], lane.seed[slot] = topk, seed
        hit = eos >= 0 and tok == eos
        if hit or lane.budget[slot] <= 0:
            self._finish(lane, row, FINISH_EOS if hit else FINISH_LENGTH,
                         now)
        else:
            lane.active[slot] = True

    # ----------------------------------------------- prefix-cache fast path
    def _prefill_hits(self, claimed, lane: _Lane) -> None:  # holds: worker
        """Admit requests whose prompt matched a stored prefix and whose
        unseen suffix fits one chunk: copy-on-reference the stored KV into
        lane slots (one fused gather/scatter) and run a single suffix
        chunk at the absolute prefix offset — the whole prompt never runs.
        ``claimed`` is a list of (request, PrefixEntry) pairs; entry refs
        are released once the copy has been issued. Failure handling
        mirrors _prefill."""
        try:
            self._prefill_hits_inner(claimed, lane)
        except Exception as e:
            live = {id(row.req) for row in lane.rows.values()}
            ids = {id(r) for r, _ in claimed}
            for slot, rid in enumerate(lane.pool.request_of):
                if rid in ids and slot not in lane.rows:
                    lane.pool.release(slot)
            for r, _ in claimed:
                if id(r) not in live and not r.future.done():
                    r.future.set_exception(e)

    def _prefill_hits_inner(self, claimed, lane: _Lane) -> None:  # holds: worker
        eng, clock = self.eng, self.clock
        store = eng._prefix_store(lane.bucket)
        C = eng.ec.prefill_chunk
        t0 = time.perf_counter()
        B, pool = len(claimed), lane.pool
        reqs = [r for r, _ in claimed]
        with clock.phase("dispatch"):
            # claim without reset: the load overwrites the slots fully
            slots = pool.claim([id(r) for r in reqs])
            try:
                store.load_many([e for _, e in claimed], pool, slots)
            finally:
                for _, e in claimed:
                    store.release(e)
            toks = np.zeros((B, C), np.int32)
            start = np.zeros(B, np.int32)
            nvalid = np.zeros(B, np.int32)
            for i, (r, e) in enumerate(claimed):
                r.t_start = t0
                suffix = np.asarray(r.tokens)[e.n_tokens:]
                toks[i, :len(suffix)] = suffix
                start[i], nvalid[i] = e.n_tokens, len(suffix)
            temp, topk, seed, eos, budget, any_sample = \
                eng._sampling_arrays(reqs)
            sargs = ((jnp.asarray(temp), jnp.asarray(topk),
                      jnp.asarray(seed)) if any_sample else (None, None, None))
            first, caches = eng._chunk_fn()(
                eng.params, jnp.asarray(toks), jnp.asarray(start),
                jnp.asarray(nvalid), pool.batch_view(slots, gather=True),
                *sargs)
            if eng.ec.spec_decode:
                # the suffix chunk's padded tail also stamps valid pos
                # values past the prompt — truncate at install (see
                # _prefill_inner)
                pool.scatter_rollback(
                    slots, caches, [len(r.tokens) for r in reqs],
                    lengths=[len(r.tokens) + 1 for r in reqs])
                self._draft_prefill(lane, reqs, slots)
            else:
                pool.write_back(slots, caches,
                                lengths=[len(r.tokens) + 1 for r in reqs])
            for (r, e), s in zip(claimed, slots):
                # a prompt extending >= 1 chunk past its matched prefix is
                # a new, deeper boundary worth storing (conversation growth)
                self._insert_prefix(lane, r, e.n_tokens, s)
        with clock.phase("device"):
            jax.block_until_ready(first)
        with clock.phase("copy"):
            first = np.asarray(first)
        eng._stats["prefill_batches"] += 1
        t1 = time.perf_counter()
        with clock.phase("publish"):
            for i, (r, s) in enumerate(zip(reqs, slots)):
                r.t_prefill_done = t1
                self._start_row(lane, r, s, int(first[i]), len(r.tokens),
                                budget=int(budget[i]), eos=int(eos[i]),
                                temp=float(temp[i]), topk=int(topk[i]),
                                seed=int(seed[i]), now=t1)

    def _insert_prefix(self, lane: _Lane, r, matched: int,  # holds: worker
                       slot: int) -> None:
        """Insert-on-complete: offer the finished prompt's KV (sitting in
        its lane slot) to the bucket's prefix store. ``matched`` is what
        this request itself reused — depths at or below it are already
        stored. No-op when the prefix cache is off for the bucket."""
        store = self.eng._prefix_store(lane.bucket)
        if store is None:
            return
        ins, evc = store.insert(r.tokens, matched, lane.pool, slot)
        if ins or evc:
            stat = self.eng._lane_stat(lane.bucket)
            stat["prefix_inserts"] += ins
            stat["prefix_evictions"] += evc
            stat["prefix_bytes"] = store.bytes_used

    # --------------------------------------------------- chunked prefill
    def _begin_fills(self, claimed, lane: _Lane, entries=None) -> None:  # holds: worker
        """Reserve a lane slot + a staging slot per long-prompt join; the
        prompt then advances one chunk per scheduler turn in _fill_chunk.
        ``entries[i]`` (when given) is request i's matched ``PrefixEntry``:
        its stored KV is copied into the staging slot and the fill starts
        ``matched`` tokens in — a head start on a prompt whose unseen
        suffix still spans multiple chunks. Entry refs are released here
        whatever happens. Failure handling mirrors _prefill: claimed
        futures are RUNNING, so fail them here and release both slots."""
        eng = self.eng
        store = eng._prefix_store(lane.bucket)
        if entries is None:
            entries = [None] * len(claimed)
        try:
            staging = lane.get_staging(eng)
            temp, topk, seed, eos, budget, _ = eng._sampling_arrays(claimed)
            slots = lane.pool.assign_many([id(r) for r in claimed])
            stg = staging.assign_many([id(r) for r in claimed])
            hit = [(ent, stg[i]) for i, ent in enumerate(entries)
                   if ent is not None]
            if hit:
                with self.clock.phase("dispatch"):
                    store.load_many([ent for ent, _ in hit], staging,
                                    [s for _, s in hit])
            t0 = time.perf_counter()
            for i, r in enumerate(claimed):
                r.t_start = t0
                matched = entries[i].n_tokens if entries[i] else 0
                lane.fills.append(_Fill(
                    req=r, slot=slots[i], stg=stg[i],
                    filled=matched, matched=matched,
                    temp=float(temp[i]), topk=int(topk[i]),
                    seed=int(seed[i]), eos=int(eos[i]),
                    budget=int(budget[i])))
        except Exception as e:
            ids = {id(r) for r in claimed}
            self._release_fills(lane, [f for f in lane.fills
                                       if id(f.req) in ids])
            for pool in (lane.pool, lane.staging):
                if pool is None:
                    continue
                for slot, rid in enumerate(pool.request_of):
                    if rid in ids:
                        pool.release(slot)
            for r in claimed:
                if not r.future.done():
                    r.future.set_exception(e)
        finally:
            for ent in entries:
                if ent is not None:
                    store.release(ent)

    def _release_fills(self, lane: _Lane, fills) -> None:  # holds: worker
        for f in fills:
            if f in lane.fills:
                lane.fills.remove(f)
            lane.pool.release(f.slot)
            if lane.staging is not None:
                lane.staging.release(f.stg)

    def _fill_chunk(self, lane: _Lane) -> None:  # holds: worker
        """Advance every in-flight fill of this lane by one prompt chunk
        (one jitted call over the fill batch). Fills whose prompt completes
        are copied staging -> lane slot (one chunk-granular write_back) and
        join the decode set with their first token."""
        eng = self.eng
        now = time.perf_counter()
        for f in list(lane.fills):       # cancelled mid-prefill: retire
            h = f.req.handle
            if h is not None and h.cancel_requested:
                with self.clock.phase("publish"):
                    self._release_fills(lane, [f])
                    f.req.t_prefill_done = now
                    self._resolve(f.req, [], FINISH_CANCELLED, now)
        if not lane.fills:
            return
        try:
            self._fill_chunk_inner(lane)
        except Exception as e:
            fills = list(lane.fills)
            self._release_fills(lane, fills)
            for f in fills:
                if not f.req.future.done():
                    f.req.future.set_exception(e)

    def _fill_chunk_inner(self, lane: _Lane) -> None:  # holds: worker
        eng, clock = self.eng, self.clock
        C = eng.ec.prefill_chunk
        fills = list(lane.fills)
        B = len(fills)
        staging = lane.get_staging(eng)
        with clock.phase("dispatch"):
            toks = np.zeros((B, C), np.int32)
            start = np.zeros(B, np.int32)
            nvalid = np.zeros(B, np.int32)
            temp = np.zeros(B, np.float32)
            topk = np.zeros(B, np.int32)
            seed = np.zeros(B, np.int32)
            for i, f in enumerate(fills):
                chunk = np.asarray(f.req.tokens)[f.filled:f.filled + C]
                toks[i, :len(chunk)] = chunk
                start[i], nvalid[i] = f.filled, len(chunk)
                temp[i], topk[i], seed[i] = f.temp, f.topk, f.seed
            any_sample = bool((temp > 0).any())
            sargs = ((jnp.asarray(temp), jnp.asarray(topk),
                      jnp.asarray(seed)) if any_sample else (None, None, None))
            stg_slots = [f.stg for f in fills]
            first, caches = eng._chunk_fn()(
                eng.params, jnp.asarray(toks), jnp.asarray(start),
                jnp.asarray(nvalid),
                staging.batch_view(stg_slots, gather=True), *sargs)
            staging.write_back(
                stg_slots, caches,
                lengths=[f.filled + int(nvalid[i])
                         for i, f in enumerate(fills)])
        with clock.phase("device"):
            jax.block_until_ready(first)
        with clock.phase("copy"):
            first = np.asarray(first)
        eng._stats["prefill_chunks"] += B
        eng._lane_stat(lane.bucket)["prefill_chunks"] += B
        done = []
        for i, f in enumerate(fills):
            f.filled += int(nvalid[i])
            if f.filled >= len(f.req.tokens):
                done.append((i, f))
        if not done:
            return
        t1 = time.perf_counter()
        with clock.phase("dispatch"):
            # one scatter installs every completed prompt into its lane slot
            done_slots = [f.slot for _, f in done]
            src = staging.batch_view([f.stg for _, f in done], gather=True)
            if eng.ec.spec_decode:
                # the last staged chunk's padded tail carries valid pos
                # values past the prompt — truncate at install (see
                # _prefill_inner)
                lane.pool.scatter_rollback(
                    done_slots, src, [f.filled for _, f in done],
                    lengths=[f.filled + 1 for _, f in done])
                self._draft_prefill(lane, [f.req for _, f in done],
                                    done_slots)
            else:
                lane.pool.write_back(
                    done_slots, src, lengths=[f.filled + 1 for _, f in done])
            for _, f in done:
                self._insert_prefix(lane, f.req, f.matched, f.slot)
        with clock.phase("publish"):
            for i, f in done:
                lane.fills.remove(f)
                staging.release(f.stg)
                f.req.t_prefill_done = t1
                self._start_row(lane, f.req, f.slot, int(first[i]),
                                f.filled, budget=f.budget, eos=f.eos,
                                temp=f.temp, topk=f.topk, seed=f.seed,
                                now=t1)

    # ------------------------------------------------------ decode steps
    def _segment(self, lane: _Lane) -> None:  # holds: worker
        """One decode segment for a lane, at the smallest width tier that
        fits its live occupancy (``segment_width='adaptive'``; 'fixed'
        degenerates the ladder to ``max_batch`` and always takes the
        full-width path). Both paths return per-row results aligned with
        ``slots``; the retire loop below is shared."""
        eng = self.eng
        occ = len(lane.rows)
        width = pick_tier(occ, eng._tiers)
        stat = eng._lane_stat(lane.bucket)
        if width >= eng.ec.max_batch:
            width = eng.ec.max_batch
            slots, toks, emits, st_active, st_eos = self._segment_full(lane)
        else:
            slots, toks, emits, st_active, st_eos = \
                self._segment_compact(lane, width)
            stat["compact_segments"] += 1
        eng.batch_sizes.append(occ)              # per-segment occupancy
        eng._stats["decode_segments"] += 1
        stat["decode_segments"] += 1
        stat["occupancy_sum"] += occ
        stat["tier_hist"][width] += 1    # key pre-created per tier
        #                                  (metrics() iterates lock-free)
        now = time.perf_counter()
        pool = lane.pool
        with self.clock.phase("publish"):
            for j, s in enumerate(slots):
                row = lane.rows[s]
                new = toks[j][emits[j]].tolist()
                row.toks.extend(new)
                row.req.handle._push(new)
                pool.lengths[s] = int(lane.pos[s]) + 1
                if not st_active[j]:
                    self._finish(lane, row, FINISH_EOS if st_eos[j]
                                 else FINISH_LENGTH, now)
                elif row.req.handle.cancel_requested:
                    self._finish(lane, row, FINISH_CANCELLED, now)

    def _segment_full(self, lane: _Lane):  # holds: worker
        """Full-width segment over every pool slot (live rows plus inert
        free/prefilling slots) — today's fixed-width path, and the adaptive
        path's top tier. The pool caches are donated and swapped whole."""
        eng, clock = self.eng, self.clock
        pool = lane.pool
        with clock.phase("dispatch"):
            any_sample = any(lane.temp[s] > 0 for s in lane.rows)
            sargs = ((jnp.asarray(lane.temp), jnp.asarray(lane.topk),
                      jnp.asarray(lane.seed)) if any_sample
                     else (None, None, None))
            toks, emits, state, caches = eng._segment_fn()(
                eng.params, jnp.asarray(lane.last_tok[:, None]),
                jnp.asarray(lane.pos[:, None]), pool.caches,
                jnp.asarray(lane.active), jnp.asarray(lane.budget),
                jnp.asarray(lane.eos), *sargs)
            pool.caches = caches
        with clock.phase("device"):
            jax.block_until_ready((toks, emits, state))
        with clock.phase("copy"):
            toks, emits = np.asarray(toks), np.asarray(emits)
            st_active = np.asarray(state["active"])
            st_eos = np.asarray(state["eos_hit"])
            lane.last_tok = np.asarray(state["tok"])[:, 0].copy()
            lane.pos = np.asarray(state["pos"])[:, 0].copy()
            lane.budget = np.asarray(state["budget"]).copy()
            lane.active = st_active.copy()
        slots = list(lane.rows)
        return (slots, toks[slots], emits[slots], st_active[slots],
                st_eos[slots])

    def _segment_compact(self, lane: _Lane, width: int):  # holds: worker
        """Compacted segment: gather the live rows (and their decode
        state) into a ``width``-row view, decode at that width, scatter
        the live prefix back to the home slots. View rows past the
        occupancy are duplicates of ``slots[0]`` that ride along inactive
        and are never scattered back, so pool slots outside ``slots`` —
        free, prefilling, or mid-retire — keep their KV and state bitwise
        (tested as a round-trip property)."""
        eng, clock = self.eng, self.clock
        pool = lane.pool
        slots = sorted(lane.rows)         # deterministic gather order
        occ = len(slots)
        with clock.phase("dispatch"):
            # idx is the view's gather order (slots + padding duplicates);
            # state rows are gathered by the same idx so row j of the state
            # always describes row j of the cache view
            idx, view = pool.compact_view(slots, width)
            act = lane.active[idx].copy()
            act[occ:] = False                 # padding rows are inert
            any_sample = any(lane.temp[s] > 0 for s in slots)
            sargs = ((jnp.asarray(lane.temp[idx]),
                      jnp.asarray(lane.topk[idx]),
                      jnp.asarray(lane.seed[idx])) if any_sample
                     else (None, None, None))
            toks, emits, state, caches = eng._segment_fn()(
                eng.params, jnp.asarray(lane.last_tok[idx][:, None]),
                jnp.asarray(lane.pos[idx][:, None]), view,
                jnp.asarray(act), jnp.asarray(lane.budget[idx]),
                jnp.asarray(lane.eos[idx]), *sargs)
            pool.scatter_back(slots, caches)
        with clock.phase("device"):
            jax.block_until_ready((toks, emits, state))
        with clock.phase("copy"):
            toks, emits = np.asarray(toks)[:occ], np.asarray(emits)[:occ]
            st_active = np.asarray(state["active"])[:occ]
            st_eos = np.asarray(state["eos_hit"])[:occ]
            lane.last_tok[slots] = np.asarray(state["tok"])[:occ, 0]
            lane.pos[slots] = np.asarray(state["pos"])[:occ, 0]
            lane.budget[slots] = np.asarray(state["budget"])[:occ]
            lane.active[slots] = st_active
        return slots, toks, emits, st_active, st_eos

    # ------------------------------------------------- speculative rounds
    def _draft_prefill(self, lane: _Lane, reqs, slots) -> None:  # holds: worker
        """Whole-prompt prefill of the draft model's KV for newly admitted
        rows, into the draft pool at the rows' lane slot indices. The
        draft always sees the full prompt in one call — prompts fit the
        bucket by construction and the draft has no prefix store —
        whichever path (whole, prefix-hit, chunked fill) admitted the row
        on the target side; the rollback wipes the padded tail exactly
        like the target install's. The draft pool bypasses slot
        bookkeeping (no claim/release): slot liveness is the lane pool's."""
        eng = self.eng
        dpool = eng._get_draft_pool(lane.bucket)
        B, bucket = len(reqs), lane.bucket
        toks = np.zeros((B, bucket), np.int32)
        lens = np.zeros(B, np.int32)
        for i, r in enumerate(reqs):
            toks[i, :len(r.tokens)] = r.tokens
            lens[i] = len(r.tokens)
        sl = jnp.asarray(list(slots), jnp.int32)
        dpool.caches, dview = kvcache._reset_and_view(
            dpool.caches, dpool._template, sl)
        dcaches = eng._draft_prefill_fn()(eng.draft_params,
                                          jnp.asarray(toks), dview)
        dpool.caches = kvcache._scatter_rollback(
            dpool.caches, dcaches, sl, jnp.asarray(lens))

    def _spec_round(self, lane: _Lane) -> None:  # holds: worker
        """One draft-and-verify round for a lane's live rows — the spec
        engine's replacement for ``_segment``. Always the compacted gather
        path (``segment_width='fixed'`` just pins the tier ladder to
        max_batch), so untouched pool slots stay bitwise identical: the
        round runs on a tier-width *view* of both pools and only the live
        prefix is scattered home, each row truncated to its own commit
        boundary. Per-row committed counts (1..spec_k+1) desynchronize the
        rows' positions — which plain per-slot segments never do — and the
        rollback is what re-establishes, for both pools, the invariant
        that positions at or past a row's frontier hold the empty
        sentinel before the next round reads them."""
        eng, clock = self.eng, self.clock
        k = eng.ec.spec_k
        pool, dpool = lane.pool, eng._get_draft_pool(lane.bucket)
        slots = sorted(lane.rows)         # deterministic gather order
        occ = len(slots)
        width = pick_tier(occ, eng._tiers)
        with clock.phase("dispatch"):
            idx, view = pool.compact_view(slots, width)
            _, dview = dpool.compact_view(slots, width)
            any_sample = any(lane.temp[s] > 0 for s in slots)
            sargs = ((jnp.asarray(lane.temp[idx]),
                      jnp.asarray(lane.topk[idx]),
                      jnp.asarray(lane.seed[idx])) if any_sample
                     else (None, None, None))
            drafts, verify, caches, dcaches = eng._spec_round_fn()(
                eng.params, eng.draft_params,
                jnp.asarray(lane.last_tok[idx][:, None]),
                jnp.asarray(lane.pos[idx][:, None]), view, dview, *sargs)
        with clock.phase("device"):
            jax.block_until_ready((drafts, verify))
        with clock.phase("copy"):
            drafts = np.asarray(drafts)[:occ]     # (occ, k) proposals
            verify = np.asarray(verify)[:occ]     # (occ, k+1) target picks
        stat = eng._lane_stat(lane.bucket)
        eng.batch_sizes.append(occ)
        eng._stats["decode_segments"] += 1
        stat["decode_segments"] += 1
        stat["occupancy_sum"] += occ
        stat["tier_hist"][width] += 1
        stat["spec_rounds"] += 1
        stat["spec_proposed"] += occ * k
        now = time.perf_counter()
        bounds = np.zeros(occ, np.int32)
        retire = []
        with clock.phase("publish"):
            for j, s in enumerate(slots):
                row = lane.rows[s]
                a = 0              # leading draft tokens the target agreed on
                while a < k and drafts[j, a] == verify[j, a]:
                    a += 1
                stat["spec_accepted"] += a
                # commit the agreements plus one target-selected token (the
                # correction at the first disagreement, or the bonus token
                # after a full accept), clamped to the row's budget
                c = min(a + 1, int(lane.budget[s]))
                committed = verify[j, :c].tolist()
                eos, eos_hit = int(lane.eos[s]), False
                if eos >= 0 and eos in committed:
                    committed = committed[:committed.index(eos) + 1]
                    eos_hit = True
                c = len(committed)
                bounds[j] = int(lane.pos[s]) + c
                lane.last_tok[s] = committed[-1]
                lane.pos[s] = bounds[j]
                lane.budget[s] -= c
                row.toks.extend(committed)
                row.req.handle._push(committed)
                pool.lengths[s] = int(bounds[j]) + 1
                if eos_hit:
                    retire.append((row, FINISH_EOS))
                elif lane.budget[s] <= 0:
                    retire.append((row, FINISH_LENGTH))
                elif row.req.handle.cancel_requested:
                    retire.append((row, FINISH_CANCELLED))
        # scatter before retiring (like _segment_compact): _finish releases
        # slots, and a released slot must not be written afterwards
        with clock.phase("dispatch"):
            pool.scatter_rollback(slots, caches, bounds)
            dpool.scatter_rollback(slots, dcaches, bounds)
        with clock.phase("publish"):
            for row, reason in retire:
                self._finish(lane, row, reason, now)

    # ------------------------------------------------------------ retire
    def _resolve(self, r, toks, reason: str, now: float) -> None:  # holds: worker
        eng = self.eng
        timing = RequestTiming(queue_s=r.t_start - r.t_submit,
                               prefill_s=r.t_prefill_done - r.t_start,
                               decode_s=now - r.t_prefill_done)
        eng.timings.append(timing)
        eng.latencies.append(now - r.t_submit)
        r.future.set_result(GenerationResult(
            tokens=np.asarray(toks, np.int32), finish_reason=reason,
            timing=timing, request_id=r.handle.request.request_id))

    def _finish(self, lane: _Lane, row: _Row, reason: str,  # holds: worker
                now: float) -> None:
        del lane.rows[row.slot]
        lane.pool.release(row.slot)
        lane.active[row.slot] = False
        self._resolve(row.req, row.toks, reason, now)

    def _fail_inflight(self, exc: Exception) -> None:  # holds: worker
        for lane in self.lanes.values():
            for row in list(lane.rows.values()):
                del lane.rows[row.slot]
                lane.pool.release(row.slot)
                lane.active[row.slot] = False
                if not row.req.future.done():
                    row.req.future.set_exception(exc)
            fills = list(lane.fills)
            self._release_fills(lane, fills)
            for f in fills:
                if not f.req.future.done():
                    f.req.future.set_exception(exc)

    def _shutdown(self) -> None:  # holds: worker
        err = RuntimeError("engine is closed")
        self._fail_inflight(err)
        for r in self.pending.drain():
            if not r.future.done():
                r.future.set_exception(err)
