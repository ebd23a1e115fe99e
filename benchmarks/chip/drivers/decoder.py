"""Driver of decoder cells: the program's ``ServingEngine`` in decoder mode
(continuous scheduler, a lane per pad bucket, adaptive width tiers,
``CachePool`` KV slots), fed through ``generate`` at Poisson arrivals.

Every token is stamped when the engine publishes it to its request's
stream (``RequestHandle._push``, wrapped for the run), so no client thread
per request competes with the engine's scheduler for the interpreter:
time to first token runs from when the request was due to its first
token, and time per output token is (last - first) / (n - 1). Decoding is
greedy, so every served token can be judged against the reference after
the window.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from harness import loop, trace
from harness.program import program_config
from harness.record import Check, Request, Window
from harness.traffic import arrivals, output_budgets, prompts, rng_for

DRAIN_S = 60.0
SPANS = (("_drain", "sched.drain"), ("_admit", "sched.admit"),
         ("_prefill", "sched.prefill"), ("_fill_chunk", "sched.fill_chunk"),
         ("_segment_full", "sched.segment"),
         ("_segment_compact", "sched.segment_compact"),
         ("_finish", "sched.finish"))


def annotate() -> None:
    from repro.serving.continuous import ContinuousScheduler
    trace.annotate([(ContinuousScheduler, m, s) for m, s in SPANS])


class Session:
    """Set-up of one decoder cell: weights from the seed, the engine and its
    warmed shapes (the cell's bucket, join sizes and width tiers, greedy)."""

    def __init__(self, cell, seed: int, cfg=None):
        from repro.serving import EngineConfig, ServingEngine
        self.cell, self.seed = cell, seed
        conf, traffic = cell.config, cell.traffic
        self.ref = cell.reference()
        cfg = cfg or program_config(conf)
        stamp_tokens()
        params = self.ref.make_weights(conf, seed)
        jax.block_until_ready(params)
        eng_kw = dict(traffic["engine"])
        eng_kw["pad_buckets"] = tuple(eng_kw["pad_buckets"])
        self.eng = ServingEngine(cfg, params,
                                 EngineConfig(mode="decoder", **eng_kw))
        del params
        self.eng.warmup(batch_sizes=range(1, eng_kw["max_batch"] + 1),
                        buckets=eng_kw["pad_buckets"])

    def measure(self, seconds: float, trace: bool = False,
                rate: float | None = None) -> Window:
        from repro.serving import SamplingParams
        traffic = dict(self.cell.traffic)
        if rate:
            traffic["rate_per_s"] = rate
        vocab = self.cell.config["vocab_size"]
        due = arrivals(traffic, seconds)
        n = len(due)
        toks = prompts(traffic, n, vocab, rng_for(self.seed, "prompts"))
        budgets = output_budgets(traffic, n)
        reqs, handles = [], []
        self.eng.window()
        t0 = time.perf_counter()
        t1 = t0 + seconds
        span = loop.TracedSpan(trace, t0, t1, on_edge=self.eng.metrics)
        for i, d in enumerate(due):
            loop.wait_until(t0 + d)
            span.poll(t0 + d)
            r = Request(due=t0 + d, length=len(toks[i]),
                        budget=int(budgets[i]))
            r.sent = time.perf_counter()
            handles.append(self.eng.generate(toks[i], SamplingParams(
                max_new_tokens=int(budgets[i]))))
            reqs.append(r)
        loop.wait_until(t1)
        span.stop()
        deadline = time.perf_counter() + DRAIN_S
        for h, r, prompt in zip(handles, reqs, toks):
            _collect(h, r, prompt, max(0.0, deadline - time.perf_counter()))
        engine = self.eng.window()
        span.reduce()
        win = Window(conf=self.cell.config, traffic=traffic,
                     reference=self.ref, peaks={}, t0=t0, t1=t1,
                     requests=reqs, engine=engine)
        if span.summary is not None:
            win.trace, win.trace_t0, win.trace_t1 = (span.summary, span.t0,
                                                     span.t1)
            win.trace_engine = span.counters()
        return win

    def close(self) -> None:
        self.eng.close()
        self.eng = None


def stamp_tokens() -> None:
    """Wrap ``RequestHandle._push`` so that each handle records, in
    ``token_times``, when each of its tokens was published to its stream."""
    from repro.serving.api import RequestHandle
    real = RequestHandle._push
    if getattr(real, "_bench_stamp", False):
        return

    def push(self, tokens):
        now = time.perf_counter()
        self.__dict__.setdefault("token_times", []).extend(
            [now] * len(tokens))
        real(self, tokens)
    push._bench_stamp = True
    RequestHandle._push = push


def _collect(handle, r: Request, prompt, timeout: float) -> None:
    """One request's answer and token stamps, waiting at most ``timeout``;
    a request that never finished keeps ``done`` None."""
    try:
        res = handle.result(timeout=timeout)
    except Exception as e:           # a failed or late request is reported
        r.error = repr(e)
        return
    times = r.token_times = list(getattr(handle, "token_times", ()))
    r.n_out = len(res.tokens)
    r.queue_s, r.prefill_s = res.timing.queue_s, res.timing.prefill_s
    if times:
        r.first, r.done = times[0], times[-1]
    r.keep = (prompt, np.asarray(res.tokens, np.int32))


def sample(win: Window, seed: int) -> list:
    """The requests the check compares: the one with the most served
    tokens, then others in an order drawn from the seed, until the
    traffic's ``check`` count of requests or of served tokens is reached."""
    want = win.traffic["check"]
    done = [r for r in win.requests if r.error is None and r.keep is not None]
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: (done[i].n_out,
                                                   done[i].length))
    rest = [i for i in rng_for(seed, "sample").permutation(len(done))
            if i != longest]
    picked, tokens = [], 0
    for i in [longest] + rest:
        if len(picked) >= want["requests"] or tokens >= want["tokens"]:
            break
        picked.append(done[i])
        tokens += done[i].n_out
    return picked


def check(cell, seed: int, win: Window, control: str | None = None) -> list:
    """Served tokens against the reference's full forward over each sampled
    prompt and its served tokens, with weights made again from the seed.
    With ``control`` (a precision), each position's token is the one the
    reference at that precision puts first, in the program's place."""
    conf, eng = cell.config, cell.traffic["engine"]
    ref = cell.reference()
    length = max(eng["pad_buckets"]) + eng["max_new_tokens"]
    unanswered = sum(1 for r in win.requests
                     if r.error is not None or r.done is None)
    short = sum(1 for r in win.requests
                if r.error is None and r.done is not None
                and r.n_out != r.budget)
    picked = sample(win, seed)
    w = ref.make_weights(conf, seed)
    gap = max((ref.served_gap(conf, w, *r.keep, length, control)
               for r in picked),
              default=float("inf"))
    return [Check("logit_gap", gap, conf["limits"].get("logit_gap", 0.0)),
            Check("short_outputs", short, 0),
            Check("unanswered", unanswered, 0)]
