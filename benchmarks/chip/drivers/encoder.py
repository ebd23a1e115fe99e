"""Driver of encoder cells: the program's ``ServingEngine`` in encoder mode,
fed sentence by sentence through ``submit``, with an output head that
returns the tag and detect logits of every position.

Traffic ``closed_bursts`` sends ``burst`` sentences at once and the next
burst when every sentence of the last is answered (the paper's load
procedure); ``open_poisson`` sends single sentences at Poisson arrivals.
A sentence's latency runs from when it was due to when its answer
resolved. A sample of answers, drawn from the seed, is kept and compared
with the reference after the window.
"""
from __future__ import annotations

import functools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import loop, trace
from harness.program import program_config
from harness.record import Check, Request, Window
from harness.traffic import arrivals, rng_for, sentence_pool

DRAIN_S = 60.0
SAMPLE_EVERY = 32              # every 32nd answer is kept for the check,
SAMPLE_MAX = 192               # from an offset drawn from the seed, at most


def _head(params, hidden, mask):
    hid = hidden.astype(jnp.float32)
    return hid @ params["label_head"]["w"], hid @ params["detect_head"]["w"]


class Session:
    """Set-up of one encoder cell: weights from the seed, the engine, its
    warmed shapes, and the traffic's sentences."""

    def __init__(self, cell, seed: int, cfg=None):
        from repro.serving import EngineConfig, ServingEngine
        self.cell, self.seed = cell, seed
        conf, traffic = cell.config, cell.traffic
        self.ref = cell.reference()
        cfg = cfg or program_config(conf)
        params = self.ref.make_weights(conf, seed)
        jax.block_until_ready(params)
        eng_kw = dict(traffic["engine"])
        eng_kw["pad_buckets"] = tuple(eng_kw["pad_buckets"])
        self.eng = ServingEngine(cfg, params,
                                 EngineConfig(mode="encoder", **eng_kw),
                                 head_fn=_head)
        del params
        self.eng.warmup(batch_sizes=range(1, eng_kw["max_batch"] + 1),
                        buckets=eng_kw["pad_buckets"])
        self.pool = sentence_pool(traffic, conf["vocab_size"],
                                  rng_for(seed, "order"))
        self._offset = int(rng_for(seed, "sample").integers(SAMPLE_EVERY))
        self._kept = 0

    def _request(self, i: int, due: float):
        s = self.pool[i % len(self.pool)]
        r = Request(due=due, length=len(s))
        if (self._kept < SAMPLE_MAX
                and i % SAMPLE_EVERY == self._offset):
            r.keep = s
            self._kept += 1
        return r, s

    def _submit(self, r: Request, s, pending: "_Pending") -> None:
        """Hand one sentence to the engine. The future is not kept: its
        callback records what the check and the readers need, so answers
        that are not sampled are freed as they resolve."""
        pending.add()
        r.sent = time.perf_counter()
        f = self.eng.submit(s)
        f.add_done_callback(functools.partial(_on_done, r, pending))

    def measure(self, seconds: float, trace: bool = False,
                rate: float | None = None) -> Window:
        traffic = self.cell.traffic
        reqs, pending = [], _Pending()
        self.eng.window()
        t0 = time.perf_counter()
        t1 = t0 + seconds
        span = loop.TracedSpan(trace, t0, t1, on_edge=self.eng.metrics)
        if traffic["kind"] == "closed_bursts":
            i = 0
            while True:
                now = time.perf_counter()
                if now >= t1:
                    break
                span.poll(now)
                for _ in range(traffic["burst"]):
                    r, s = self._request(i, now)
                    i += 1
                    reqs.append(r)
                    self._submit(r, s, pending)
                pending.wait(DRAIN_S)
            span.stop()
        elif traffic["kind"] == "open_poisson":
            tr = dict(traffic, rate_per_s=rate or traffic["rate_per_s"])
            due = arrivals(tr, seconds)
            for i, d in enumerate(due):
                loop.wait_until(t0 + d)
                span.poll(t0 + d)
                r, s = self._request(i, t0 + d)
                reqs.append(r)
                self._submit(r, s, pending)
            loop.wait_until(t1)
            span.stop()
            pending.wait(DRAIN_S)
        else:
            raise ValueError(f"unknown traffic kind {traffic['kind']!r}")
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


class _Pending:
    """Sentences handed over and not yet answered."""

    def __init__(self):
        self._n = 0
        self._cv = threading.Condition()

    def add(self) -> None:
        with self._cv:
            self._n += 1

    def done(self) -> None:
        with self._cv:
            self._n -= 1
            if not self._n:
                self._cv.notify_all()

    def wait(self, timeout: float) -> bool:
        with self._cv:
            return self._cv.wait_for(lambda: not self._n, timeout)


def _on_done(r: Request, pending: _Pending, f) -> None:
    r.first = r.done = time.perf_counter()
    try:
        tag, det = f.result()
        r.n_out = tag.shape[0]
        if r.keep is not None:       # copies: views would pin whole batches
            r.keep = (r.keep, np.array(tag), np.array(det))
    except Exception as e:           # an answer that failed is reported
        r.error = repr(e)
    finally:
        pending.done()


SPANS = ("_serve_batch", "engine.serve_batch"), ("_record_batch",
                                                  "engine.record_batch")


def annotate() -> None:
    from repro.serving import ServingEngine
    trace.annotate([(ServingEngine, m, s) for m, s in SPANS])


def check(cell, seed: int, win: Window, control: str | None = None) -> list:
    """The kept answers against the reference: ``look``'s error of each
    head, beside its limit, and the answers that were malformed or never
    came."""
    got = look(cell, seed, win, control)
    limits = cell.config["limits"]
    return [Check(f"{h}_err", e["err"], limits.get(f"{h}_err", 0.0))
            for h, e in got["heads"].items()] + [
        Check("bad_answers", got["bad_answers"], 0),
        Check("unanswered", got["unanswered"], 0)]


def look(cell, seed: int, win: Window, control: str | None = None) -> dict:
    """The kept answers against the reference, run at each answer's own
    padded width, with weights made again from the seed. With ``control``
    (a precision), the reference computed at that precision takes the
    program's place: the readings a wrong program of that kind gives.

    A head's ``err`` is the largest |answer - reference| over every real
    position of every kept sentence, over the largest |reference| there:
    the widest gap on the scale of the head's logits. ``sentence`` is the
    same ratio taken sentence by sentence, at its worst, with that
    sentence's length and largest |reference|: a short sentence whose two
    detect logits happen to be small reads high there while its gap is
    no wider than the others'."""
    conf = cell.config
    ref = cell.reference()
    buckets = set(cell.traffic["engine"]["pad_buckets"])
    kept = [r.keep for r in win.requests if isinstance(r.keep, tuple)]
    unanswered = sum(1 for r in win.requests
                     if r.error is not None or r.done is None)
    w = ref.make_weights(conf, seed)
    bad, by_width = 0, {}
    gap = {h: 0.0 for h in ref.HEADS}
    scale = {h: 0.0 for h in ref.HEADS}
    worst = {h: (0.0, 0, 0.0) for h in ref.HEADS}
    for s, tag, det in kept:
        width = tag.shape[0]
        if (width not in buckets or width < len(s)
                or tag.shape != (width, conf["n_tags"])
                or det.shape != (width, 2)):
            bad += 1
            continue
        by_width.setdefault(width, []).append((s, tag, det))
    for width, items in by_width.items():
        for j in range(0, len(items), 32):
            chunk = items[j:j + 32]
            toks = np.zeros((len(chunk), width), np.int32)
            for i, (s, _, _) in enumerate(chunk):
                toks[i, :len(s)] = s
            want = ref.heads(conf, w, toks)
            if control is not None:
                low = ref.heads(conf, w, toks, control)
                chunk = [(s, low[0][i], low[1][i])
                         for i, (s, _, _) in enumerate(chunk)]
            for i, (s, *got) in enumerate(chunk):
                n = len(s)
                for h, g, r in zip(ref.HEADS, got, want):
                    g = np.asarray(g[:n], np.float64)
                    r = np.asarray(r[i, :n], np.float64)
                    d, m = float(np.abs(g - r).max()), float(np.abs(r).max())
                    gap[h], scale[h] = max(gap[h], d), max(scale[h], m)
                    if d / max(m, 1e-30) > worst[h][0]:
                        worst[h] = (d / max(m, 1e-30), n, m)
    checked = len(kept) - bad
    heads = {h: {"err": gap[h] / max(scale[h], 1e-30) if checked
                 else float("inf"),
                 "gap": gap[h], "scale": scale[h],
                 "sentence": dict(zip(("err", "length", "scale"), worst[h]))}
             for h in ref.HEADS}
    return {"heads": heads, "checked": checked, "bad_answers": bad,
            "unanswered": unanswered}
