"""The decoder's algorithmic work inside the traced span, counted from the
client's stamps and the engine's counters (per-layer readers share it)."""
from __future__ import annotations

import dataclasses
from typing import Optional

from harness import flops as fl


@dataclasses.dataclass
class Work:
    flops: int
    bytes: int
    prefill_tokens: int
    decode_tokens: int


def decode_work(win) -> Optional[Work]:
    if win.trace is None or win.trace_engine is None:
        return None
    conf = win.conf
    lo, hi = win.trace_t0, win.trace_t1
    flops = prefill_tokens = decode_tokens = kv_tokens = 0
    for r in win.requests:
        if r.queue_s is not None and lo <= r.sent + r.queue_s < hi:
            flops += fl.prefill_flops(conf, r.length)
            prefill_tokens += r.length
            kv_tokens += r.length
        for j, t in enumerate(r.token_times or ()):
            if j and lo <= t < hi:
                flops += fl.decode_flops(conf, r.length + j)
                decode_tokens += 1
                kv_tokens += r.length + j
    eng = win.trace_engine
    steps = (eng.get("decode_segments", 0)
             * win.traffic["engine"]["decode_segment"])
    calls = steps + eng.get("prefill_batches", 0)
    nbytes = (calls * fl.weight_bytes(conf)
              + kv_tokens * fl.kv_bytes_per_token(conf))
    return Work(flops, nbytes, prefill_tokens, decode_tokens)
