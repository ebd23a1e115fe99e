"""What a measured window saw, as the metric readers get it.

Times are host ``perf_counter`` seconds. A request's ``due`` is when the
traffic wanted it sent, so latencies read from it include any lateness of
the generator and any stall that delayed the send.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Request:
    due: float                     # when the traffic wanted it sent
    length: int                    # prompt or sentence tokens
    sent: float = 0.0              # when the client handed it over
    first: Optional[float] = None  # first token (decoder) / result (encoder)
    done: Optional[float] = None   # last token (decoder) / result (encoder)
    n_out: int = 0                 # tokens served / logit rows returned
    budget: int = 0                # tokens asked for (decoder)
    token_times: Optional[List[float]] = None
    queue_s: Optional[float] = None      # the engine's own span
    prefill_s: Optional[float] = None    # the engine's own span
    error: Optional[str] = None
    keep: Any = None               # the answer, when sampled for the check


@dataclasses.dataclass
class Check:
    """One number that decides ``correct``: it passes at or under ``limit``."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value) and self.value <= self.limit)


@dataclasses.dataclass
class Window:
    conf: dict
    traffic: dict
    reference: Any                 # the configuration's reference module
    peaks: dict
    t0: float                      # window start
    t1: float                      # window end
    requests: List[Request]
    engine: Dict[str, Any]         # engine.window() over [t0, t1]
    setup_s: float = 0.0
    compiles: int = 0              # programs lowered inside the window
    trace: Any = None              # trace.TraceSummary of the traced span
    trace_t0: float = 0.0
    trace_t1: float = 0.0
    trace_engine: Optional[Dict[str, Any]] = None  # engine.window() there

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def finished(self) -> List[Request]:
        return [r for r in self.requests
                if r.error is None and r.done is not None]


def p95(values) -> Optional[float]:
    """95th percentile (numpy's linear interpolation); None when empty."""
    values = [v for v in values if v is not None]
    return float(np.percentile(values, 95)) if values else None
