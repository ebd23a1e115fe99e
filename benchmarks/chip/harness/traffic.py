"""The one traffic generator: it reads a mix's parameters from
``traffic/<name>.json`` and draws requests from the run's seed.

An open-loop mix gives every seed the same sizes and inter-arrival gaps,
taken at stratified quantiles of its distributions, in one order that
all seeds share (drawn from ``SCHEDULE``, not from the seed); the seed
draws the token contents. Near the knee the order alone decides the
tail: on one TPU v5e, seeds that differed only in order read a chat TTFT
p95 from 360 to 1,060 ms where two runs of one seed agreed within a
tenth. A closed-loop mix sends its sentence pool in an order drawn from
the seed; there the order does not move the rate.

Distributions of a length (``{"dist": ...}``):
  lognormal   ``median``, ``sigma``, clipped to [``min``, ``max``]
  loguniform  log-uniform on [``min``, ``max``]
  uniform     uniform on [``min``, ``max``]
Arrivals: ``open_poisson`` (Poisson at ``rate_per_s``) or ``closed_bursts``
(``burst`` requests at once, the next burst when all are done).
"""
from __future__ import annotations

import math
import zlib
from typing import List

import numpy as np

from harness.corpus import gec_sentences

_MASK32 = (1 << 32) - 1
SCHEDULE = 0        # the seed of the order that every run's schedule shares


def rng_for(seed: int, salt: str) -> np.random.Generator:
    """A generator for one use (``salt``) of the run's seed. Seeds of any
    size are taken whole: no two seeds below 2**64 share a stream."""
    seed %= 1 << 64
    return np.random.default_rng(
        [seed & _MASK32, seed >> 32, zlib.crc32(salt.encode())])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` integer lengths at stratified quantiles of ``spec``."""
    u = _quantiles(n)
    lo, hi = spec["min"], spec["max"]
    dist = spec["dist"]
    if dist == "lognormal":
        from statistics import NormalDist
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif dist == "loguniform":
        x = np.exp(np.log(lo) + u * (np.log(hi) - np.log(lo)))
    elif dist == "uniform":
        x = lo + u * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def request_count(traffic: dict, seconds: float) -> int:
    """Requests an open-loop mix offers in ``seconds``."""
    return max(1, math.ceil(traffic["rate_per_s"] * seconds))


def arrivals(traffic: dict, seconds: float) -> np.ndarray:
    """Due times (seconds from the window's start) of an open-loop mix:
    Poisson arrivals at ``rate_per_s``, their gaps at stratified quantiles
    of the exponential distribution, in the shared order."""
    n = request_count(traffic, seconds)
    gaps = rng_for(SCHEDULE, "arrivals").permutation(
        -np.log1p(-_quantiles(n)) / traffic["rate_per_s"])
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def prompts(traffic: dict, n: int, vocab_size: int, rng) -> List[np.ndarray]:
    """``n`` prompts of the mix's stratified lengths, in the shared order,
    with token ids drawn from ``rng``."""
    lens = rng_for(SCHEDULE, "prompts").permutation(
        lengths(traffic["prompt"], n))
    return [rng.integers(0, vocab_size, int(k), dtype=np.int32)
            for k in lens]


def output_budgets(traffic: dict, n: int) -> np.ndarray:
    """Served-token budgets of ``n`` requests, stratified, in the shared
    order."""
    return rng_for(SCHEDULE, "budgets").permutation(
        lengths(traffic["output"], n))


def sentence_pool(traffic: dict, vocab_size: int, rng) -> List[np.ndarray]:
    """The mix's pool of GEC sentences (the same pool for every seed: the
    corpus has a seed of its own), in an order drawn from ``rng``."""
    c = traffic["corpus"]
    pool = gec_sentences(c["pool"], vocab_size=vocab_size,
                         edit_words=c["edit_words"], mean_len=c["mean_len"],
                         error_rate=c["error_rate"], seed=c["seed"])
    return [pool[i] for i in rng.permutation(len(pool))]
