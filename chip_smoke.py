"""Bring-up check on one TPU chip, through the entry points a user calls.

  python chip_smoke.py

Phases, in order, in this one process (a chip belongs to one process):

  device   print the JAX version and the device; refuse anything but a TPU
  decoder  qwen2-0.5b at published widths, served by ``ServingEngine``
           (continuous, multi-lane, adaptive width tiers): greedy and sampled
           requests across two pad buckets, every result read and checked
  encoder  gector-base at published widths (the paper's model), served by
           ``ServingEngine(mode="encoder")`` on generated GEC sentences
  kernels  the Pallas kernels compiled for the chip (never interpreted) at
           qwen2-0.5b shapes, each against ``kernels/ref.py`` and the XLA path

Weights are random, made from a fixed seed. Each phase prints its wall time,
its compile time and the device's peak bytes so far: set-up facts, not
performance results. Any failed check exits non-zero. The last line of a
passing run is one JSON object naming the device.
"""
from __future__ import annotations

import contextlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.corpus import CorpusConfig, GECCorpus  # noqa: E402
from repro.core.gector import gector_forward, init_gector  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.decode_attention import decode_attention  # noqa: E402
from repro.kernels.flash_attention import (flash_attention,  # noqa: E402
                                           live_block_counts)
from repro.kernels.int8_matmul import int8_matmul  # noqa: E402
from repro.kernels.ops import matmul_q8  # noqa: E402
from repro.models import forward, init_params  # noqa: E402
from repro.models.attention import naive_attention  # noqa: E402
from repro.quant import quantize_leaf  # noqa: E402
from repro.serving import EngineConfig, SamplingParams, ServingEngine  # noqa: E402

SEED = 0
RESULT_TIMEOUT_S = 600
# The engine's first greedy token must be the argmax of a direct forward of
# the same prompt. Weights are random, so two logits can sit closer than the
# bf16 rounding of two differently fused programs; the engine's token then
# passes if its reference logit is within this many logit units of the top.
ARGMAX_TIE_TOL = 0.05
# Outputs against their float32 references, as max |err| / max |ref|: the
# kernels and the engine's encoder compute in bf16 (8 mantissa bits).
REL_TOL = 2e-2


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ----------------------------------------------------------------- device
def device_phase():
    """Print the JAX version and the device; exit non-zero off a TPU."""
    dev = jax.devices()[0]
    print(f"jax_version {jax.__version__}")
    print(f"platform {dev.platform}")
    print(f"device_kind {dev.device_kind}")
    print(f"device_count {len(jax.devices())}")
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"platform={dev.platform!r}")
    return dev


class PhaseClock:
    """Wall time, compile time and peak device bytes of each phase.

    Compile time sums JAX's own trace/lower/compile duration events, so it
    counts what this process compiled, not what the cache served."""

    def __init__(self, device):
        self.device = device
        self._compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self._compile_s += duration

    @contextlib.contextmanager
    def phase(self, name: str):
        c0, t0 = self._compile_s, time.perf_counter()
        yield
        wall = time.perf_counter() - t0
        stats = self.device.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use", "not reported")
        print(f"phase {name}: wall_s={wall!r} "
              f"compile_s={self._compile_s - c0!r} "
              f"peak_bytes_in_use={peak}", flush=True)


# ---------------------------------------------------------------- decoder
def _prompts(rng, vocab, buckets, n):
    """n prompts alternating between the two buckets' length ranges."""
    lo_b, hi_b = buckets
    out = []
    for i in range(n):
        length = (rng.randint(4, lo_b + 1) if i % 2 == 0
                  else rng.randint(lo_b + 1, hi_b + 1))
        out.append(rng.randint(0, vocab, (length,)).astype(np.int32))
    return out


def decoder_phase(cfg, *, buckets=(64, 128), max_batch=4, max_new_tokens=16,
                  n_requests=8, seed=SEED) -> dict:
    """Serve ``n_requests`` through the default decoder path and check every
    result. Returns facts about the run."""
    params = init_params(cfg, jax.random.PRNGKey(seed))
    eng = ServingEngine(cfg, params, EngineConfig(
        mode="decoder", pad_buckets=tuple(buckets), max_batch=max_batch,
        max_new_tokens=max_new_tokens))
    rng = np.random.RandomState(seed)
    prompts = _prompts(rng, cfg.vocab_size, buckets, n_requests)
    # request 1 samples; the rest decode greedily, with mixed budgets
    sampling = [SamplingParams(max_new_tokens=max_new_tokens - (i % 3) * 3)
                for i in range(n_requests)]
    sampling[1] = SamplingParams(max_new_tokens=max_new_tokens,
                                 temperature=0.8, top_k=40, seed=7)
    try:
        t0 = time.perf_counter()
        eng.warmup(sampled=True)
        warmup_s = time.perf_counter() - t0
        eng.window()                              # measured span starts here
        handles = [eng.generate(p, sp) for p, sp in zip(prompts, sampling)]
        results = [h.result(timeout=RESULT_TIMEOUT_S) for h in handles]
        # the first prompt again, alone: greedy decode must not depend on
        # which rows shared its batch
        again = eng.generate(prompts[0], sampling[0]).result(
            timeout=RESULT_TIMEOUT_S)
        win = eng.window()
    finally:
        eng.close()

    for i, (r, sp) in enumerate(zip(results + [again],
                                    sampling + [sampling[0]])):
        toks = np.asarray(r.tokens)
        check(r.finish_reason == "length",
              f"request {i}: finish_reason={r.finish_reason!r}")
        check(len(toks) == sp.max_new_tokens,
              f"request {i}: {len(toks)} tokens, budget {sp.max_new_tokens}")
        check(((toks >= 0) & (toks < cfg.vocab_size)).all(),
              f"request {i}: token outside [0, {cfg.vocab_size})")
    check(np.array_equal(results[0].tokens, again.tokens),
          f"repeated greedy request differs: {results[0].tokens} vs "
          f"{again.tokens}")
    check(win["requests"] == n_requests + 1,
          f"window saw {win['requests']} requests, sent {n_requests + 1}")
    check(win["jit_compiles"] == 0,
          f"{win['jit_compiles']} compiles inside the served window")

    # the engine's first greedy token against a direct forward on the chip
    p0 = prompts[0]
    bucket = min(b for b in buckets if len(p0) <= b)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(p0)] = p0
    logits = jax.jit(lambda prm, t: forward(cfg, prm, tokens=t)[0])(
        params, jnp.asarray(padded))
    row = np.asarray(logits[0, len(p0) - 1, :cfg.vocab_size], np.float32)
    top = int(np.argmax(row))
    first = int(results[0].tokens[0])
    gap = float(row[top] - row[first])
    check(first == top or gap <= ARGMAX_TIE_TOL,
          f"engine's first token {first} vs reference argmax {top}: "
          f"reference logit gap {gap!r} > {ARGMAX_TIE_TOL}")
    srt = np.sort(row)
    return {"requests": n_requests + 1, "warmup_s": warmup_s,
            "first_token": first, "reference_argmax": top,
            "reference_top2_margin": float(srt[-1] - srt[-2]),
            "window": {k: win[k] for k in ("requests", "jit_compiles",
                                           "decode_segments")}}


# ---------------------------------------------------------------- encoder
def encoder_phase(cfg, *, buckets=(32, 64), max_batch=4, n_sentences=8,
                  seed=SEED) -> dict:
    """Serve generated GEC sentences through the encoder path; outputs must
    have the right shape, be finite, and agree with a direct forward."""
    corpus = GECCorpus(CorpusConfig(vocab_size=cfg.vocab_size,
                                    edit_words=256, seed=5))
    params = init_gector(cfg, jax.random.PRNGKey(seed), corpus.vocab)
    n_tags = corpus.vocab.n_tags
    sentences = [src.astype(np.int32) for src, _, _ in corpus.generate(64)
                 if len(src) <= buckets[-1]][:n_sentences]
    check(len(sentences) == n_sentences, "corpus gave too few sentences")

    def head(p, hid, mask):
        hid = hid.astype(jnp.float32)
        return hid @ p["label_head"]["w"], hid @ p["detect_head"]["w"]

    eng = ServingEngine(cfg, params, EngineConfig(
        mode="encoder", pad_buckets=tuple(buckets), max_batch=max_batch),
        head_fn=head)
    try:
        t0 = time.perf_counter()
        eng.warmup()
        warmup_s = time.perf_counter() - t0
        futs = [eng.submit(s) for s in sentences]
        outs = [f.result(timeout=RESULT_TIMEOUT_S) for f in futs]
    finally:
        eng.close()

    # a batch pads to the bucket of its longest sentence
    for i, (s, (tag, det)) in enumerate(zip(sentences, outs)):
        width = tag.shape[0]
        check(width in buckets and width >= len(s)
              and tag.shape == (width, n_tags),
              f"sentence {i} ({len(s)} tokens): tag logits {tag.shape}")
        check(det.shape == (width, 2),
              f"sentence {i}: detect logits {det.shape}")
        check(np.isfinite(tag).all() and np.isfinite(det).all(),
              f"sentence {i}: non-finite logits")

    # sentence 0 against a direct forward at the same padded length
    s0 = sentences[0]
    padded = np.zeros((1, outs[0][0].shape[0]), np.int32)
    padded[0, :len(s0)] = s0
    ref_tag, _ = jax.jit(lambda p, t: gector_forward(cfg, p, t))(
        params, jnp.asarray(padded))
    errs = {}
    _close(errs, "tag_logits_vs_forward", outs[0][0], ref_tag[0])
    return {"sentences": n_sentences, "warmup_s": warmup_s, **errs}


# ---------------------------------------------------------------- kernels
def _close(errs: dict, name: str, out, want) -> None:
    """Record max |out - want| / max |want| under ``name``; fail past
    ``REL_TOL``."""
    out = np.asarray(out, np.float32)
    want = np.asarray(want, np.float32)
    err = float(np.abs(out - want).max() / max(np.abs(want).max(), 1e-30))
    errs[name] = err
    check(err <= REL_TOL, f"{name}: rel err {err!r} > {REL_TOL}")


def kernel_phase(cfg, *, interpret: bool, batch=4, buckets=(128, 512),
                 seed=SEED) -> dict:
    """int8_matmul, decode_attention and flash_attention at ``cfg``'s
    widths and head shapes, each against the float32 reference
    (``kernels/ref.py``, at ``highest`` matmul precision) and the XLA path
    the model runs today."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    errs = {}
    dt = cfg.jdtype

    # int8 weights: the MLP's up (d -> d_ff) and down (d_ff -> d) shapes
    for name, (k_dim, n_dim) in (("up", (cfg.d_model, cfg.d_ff)),
                                 ("down", (cfg.d_ff, cfg.d_model))):
        x = jax.random.normal(next(keys), (128, k_dim), dt)
        leaf = quantize_leaf(
            jax.random.normal(next(keys), (k_dim, n_dim), jnp.float32), 1)
        qw, scale = leaf["qw"], leaf["scale"]
        # published widths are 128-multiples; zero padding covers the rest
        pk, pn = (-k_dim) % 128, (-n_dim) % 128
        out = int8_matmul(jnp.pad(x, ((0, 0), (0, pk))),
                          jnp.pad(qw, ((0, pk), (0, pn))),
                          jnp.pad(scale, (0, pn)),
                          interpret=interpret)[:, :n_dim]
        with jax.default_matmul_precision("highest"):
            want = ref.int8_matmul_ref(x, qw, scale)
        xla = matmul_q8(x, qw, scale)     # QUANT_MATMUL_IMPL="xla", as served
        _close(errs, f"int8_matmul_{name}_vs_ref", out, want)
        _close(errs, f"int8_matmul_{name}_vs_xla", out, xla)

    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    g = hq // hkv
    bk = 128

    # decode attention: one query per row against a bucket-sized KV ring,
    # rows filled to different depths (block skipping must stay exact)
    L = buckets[0] * 2
    q = jax.random.normal(next(keys), (batch, 1, hq, hd), dt)
    k = jax.random.normal(next(keys), (batch, L, hkv, hd), dt)
    v = jax.random.normal(next(keys), (batch, L, hkv, hd), dt)
    valid = np.linspace(1, L, batch).astype(np.int32)
    kv_pos = np.where(np.arange(L)[None, :] < valid[:, None],
                      np.arange(L)[None, :], -1).astype(np.int32)
    q_pos = valid - 1
    qf = q[:, 0].reshape(batch * hkv, g, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(batch * hkv, L, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(batch * hkv, L, hd)
    kvp = jnp.asarray(np.repeat(kv_pos, hkv, axis=0))
    qp = jnp.asarray(np.repeat(q_pos, hkv)[:, None])
    out, vis = decode_attention(qf, kf, vf, qp, kvp, bk=bk,
                                return_visits=True, interpret=interpret)
    with jax.default_matmul_precision("highest"):
        want = ref.decode_attention_ref(qf, kf, vf, qp[:, 0], kvp)
    model = naive_attention(q, k, v, jnp.asarray(q_pos)[:, None],
                            jnp.asarray(kv_pos))[:, 0]
    _close(errs, "decode_attention_vs_ref", out, want)
    _close(errs, "decode_attention_vs_model", out,
           model.reshape(batch * hkv, g, hd))
    want_vis = np.repeat(-(-valid // bk), hkv)
    check(np.array_equal(np.asarray(vis)[:, 0], want_vis),
          f"decode_attention visited {np.asarray(vis)[:, 0]}, "
          f"live blocks {want_vis}")

    # causal prefill attention at each bucket
    for S in buckets:
        q = jax.random.normal(next(keys), (batch * hq, S, hd), dt)
        k = jax.random.normal(next(keys), (batch * hkv, S, hd), dt)
        v = jax.random.normal(next(keys), (batch * hkv, S, hd), dt)
        out, vis = flash_attention(q, k, v, causal=True, bq=128, bk=bk,
                                   return_visits=True, interpret=interpret)
        with jax.default_matmul_precision("highest"):
            want = ref.flash_attention_ref(q, k, v, causal=True)
        pos = jnp.broadcast_to(jnp.arange(S), (batch, S))
        model = naive_attention(
            *(t.reshape(batch, -1, S, hd).transpose(0, 2, 1, 3)
              for t in (q, k, v)), pos, pos)
        _close(errs, f"flash_attention_{S}_vs_ref", out, want)
        _close(errs, f"flash_attention_{S}_vs_model", out,
               model.transpose(0, 2, 1, 3).reshape(batch * hq, S, hd))
        want_vis = live_block_counts(S, S, causal=True, window=None,
                                     bq=128, bk=bk)
        check((np.asarray(vis) == np.array(want_vis)[None, :]).all(),
              f"flash_attention {S}: visits differ from the live range")
    return errs


# ------------------------------------------------------------------- main
def main() -> int:
    dev = device_phase()
    print(f"compile_cache_dir {enable_compile_cache()}")
    clock = PhaseClock(dev)
    qwen = get_config("qwen2-0.5b")
    with clock.phase("decoder"):
        facts = decoder_phase(qwen)
    print(f"decoder {json.dumps(facts)}")
    with clock.phase("encoder"):
        facts = encoder_phase(get_config("gector-base"))
    print(f"encoder {json.dumps(facts)}")
    with clock.phase("kernels"):
        facts = kernel_phase(qwen, interpret=False)
    print(f"kernels {json.dumps(facts)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
