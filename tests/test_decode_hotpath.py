"""Decode hot path (PR: block-skipping flash attention, fused scan decode,
pooled KV caches): kernel skipping is exact and actually skips, the fused
scan path is token-identical to the seed's per-token loop, pool slots don't
leak state, and the engine rejects instead of truncating."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import (flash_attention,
                                           live_block_counts,
                                           n_visited_blocks)
from repro.kernels.ref import decode_attention_ref, flash_attention_ref
from repro.models import init_params
from repro.serving import (CachePool, EngineConfig, RequestTooLong,
                           ServingEngine)

R = jax.random.PRNGKey


# ------------------------------------------------- block-skipping kernels
@pytest.mark.parametrize("causal,window,softcap", [
    (True, None, None),
    (True, 64, None),          # window aligned to bk
    (True, 40, None),          # window NOT aligned to bk (partial blocks)
    (True, 100, 30.0),         # non-aligned + softcap
    (False, None, None),
    (False, 96, None),         # windowed non-causal: lo-skip only
])
def test_flash_block_skipping_matches_ref(causal, window, softcap):
    S, bq, bk = 256, 64, 64
    q = jax.random.normal(R(0), (4, S, 32), jnp.float32)
    k = jax.random.normal(R(1), (2, S, 32), jnp.float32)
    v = jax.random.normal(R(2), (2, S, 32), jnp.float32)
    out, vis = flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, bq=bq, bk=bk,
                               return_visits=True, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window,
                              softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # the kernel's runtime visit counter must equal the analytic live range
    exp = live_block_counts(S, S, causal=causal, window=window, bq=bq, bk=bk)
    assert (np.asarray(vis) == np.array(exp)[None, :]).all()


def test_flash_causal_visits_about_half():
    """Acceptance: causal flash attention scores ~half the KV blocks the
    seed's full sweep visited."""
    S, bq, bk = 512, 64, 64
    q = jax.random.normal(R(0), (2, S, 32), jnp.float32)
    k = jax.random.normal(R(1), (2, S, 32), jnp.float32)
    v = jax.random.normal(R(2), (2, S, 32), jnp.float32)
    _, vis = flash_attention(q, k, v, causal=True, bq=bq, bk=bk,
                             return_visits=True, interpret=True)
    total = (S // bq) * (S // bk)          # what the seed always visited
    visited = int(np.asarray(vis)[0].sum())
    assert visited == total * (1 + S // bk) / (2 * S // bk)  # 36 of 64
    assert visited <= 0.6 * total


def test_flash_windowed_grid_shrinks():
    """Causal+windowed attention shrinks the kv grid axis itself to
    O(window/bk) — dead blocks are not even iterated."""
    S, bq, bk, window = 512, 64, 64, 64
    assert n_visited_blocks(causal=True, window=window, bq=bq, bk=bk,
                            n_kv=S // bk) == 3
    assert n_visited_blocks(causal=True, window=None, bq=bq, bk=bk,
                            n_kv=S // bk) == S // bk


def test_decode_attention_early_out():
    """A short request in a long ring buffer only pays for the live blocks;
    sliding windows bound the sweep regardless of cache length."""
    BHkv, G, D, L, bk = 4, 2, 32, 256, 64
    q = jax.random.normal(R(0), (BHkv, G, D), jnp.float32)
    k = jax.random.normal(R(1), (BHkv, L, D), jnp.float32)
    v = jax.random.normal(R(2), (BHkv, L, D), jnp.float32)
    for valid, window, want in [(17, None, 1), (120, None, 2),
                                (256, None, 4), (120, 40, 1)]:
        kv_pos = jnp.where(jnp.arange(L)[None, :] < valid,
                           jnp.arange(L)[None, :], -1).astype(jnp.int32)
        kv_pos = jnp.broadcast_to(kv_pos, (BHkv, L))
        q_pos = jnp.full((BHkv, 1), valid - 1, jnp.int32)
        out, vis = decode_attention(q, k, v, q_pos, kv_pos, window=window,
                                    bk=bk, return_visits=True,
                                    interpret=True)
        ref = decode_attention_ref(q, k, v, q_pos[:, 0], kv_pos,
                                   window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        assert (np.asarray(vis) == want).all()


def test_attn_block_size_heuristic_and_override():
    # heuristic: blocks shrink toward the sequence / window
    assert ops.attn_block_sizes("prefill", 2048, 2048) == (128, 128)
    assert ops.attn_block_sizes("prefill", 30, 30) == (32, 32)
    bq, bk = ops.attn_block_sizes("prefill", 2048, 2048, window=40)
    assert bk == 64
    assert ops.attn_block_sizes("decode", 1, 48)[1] == 64
    # a registered (autotuned) entry wins over the heuristic
    ops.register_attn_block_sizes("prefill", 2048, 2048, None, 32, 16)
    try:
        assert ops.attn_block_sizes("prefill", 2048, 2048) == (32, 16)
    finally:
        ops._ATTN_BLOCK_TABLE.clear()
    # heuristic block sizes stay correct through the padded ops wrapper
    B, S, H, D = 1, 200, 4, 16
    q = jax.random.normal(R(3), (B, S, H, D), jnp.float32)
    k = jax.random.normal(R(4), (B, S, 2, D), jnp.float32)
    v = jax.random.normal(R(5), (B, S, 2, D), jnp.float32)
    out = ops.mha_prefill(q, k, v, window=40)
    ref = flash_attention_ref(
        q.transpose(0, 2, 1, 3).reshape(B * H, S, D),
        k.transpose(0, 2, 1, 3).reshape(B * 2, S, D),
        v.transpose(0, 2, 1, 3).reshape(B * 2, S, D),
        window=40).reshape(B, H, S, D).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal,window", [(False, None), (False, 96),
                                           (True, None)])
def test_mha_prefill_padded_kv_masked(causal, window):
    """Padded KV columns must never receive softmax mass — the causal mask
    alone does not hide them when causal=False (kv_len masking in the
    kernel does)."""
    B, S, H, D = 1, 200, 4, 16          # pads to a block multiple
    q = jax.random.normal(R(0), (B, S, H, D), jnp.float32)
    k = jax.random.normal(R(1), (B, S, 2, D), jnp.float32)
    v = jax.random.normal(R(2), (B, S, 2, D), jnp.float32)
    out = ops.mha_prefill(q, k, v, causal=causal, window=window)
    ref = flash_attention_ref(
        q.transpose(0, 2, 1, 3).reshape(B * H, S, D),
        k.transpose(0, 2, 1, 3).reshape(B * 2, S, D),
        v.transpose(0, 2, 1, 3).reshape(B * 2, S, D),
        causal=causal, window=window).reshape(B, H, S, D).transpose(
            0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# ------------------------------------------------------ fused scan decode
def test_scan_decode_matches_seed_loop():
    """The fused prefill+scan path must produce token-for-token identical
    output to the seed's per-token Python loop (use_scan_decode=False
    reproduces the seed structure exactly, scanned periods included)."""
    cfg = get_config("qwen2-0.5b", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, cfg.vocab_size, (rng.randint(3, 12),))
               for _ in range(3)]
    outs = {}
    for scan, pool in [(False, False), (True, True)]:
        eng = ServingEngine(cfg, params, EngineConfig(
            mode="decoder", max_batch=4, max_new_tokens=3,
            pad_buckets=(16,), use_scan_decode=scan, use_cache_pool=pool))
        try:
            futs = [eng.submit(p) for p in prompts]
            outs[scan] = np.stack([f.result(timeout=300) for f in futs])
        finally:
            eng.close()
    assert (outs[False] == outs[True]).all()


# ----------------------------------------------------------- cache pool
def test_cache_pool_acquire_resets_and_isolates():
    cfg = get_config("qwen2-0.5b", smoke=True)
    pool = CachePool(cfg, n_slots=4, max_len=16, dtype=jnp.float32)
    slots, view = pool.acquire(["a", "b"])
    assert len(slots) == 2 and pool.free_slots == 2
    # dirty everything, release, re-acquire: slots must come back clean
    pool.caches = jax.tree.map(lambda x: x + 1, pool.caches)
    pool.release_many(slots)
    slots2, view2 = pool.acquire(["c", "d", "e"])
    assert pool.free_slots == 1
    pos = np.asarray(view2["blk0"]["pos"])
    assert (pos == -1).all()                   # sentinel restored
    assert (np.asarray(view2["blk0"]["k"]) == 0).all()
    # unassigned slot keeps its (dirty) state — reset is per-assignment
    spare = [i for i in range(4) if i not in slots2][0]
    assert (np.asarray(pool.caches["blk0"]["pos"][:, spare]) == 0).all()


def test_cache_pool_write_back_persists():
    cfg = get_config("qwen2-0.5b", smoke=True)
    pool = CachePool(cfg, n_slots=4, max_len=16, dtype=jnp.float32)
    slots, view = pool.acquire(["a", "b"])
    view = jax.tree.map(lambda x: x + 2, view)
    pool.write_back(slots, view, lengths=[5, 7])
    got = np.asarray(pool.caches["blk0"]["pos"][:, slots])
    assert (got == 1).all()                    # -1 + 2
    assert pool.lengths[slots[0]] == 5 and pool.lengths[slots[1]] == 7


# ------------------------------------------------------- engine behaviour
def test_engine_rejects_too_long_requests():
    cfg = get_config("gector-base", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, EngineConfig(
        mode="encoder", max_batch=4, pad_buckets=(16, 32)))
    try:
        fut = eng.submit(np.zeros(33, np.int32))    # > largest bucket
        with pytest.raises(RequestTooLong):
            fut.result(timeout=30)
        ok = eng.submit(np.zeros(20, np.int32))     # still serves valid ones
        assert ok.result(timeout=120).shape[0] == 32
    finally:
        eng.close()


def test_admission_no_thread_per_request_and_nonblocking_submit():
    """Admission control must not spawn a dispatcher thread per request,
    and a saturated engine must not block submit() — excess requests park
    on the overflow queue whose true depth shows up in the stats."""
    cfg = get_config("gector-base", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, EngineConfig(
        mode="encoder", max_batch=4, pad_buckets=(32,), max_inflight=2))
    try:
        fut = eng.submit(np.zeros(8, np.int32))     # warm the compile cache
        fut.result(timeout=120)
        base = threading.active_count()
        peak = base
        futs = []
        t0 = time.perf_counter()
        for _ in range(8):
            futs.append(eng.submit(np.zeros(8, np.int32)))
            peak = max(peak, threading.active_count())
        submit_wall = time.perf_counter() - t0      # all 8 fired at once
        for f in futs:
            f.result(timeout=120)
        assert peak <= base                         # no per-request threads
        assert submit_wall < 1.0                    # submit never blocked
        m = eng.metrics()
        assert m["requests"] == 9
        assert m["admission_peak_queue"] >= 2       # true overflow depth
    finally:
        eng.close()
