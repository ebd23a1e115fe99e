"""Block-wise online-softmax (flash) attention Pallas kernel.

Grid: (batch*q_heads, q_blocks, kv_blocks); the kv dimension is the minor
(sequential) grid axis, so the fp32 (m, l, acc) running state lives in VMEM
scratch across the KV sweep. GQA is zero-copy: the kv BlockSpec index map
divides the head program id by the group size. Supports causal masking,
sliding windows (gemma2 local layers / windowed-global long-context) and
logit softcap.

Block skipping: each q-block only has a *live* KV-block range
[lo(qi), hi(qi)] — causal masking bounds hi (no KV block strictly above the
diagonal contributes), a sliding window bounds lo. Dead blocks used to be
fetched, scored, and masked to NEG_INF; now the kv grid axis is offset by
lo(qi), dead iterations pin their BlockSpec fetch to a live block (no new
data movement) and a ``pl.when`` guard skips both ``dot_general``s. For
causal+windowed attention the kv axis itself shrinks to O(window/bk)
iterations. Skipping is numerically exact: a fully-masked block contributes
p = exp(NEG_INF - m) = 0 and its one-time garbage (before any live block
raised m above NEG_INF) was already wiped by the corr-rescale, so skipped
and masked sweeps produce bit-identical outputs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _lo_block(qi, *, window, bq, bk):
    """First live kv block for q-block qi (0 when no window)."""
    if window is None:
        return qi * 0
    return jnp.maximum(0, (qi * bq - (window - 1)) // bk)


def _hi_block(qi, *, causal, bq, bk, n_kv):
    """Last live kv block for q-block qi (n_kv-1 when not causal). n_kv
    counts *live* blocks — callers cap it at ceil(kv_len/bk) so fully-pad
    blocks are skipped too."""
    if not causal:
        return qi * 0 + (n_kv - 1)
    return jnp.minimum(n_kv - 1, (qi * bq + bq - 1) // bk)


def n_visited_blocks(*, causal, window, bq, bk, n_kv):
    """Static length of the kv grid axis after skipping. Causal+windowed
    sweeps touch at most ceil((bq + window - 2)/bk) + 1 blocks per q-block;
    everything else keeps the full axis (dead iterations early-out)."""
    if causal and window is not None:
        return min(n_kv, (bq + window - 2) // bk + 2)
    return n_kv


def live_block_counts(sq, skv, *, causal, window, bq, bk, kv_len=None):
    """Reference count of live kv blocks per q-block (host-side oracle for
    the kernel's visit counter). Returns a list of length sq//bq."""
    n_kv = -(-(kv_len or skv) // bk)          # fully-pad blocks are dead
    counts = []
    for qi in range(sq // bq):
        lo = 0 if window is None else max(0, (qi * bq - (window - 1)) // bk)
        hi = n_kv - 1 if not causal else min(n_kv - 1,
                                             (qi * bq + bq - 1) // bk)
        counts.append(max(0, hi - lo + 1))
    return counts


def _kernel(q_ref, k_ref, v_ref, o_ref, vis_ref, m_ref, l_ref, acc_ref,
            cnt_ref, *, scale, causal, window, softcap, bq, bk, n_kv, n_vis,
            kv_len):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    lo = _lo_block(qi, window=window, bq=bq, bk=bk)
    hi = _hi_block(qi, causal=causal, bq=bq, bk=bk, n_kv=n_kv)
    ki_eff = lo + ki                     # logical kv block this step scores

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        cnt_ref[0] = 0

    @pl.when(ki_eff <= hi)
    def _live():
        q = q_ref[0]                                  # (bq, D)
        k = k_ref[0]                                  # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)

        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = ki_eff * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        if kv_len % bk:            # partial tail block: mask pad columns
            mask &= k_pos < kv_len
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                           # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * corr
                        + jax.lax.dot_general(
                            p.astype(v_ref.dtype), v_ref[0],
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new
        cnt_ref[0] = cnt_ref[0] + 1

    @pl.when(ki == n_vis - 1)
    def _done():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)
        vis_ref[...] = jnp.full(vis_ref.shape, cnt_ref[0], jnp.int32)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "bq", "bk", "interpret", "return_visits",
    "kv_len"))
def flash_attention(q, k, v, *, interpret, causal=True, window=None,
                    softcap=None, bq=128, bk=128, return_visits=False,
                    kv_len=None):
    """q: (BH, Sq, D); k/v: (BHkv, Skv, D), BH = BHkv * G. Sq % bq == 0,
    Skv % bk == 0 (pad at the ops layer). ``kv_len`` (static) is the real
    KV length before padding: pad columns are masked out of the softmax
    (they are NOT hidden by the causal mask when causal=False) and
    fully-pad blocks are skipped. Returns (BH, Sq, D) in q.dtype; with
    ``return_visits`` also an int32 (BH, Sq//bq) count of KV blocks
    actually scored per q-block (the block-skipping audit trail), which
    leaves the kernel as a lane-wide (1, 128) row per program so every
    block stays aligned to the TPU's (8, 128) tiling."""
    BH, Sq, D = q.shape
    BHkv, Skv, _ = k.shape
    G = BH // BHkv
    kv_len = Skv if kv_len is None else kv_len
    n_kv = -(-kv_len // bk)                   # live blocks only
    n_q = Sq // bq
    n_vis = n_visited_blocks(causal=causal, window=window, bq=bq, bk=bk,
                             n_kv=n_kv)
    grid = (BH, n_q, n_vis)
    kern = functools.partial(
        _kernel, scale=D ** -0.5, causal=causal, window=window,
        softcap=softcap, bq=bq, bk=bk, n_kv=n_kv, n_vis=n_vis,
        kv_len=kv_len)

    def kv_map(bh, qi, ki):
        # offset by the live range start; dead tail iterations re-fetch the
        # last live block (pinned -> no extra data movement) and early-out
        lo = _lo_block(qi, window=window, bq=bq, bk=bk)
        hi = _hi_block(qi, causal=causal, bq=bq, bk=bk, n_kv=n_kv)
        return (bh // G, jnp.minimum(lo + ki, hi), 0)

    out, visits = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, D), kv_map),
            pl.BlockSpec((1, bk, D), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, 1, 1, 128), lambda bh, qi, ki: (bh, qi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((BH, n_q, 1, 128), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),     # running max
            pltpu.VMEM((bq, 1), jnp.float32),     # running denominator
            pltpu.VMEM((bq, D), jnp.float32),     # output accumulator
            pltpu.SMEM((1,), jnp.int32),          # live-block visit counter
        ],
        interpret=interpret,
    )(q, k, v)
    if return_visits:
        return out, visits[:, :, 0, 0]
    return out
