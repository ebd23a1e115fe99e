"""Single-step GQA decode attention over a (ring-buffered) KV cache.

Grid: (batch*kv_heads, kv_blocks). Each program attends the G grouped query
heads of one kv head against one KV block; running (m, l, acc) state sits in
VMEM scratch across the KV sweep. Validity comes from the cache's absolute
position buffer (pos < 0 = empty slot), so ring-buffer wraparound and
sliding windows fall out of the same mask.

Block skipping: the validity mask is a cheap (1, bk) VPU computation on the
already-resident position block, so it is evaluated *first* and the two
``dot_general``s (the expensive part) run under ``pl.when(any live)``. A
short request in a long cache — the dominant serving shape — then pays for
ceil(len/bk) blocks instead of the full ring sweep, and sliding-window
decode pays O(window) regardless of cache length. Exact: a fully-dead block
contributed p = 0 after masking anyway (see flash_attention.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(qpos_ref, q_ref, k_ref, v_ref, kvpos_ref, o_ref, vis_ref,
            m_ref, l_ref, acc_ref, cnt_ref, *, scale, window, softcap, n_kv):
    bh = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        cnt_ref[0] = 0

    # mask first: (1, bk) vector ops on the resident position block — if no
    # kv slot in this block is live, skip both dot_generals entirely
    q_pos = qpos_ref[bh]                           # scalar, prefetched to SMEM
    kv_pos = kvpos_ref[0]                          # (1, bk)
    mask = (kv_pos >= 0) & (kv_pos <= q_pos)
    if window is not None:
        mask &= kv_pos > q_pos - window

    @pl.when(jnp.any(mask))
    def _live():
        q = q_ref[0]                                   # (G, D)
        k = k_ref[0]                                   # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(mask, s, NEG_INF)                # (G, bk)

        m_prev = m_ref[...]                            # (G, 1)
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

    @pl.when(ki == n_kv - 1)
    def _done():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)
        vis_ref[...] = jnp.full(vis_ref.shape, cnt_ref[0], jnp.int32)


@functools.partial(jax.jit, static_argnames=(
    "window", "softcap", "bk", "interpret", "return_visits"))
def decode_attention(q, k, v, q_pos, kv_pos, *, interpret, window=None,
                     softcap=None, bk=128, return_visits=False):
    """q: (BHkv, G, D); k/v: (BHkv, L, D); q_pos: (BHkv, 1) int32;
    kv_pos: (BHkv, L) int32 (-1 = empty). L % bk == 0. -> (BHkv, G, D);
    with ``return_visits`` also an int32 (BHkv, 1) count of KV blocks whose
    dot_generals actually ran.

    TPU layout: ``q_pos`` is scalar-prefetched into SMEM, the position
    buffer is viewed as (BHkv, 1, L) so each (1, bk) block is lane-aligned,
    and the visit counter leaves as a lane-wide (1, 128) row per program."""
    BHkv, G, D = q.shape
    L = k.shape[1]
    n_kv = L // bk
    kern = functools.partial(_kernel, scale=D ** -0.5, window=window,
                             softcap=softcap, n_kv=n_kv)
    out, visits = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BHkv, n_kv),
            in_specs=[
                pl.BlockSpec((1, G, D), lambda bh, ki, qp: (bh, 0, 0)),
                pl.BlockSpec((1, bk, D), lambda bh, ki, qp: (bh, ki, 0)),
                pl.BlockSpec((1, bk, D), lambda bh, ki, qp: (bh, ki, 0)),
                pl.BlockSpec((1, 1, bk), lambda bh, ki, qp: (bh, 0, ki)),
            ],
            out_specs=[
                pl.BlockSpec((1, G, D), lambda bh, ki, qp: (bh, 0, 0)),
                pl.BlockSpec((1, 1, 128), lambda bh, ki, qp: (bh, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),      # running max
                pltpu.VMEM((G, 1), jnp.float32),      # running denominator
                pltpu.VMEM((G, D), jnp.float32),      # output accumulator
                pltpu.SMEM((1,), jnp.int32),          # live-block visit counter
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((BHkv, G, D), q.dtype),
            jax.ShapeDtypeStruct((BHkv, 1, 128), jnp.int32),
        ],
        interpret=interpret,
    )(q_pos.reshape(BHkv).astype(jnp.int32), q, k, v,
      kv_pos.reshape(BHkv, 1, L))
    if return_visits:
        return out, visits[:, :, 0]
    return out
