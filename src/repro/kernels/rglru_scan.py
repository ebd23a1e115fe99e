"""RG-LRU linear-scan Pallas kernel: h_t = a_t * h_{t-1} + b_t.

Griffin implements this recurrence as a fused sequential CUDA kernel; the
TPU adaptation streams (seq_block x width) tiles through VMEM with the
carried state h held in VMEM scratch across the sequential seq-block grid
dimension — the within-tile loop is over rows (time), vectorized across the
width lanes (W is a multiple of 128 for every assigned config).

Used for decode/long-context serving of recurrentgemma; training/prefill
use the XLA `associative_scan` path (log-depth, better for long S on the
MXU-free part of the chip) — both are validated against kernels/ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, o_ref, h_ref, *, bs):
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[0]                                    # (bs, W)
    b = b_ref[0]

    def step(t, h):
        h = a[t] * h + b[t]
        o_ref[0, t, :] = h
        return h

    h_ref[...] = jax.lax.fori_loop(0, bs, step, h_ref[...])


@functools.partial(jax.jit, static_argnames=("bs", "interpret"))
def rglru_scan(a, b, *, interpret, bs=256):
    """a, b: (B, S, W) float32, S % bs == 0 -> h: (B, S, W)."""
    B, S, W = a.shape
    assert S % bs == 0
    grid = (B, S // bs)
    return pl.pallas_call(
        functools.partial(_kernel, bs=bs),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bs, W), lambda bi, si: (bi, si, 0)),
            pl.BlockSpec((1, bs, W), lambda bi, si: (bi, si, 0)),
        ],
        out_specs=pl.BlockSpec((1, bs, W), lambda bi, si: (bi, si, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, W), jnp.float32),
        scratch_shapes=[pltpu.VMEM((W,), jnp.float32)],  # carried state
        interpret=interpret,
    )(a, b)
