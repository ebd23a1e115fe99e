"""Dequant-fused int8 matmul — quantized weights without a float copy.

Same VMEM-tiled grid as ``cache_matmul`` (one (bm, bk) activation block,
one (bk, bn) weight block and the (bm, bn) fp32 accumulator resident
across the K sweep), but the weight block arrives as int8 and the
per-output-channel scales are applied once, at the accumulator, on the
final K step. int8 values fit bf16/fp32 exactly (|q| <= 127), so casting
the block inside the kernel loses nothing and

    (x @ (q * s_col)) == (x @ q) * s_col

makes the late scale multiply mathematically identical to dequantizing
up front — with the weight operand at half/quarter the HBM traffic and
no materialized dequantized copy anywhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def vmem_bytes(bm, bn, bk, in_dtype=jnp.bfloat16):
    isz = jnp.dtype(in_dtype).itemsize
    # x block + int8 w block + scale row + fp32 accumulator
    return bm * bk * isz + bk * bn * 1 + bn * 4 + bm * bn * 4


def _kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, n_k):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32),
                            w_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _done():
        o_ref[...] = (acc_ref[...] * s_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def int8_matmul(x, qw, scale, *, interpret, bm=128, bn=128, bk=128):
    """x: (M, K) float @ qw: (K, N) int8, scale: (N,) f32 -> (M, N) x.dtype.

    Scales are broadcast as a (1, bn) block per N tile and applied at the
    fp32 accumulator on the last K step. M/N/K must be divisible by the
    block shape (pad at the ops layer).
    """
    M, K = x.shape
    K2, N = qw.shape
    assert K == K2 and scale.shape == (N,)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0
    n_k = K // bk
    grid = (M // bm, N // bn, n_k)
    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bk, bn), lambda mi, ni, ki: (ki, ni)),
            pl.BlockSpec((1, bn), lambda mi, ni, ki: (0, ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, qw, scale.reshape(1, N).astype(jnp.float32))
