"""VMEM-tiled matmul — the paper's cache-locality finding as a TPU kernel.

The paper's headline CPU result is that the machine whose working set fits
processor cache (SRAM) beats machines with 2x the vCPUs ("machine C vs E",
>50 % cost reduction). On TPU the same SRAM-vs-DRAM cliff is VMEM vs HBM.
This kernel tiles C = A @ B so that one (bm x bk), (bk x bn) and the
(bm x bn) fp32 accumulator stay VMEM-resident across the K sweep; block
shapes default to MXU-aligned multiples of 128 and are validated against the
~16 MiB VMEM budget by ``vmem_bytes``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def vmem_bytes(bm, bn, bk, in_dtype=jnp.bfloat16):
    isz = jnp.dtype(in_dtype).itemsize
    return bm * bk * isz + bk * bn * isz + bm * bn * 4  # fp32 accumulator


def _kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def cache_matmul(x, w, *, interpret, bm=128, bn=128, bk=128):
    """x: (M, K) @ w: (K, N) -> (M, N) in x.dtype, fp32 accumulation.

    M/N/K must be divisible by the block shape (pad at the ops layer).
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2 and M % bm == 0 and N % bn == 0 and K % bk == 0
    n_k = K // bk
    grid = (M // bm, N // bn, n_k)
    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bk, bn), lambda mi, ni, ki: (ki, ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        # fp32 accumulator lives in VMEM across the K sweep
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w)
