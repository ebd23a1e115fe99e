"""Seeds and the lower precision of the correctness control."""
from __future__ import annotations

import jax
import jax.numpy as jnp

E4M3_MAX = 448.0


def seed_key(seed: int):
    """A PRNG key for any non-negative seed below 2**63: the low 32 bits
    make the key and the rest is folded in, so seeds that differ only above
    bit 31 still get different keys."""
    seed %= 1 << 63
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def fp8_round(t):
    """``t`` rounded to float8 (e4m3) under one scale for the whole tensor
    that maps its largest magnitude to e4m3's largest, back in float32: the
    precision one step below bfloat16 that a quantized path would serve."""
    t = t.astype(jnp.float32)
    amax = jnp.max(jnp.abs(t))
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (t / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
