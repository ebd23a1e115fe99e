"""Compile the main path for a TPU v5e chip that is described, not attached.

The kernels and the serving programs are compiled at published widths
(qwen2-0.5b, gector-base) from shapes alone, so a tiling the chip's
compiler refuses, or a program that does not fit 16 GB, fails here at no
chip cost. Nothing runs. The topology is described inside a fixture: only
the worker given this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.gector import gector_forward, init_gector
from repro.core.tags import TagVocab
from repro.kernels.cache_matmul import cache_matmul
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.int8_matmul import int8_matmul
from repro.models import decode_segment, forward, init_params, make_caches

V5E_HBM_BYTES = 16 * 2**30
BATCH, NEW_TOKENS = 4, 16          # chip_smoke.py's engine shape


@pytest.fixture(scope="module")
def topo():
    """The v5e:2x2 topology, with JAX's persistent compilation cache off:
    what is compiled for a described chip cannot be read back here."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topology = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topology
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct factory placed on one described chip; also maps a
    pytree of shapes onto it."""
    one = SingleDeviceSharding(topo.devices[0])

    def make(shape_or_tree, dtype=None):
        if dtype is not None:
            return jax.ShapeDtypeStruct(shape_or_tree, dtype, sharding=one)
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            shape_or_tree)
    return make


def _compile(fn, *args, **jit_kw):
    return jax.jit(fn, **jit_kw).lower(*args).compile()


def _fits_one_chip(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert used < V5E_HBM_BYTES, used


# ----------------------------------------------------------------- kernels
@pytest.mark.parametrize("k_dim,n_dim", [(896, 4864), (4864, 896)],
                         ids=["mlp_up", "mlp_down"])
def test_int8_matmul_compiles(spec, k_dim, n_dim):
    c = _compile(lambda x, w, s: int8_matmul(x, w, s, interpret=False),
                 spec((128, k_dim), jnp.bfloat16),
                 spec((k_dim, n_dim), jnp.int8),
                 spec((n_dim,), jnp.float32))
    assert "tpu_custom_call" in c.as_text()


def test_cache_matmul_compiles(spec):
    c = _compile(lambda x, w: cache_matmul(x, w, interpret=False),
                 spec((128, 896), jnp.bfloat16),
                 spec((896, 4864), jnp.bfloat16))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("bucket", [128, 512])
def test_decode_attention_compiles(spec, bucket):
    """qwen2-0.5b heads (14 query, 2 KV, dim 64) over a bucket's KV ring,
    padded to the 128-position block."""
    L = -(-(bucket + NEW_TOKENS) // 128) * 128
    bh = BATCH * 2
    c = _compile(
        lambda q, k, v, qp, kp: decode_attention(
            q, k, v, qp, kp, bk=128, return_visits=True, interpret=False),
        spec((bh, 7, 64), jnp.bfloat16), spec((bh, L, 64), jnp.bfloat16),
        spec((bh, L, 64), jnp.bfloat16), spec((bh, 1), jnp.int32),
        spec((bh, L), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("bucket", [128, 512])
def test_flash_attention_compiles(spec, bucket):
    c = _compile(
        lambda q, k, v: flash_attention(q, k, v, causal=True, bq=128,
                                        bk=128, return_visits=True,
                                        interpret=False),
        spec((BATCH * 14, bucket, 64), jnp.bfloat16),
        spec((BATCH * 2, bucket, 64), jnp.bfloat16),
        spec((BATCH * 2, bucket, 64), jnp.bfloat16))
    assert "tpu_custom_call" in c.as_text()


# -------------------------------------------------- serving programs, full
@pytest.fixture(scope="module")
def qwen(spec):
    cfg = get_config("qwen2-0.5b")
    params = spec(jax.eval_shape(lambda: init_params(cfg,
                                                     jax.random.PRNGKey(0))))
    caches = spec(jax.eval_shape(lambda: make_caches(
        cfg, BATCH, 128 + NEW_TOKENS, dtype=jnp.float32)))
    return cfg, params, caches


def test_qwen_prefill_compiles(spec, qwen):
    """The engine's prefill-into-slot program at bucket 128."""
    cfg, params, caches = qwen

    def prefill(p, toks, c):
        return forward(cfg, p, tokens=toks, caches=c, mode="full")[:2]

    _fits_one_chip(_compile(prefill, params,
                            spec((BATCH, 128), jnp.int32), caches))


def test_qwen_decode_segment_compiles(spec, qwen):
    """The engine's decode segment (4 steps, caches donated)."""
    cfg, params, caches = qwen

    def seg(p, tok, pos, c, active, budget, eos):
        return decode_segment(cfg, p, tok, pos, c, n_steps=4, active=active,
                              budget=budget, eos_id=eos)

    _fits_one_chip(_compile(
        seg, params, spec((BATCH, 1), jnp.int32), spec((BATCH, 1), jnp.int32),
        caches, spec((BATCH,), jnp.bool_), spec((BATCH,), jnp.int32),
        spec((BATCH,), jnp.int32), donate_argnums=3))


def test_gector_encoder_compiles(spec):
    cfg = get_config("gector-base")
    params = spec(jax.eval_shape(lambda: init_gector(
        cfg, jax.random.PRNGKey(0), TagVocab(256))))
    _fits_one_chip(_compile(lambda p, t: gector_forward(cfg, p, t), params,
                            spec((BATCH, 64), jnp.int32)))
