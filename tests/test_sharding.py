"""Distribution layer: partition-spec derivation, divisibility guards,
mesh construction, rule policies — and a tiny-mesh end-to-end jit."""
import numpy as np

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, get_config
from repro.launch.specs import cache_partition_specs
from repro.models import init_params, make_caches
from repro.parallel.sharding import (MeshRules, param_partition_specs,
                                     rules_for, use_rules)


def _mesh11():
    from repro.launch.mesh import make_host_mesh
    return make_host_mesh()


def _fake_rules(mesh=None, **kw):
    return MeshRules(mesh=mesh or _mesh11(), **kw)


@pytest.mark.parametrize("arch", ARCHS)
def test_param_specs_cover_and_divide(arch):
    """Every param leaf gets a spec whose sharded dims divide a 16-way
    model axis / 16-way data axis (checked against full-size configs via
    eval_shape, no allocation)."""
    cfg = get_config(arch)
    shapes = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0)))

    class R:
        class mesh:
            shape = {"data": 16, "model": 16}
        batch_axes = ("data",)
        model_axis = "model"
        shard_attn_heads = cfg.n_heads % 16 == 0
        shard_kv_heads = cfg.n_kv_heads % 16 == 0
        expert_mode = ("tensor" if cfg.moe and cfg.moe.num_experts % 16
                       else "expert")
        zero1 = True

    specs = param_partition_specs(shapes, R())
    leaves_s, _ = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, P))
    leaves_p = jax.tree.leaves(shapes)
    assert len(leaves_s) == len(leaves_p)
    for sds, spec in zip(leaves_p, leaves_s):
        assert len(spec) <= len(sds.shape)
        for dim, ax in zip(sds.shape, tuple(spec)):
            if ax is not None:
                size = np.prod([R.mesh.shape[a] for a in
                                (ax if isinstance(ax, tuple) else (ax,))])
                assert dim % size == 0, (arch, sds.shape, spec)


def test_rules_for_policies():
    mesh = _mesh11()

    class M:  # 16-way model axis stand-in
        shape = {"data": 16, "model": 16}
        size = 256
    # qwen2-0.5b: 14 heads -> attention replicated
    r = rules_for(get_config("qwen2-0.5b"), M())
    assert not r.shard_attn_heads
    # qwen2-moe: 60 experts -> tensor-parallel experts
    r = rules_for(get_config("qwen2-moe-a2.7b"), M())
    assert r.expert_mode == "tensor"
    # moonshot: 64 experts -> expert-parallel
    r = rules_for(get_config("moonshot-v1-16b-a3b"), M())
    assert r.expert_mode == "expert"
    # gemma2: fully shardable
    r = rules_for(get_config("gemma2-27b"), M())
    assert r.shard_attn_heads and r.shard_kv_heads


def test_cache_specs_shard_seq_when_batch_is_one():
    cfg = get_config("gemma2-27b")

    class M:
        shape = {"data": 16, "model": 16}
    rules = MeshRules(mesh=M(), batch_axes=("data",))
    shapes = jax.eval_shape(
        lambda: make_caches(cfg, 1, 524_288, long_ctx=True))
    specs = cache_partition_specs(cfg, shapes, rules, batch=1)
    k_spec = specs["blk0"]["k"]
    assert tuple(k_spec)[1] is None          # batch unsharded
    assert "data" in str(k_spec)             # sequence sharded instead


def test_kv_cache_seq_fallback():
    """kv heads that don't divide the model axis -> cache shards its
    sequence dim over 'model' instead (§Perf iteration A: head_dim sharding
    was refuted — GSPMD all-gathered the fp32 cache for the QK dot)."""
    cfg = get_config("stablelm-12b")          # kv=8 < 16

    class M:
        shape = {"data": 16, "model": 16}
    rules = MeshRules(mesh=M(), batch_axes=("data",))
    shapes = jax.eval_shape(lambda: make_caches(cfg, 128, 32_768))
    specs = cache_partition_specs(cfg, shapes, rules, batch=128)
    k_spec = tuple(specs["blk0"]["k"])
    assert k_spec[2] == "model" and k_spec[3] is None and k_spec[4] is None


def test_shard_activation_noop_without_rules():
    from repro.parallel.sharding import shard_activation
    x = jnp.ones((4, 4))
    assert shard_activation(x, "batch", None) is x


def test_end_to_end_tiny_mesh_jit():
    """Full pipeline under a real (1x1) mesh with rules active."""
    cfg = get_config("gemma2-27b", smoke=True)
    mesh = _mesh11()
    rules = MeshRules(mesh=mesh)
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    with use_rules(rules), mesh:
        from repro.models import forward
        logits, _, _ = jax.jit(
            lambda p, t: forward(cfg, p, tokens=t))(params, toks)
    assert logits.shape == (2, 16, cfg.padded_vocab)
    assert not jnp.isnan(logits).any()


def test_production_mesh_shapes():
    # requires the 512-host-device trick -> only verify the builder logic
    from repro.launch.mesh import make_production_mesh
    if jax.device_count() >= 512:
        m = make_production_mesh(multi_pod=True)
        assert m.shape == {"pod": 2, "data": 16, "model": 16}
    else:
        with pytest.raises(Exception):
            make_production_mesh(multi_pod=True)
