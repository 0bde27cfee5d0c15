"""Sharding rules: divisibility fallback, role assignment, cache specs.

Uses AbstractMesh — no devices needed, so this runs on the 1-CPU test env
while exercising the production 16x16 and 2x16x16 topologies.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

import repro.configs as C
from repro.models import LM
from repro.sharding import param_specs, batch_spec_tree, cache_spec_tree
from repro.sharding.rules import _pick

POD = AbstractMesh((16, 16), ("data", "model"))
MULTI = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _check_divisible(specs, tree):
    ms = {"pod": 2, "data": 16, "model": 16}
    ok = []

    def one(spec, leaf):
        for dim, axes in enumerate(spec):
            if axes is None:
                continue
            group = axes if isinstance(axes, tuple) else (axes,)
            n = 1
            for a in group:
                n *= ms[a]
            assert leaf.shape[dim] % n == 0, (spec, leaf.shape)

    jax.tree.map(one, specs, jax.tree.map(lambda x: x, tree),
                 is_leaf=lambda x: isinstance(x, P))


@pytest.mark.parametrize("arch", C.ASSIGNED)
@pytest.mark.parametrize("mesh", [POD, MULTI], ids=["pod", "multipod"])
def test_param_specs_divisible_full_configs(arch, mesh):
    cfg = C.get(arch)
    lm = LM(cfg)
    params = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
    specs = param_specs(params, mesh)
    _check_divisible(specs, params)


@pytest.mark.parametrize("arch", ["deepseek-67b", "deepseek-v3-671b", "rwkv6-7b"])
def test_cache_specs_divisible(arch):
    cfg = C.get(arch)
    lm = LM(cfg)
    cache = jax.eval_shape(lambda: lm.init_cache(128, 32768))
    specs = cache_spec_tree(cache, POD)
    _check_divisible(specs, cache)


def test_long_context_cache_shards_sequence():
    """batch=1 cell: the KV cache must shard its sequence dim over DP."""
    cfg = C.get("h2o-danube-1.8b")
    lm = LM(cfg)
    cache = jax.eval_shape(lambda: lm.init_cache(1, 524288))
    specs = cache_spec_tree(cache, POD)
    k_spec = specs["layers"]["k"]
    # [L, B=1, S, KvH, hd]: B can't shard over 16 -> S must
    assert k_spec[2] is not None


def test_expert_dim_sharded_full_mesh():
    """DeepSeek-V3: 256 experts = ("data","model") on the 16x16 pod."""
    cfg = C.get("deepseek-v3-671b")
    lm = LM(cfg)
    params = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
    specs = param_specs(params, POD)
    qspec = specs["moe_blocks"]["moe"]["gate"]["q"].qweight
    # [L, E, Kp, N] -> E sharded over the full mesh
    assert qspec[1] == ("data", "model")


def test_mixtral_experts_fall_back_to_tp():
    cfg = C.get("mixtral-8x22b")
    lm = LM(cfg)
    params = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
    specs = param_specs(params, POD)
    qspec = specs["blocks"]["moe"]["gate"]["q"].qweight
    # 8 experts can't shard 16 ways -> expert dim replicated, d_ff sharded
    assert qspec[1] is None
    assert qspec[3] == "model"


def test_megatron_pairing():
    """wq col-parallel, wo row-parallel, adapters follow their base."""
    cfg = C.get("deepseek-67b")
    lm = LM(cfg)
    params = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
    specs = param_specs(params, POD)
    attn = specs["blocks"]["attn"]
    assert attn["wq"]["q"].qweight[-1] == "model"       # col
    assert attn["wo"]["q"].qweight[-2] == "model"       # row
    assert attn["wq"]["ad"].b[-1] == "model"            # B with output dim
    assert attn["wo"]["ad"].a[-2] == "model"            # A with input groups


def test_pick_falls_back_to_replication():
    spec = _pick([( "model",), ("data",)], (7,), {"data": 16, "model": 16})
    assert spec == P()


def test_batch_specs_dp():
    batch = {"tokens": jax.ShapeDtypeStruct((256, 4096), jnp.int32)}
    s = batch_spec_tree(batch, MULTI)
    assert s["tokens"][0] == ("pod", "data")
    # batch=1 falls back to replication rather than erroring
    b1 = {"tokens": jax.ShapeDtypeStruct((1, 64), jnp.int32)}
    s1 = batch_spec_tree(b1, MULTI)
    assert s1["tokens"] == P()
