"""Sharding-rule unit tests against abstract meshes (no devices needed)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config, reduced
from repro.core import split
from repro.configs import MPSLConfig, RunConfig, SHAPES
from repro.models import model as M
from repro.parallel import sharding

def _abstract_mesh(sizes, names):
    """AbstractMesh across jax versions: (sizes, names) args on >= 0.5,
    a single ((name, size), ...) shape tuple on 0.4.x."""
    try:
        return AbstractMesh(sizes, names)
    except TypeError:
        return AbstractMesh(tuple(zip(names, sizes)))


MESH = _abstract_mesh((16, 16), ("data", "model"))
MESH3 = _abstract_mesh((2, 16, 16), ("pod", "data", "model"))


def test_resolve_divisibility_fallbacks():
    with sharding.use_mesh(MESH):
        # heads divisible -> TP on heads
        assert sharding.resolve_spec(MESH, (4096, 64, 128),
                                     ("fsdp", "model", None)) \
            == P("data", "model", None)
        # 24 heads on a 16-way axis -> dropped
        assert sharding.resolve_dim(MESH, 24, "model") is None
        # chain falls through to a divisible candidate
        assert sharding.resolve_dim(MESH, 1600, ("dboth", "model")) == "model"
        assert sharding.resolve_dim(MESH, 3072, ("dboth", "model")) \
            == ("data", "model")


def test_param_specs_cover_all_leaves():
    cfg = reduced(get_config("qwen3-moe-235b-a22b"))
    params = jax.eval_shape(lambda k: M.init_lm(k, cfg),
                            jax.random.PRNGKey(0))
    specs = sharding.param_specs(params, MESH)
    leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    assert len(leaves) == len(jax.tree_util.tree_leaves(params))
    assert all(isinstance(s, P) for s in leaves)


def test_client_params_shard_on_client_axis():
    cfg = reduced(get_config("minitron-4b"))
    run = RunConfig(model=cfg, shape=SHAPES["train_4k"],
                    mpsl=MPSLConfig(n_clients=32, trainable_blocks=1))
    params, frozen = jax.eval_shape(
        lambda k: split.init_mpsl_lm(k, cfg, run)[:2], jax.random.PRNGKey(0))
    specs = sharding.param_specs(params, MESH3)
    a_spec = specs["client"]["adapter"]["a"]
    assert a_spec[0] == ("pod", "data")


def test_full_arch_sweep_specs_valid():
    """Every assigned arch's full-size param tree resolves to legal specs
    (all sharded dims divisible) on a 16x16 and a 2x16x16 mesh."""
    from repro.configs import ASSIGNED_ARCHS
    for mesh in (MESH, MESH3):
        sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
        for name, cfg in ASSIGNED_ARCHS.items():
            params = jax.eval_shape(lambda k: M.init_lm(k, cfg),
                                    jax.random.PRNGKey(0))
            specs = sharding.param_specs(params, mesh)

            def check(leaf, spec):
                for dim, s in zip(leaf.shape, tuple(spec)):
                    if s is None:
                        continue
                    axes = s if isinstance(s, tuple) else (s,)
                    total = 1
                    for a in axes:
                        total *= sizes[a]
                    assert dim % total == 0, (name, leaf.shape, spec)

            jax.tree_util.tree_map(
                check, params,
                jax.tree_util.tree_map(lambda s: s, specs,
                                       is_leaf=lambda x: isinstance(x, P)),
                is_leaf=lambda x: hasattr(x, "shape"))


def test_cache_dims_seq_fallback():
    with sharding.use_mesh(MESH):
        # kv heads divide the TP axis -> shard heads
        assert sharding.cache_dims((1, 8, 1024, 16, 128), "k", True) \
            == (None, "batch", None, "model", None)
        # kv heads don't divide -> shard seq instead, pos follows
        assert sharding.cache_dims((1, 8, 1024, 8, 128), "k", True) \
            == (None, "batch", "model", None, None)
        assert sharding.cache_dims((1, 8, 1024), "pos", True, kv_heads=8) \
            == (None, "batch", "model")
        assert sharding.cache_dims((1, 8, 1024), "pos", True, kv_heads=16) \
            == (None, "batch", None)
