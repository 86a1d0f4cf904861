"""shard_map MoE dispatch vs the dense gather/scatter path.

With capacity_factor high enough that no token drops, the two dispatch
strategies must agree exactly (the only semantic difference is local vs
global overflow accounting). Runs in a subprocess with 8 forced devices.
"""

import textwrap

import pytest

from conftest import assert_marker, run_script

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp
    import numpy as np

    from repro.configs.base import MoEConfig, TransformerConfig
    from repro.models import moe as moe_mod

    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = TransformerConfig(
        name="t", n_layers=1, d_model=32, n_heads=4, n_kv_heads=2,
        head_dim=8, d_ff=64, vocab_size=64,
        moe=MoEConfig(n_experts=8, top_k=2, d_ff_expert=16,
                      capacity_factor=8.0),   # no drops
        batch_axes=("data",), dtype="float32")

    key = jax.random.PRNGKey(0)
    p = moe_mod.init_moe_params(cfg, key)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 32))

    with mesh:
        y_dense, aux_dense = jax.jit(
            lambda p, x: moe_mod._moe_ffn_dense(p, x, cfg))(p, x)
        y_smap, aux_smap = jax.jit(
            lambda p, x: moe_mod._moe_ffn_shardmap(p, x, cfg))(p, x)
    np.testing.assert_allclose(np.asarray(y_dense), np.asarray(y_smap),
                               rtol=2e-5, atol=2e-5)
    # aux: the sharded path averages per-shard load-balance losses (mean of
    # products) instead of the global product of means — a standard EP
    # estimator difference, ~0.3% here
    np.testing.assert_allclose(float(aux_dense), float(aux_smap), rtol=2e-2)
    print("MOE_OK")

    # gradients flow through the shard_map path
    def loss(p):
        y, aux = moe_mod._moe_ffn_shardmap(p, x, cfg)
        return jnp.sum(y ** 2) + aux
    with mesh:
        g = jax.jit(jax.grad(loss))(p)
    assert all(np.isfinite(np.asarray(v)).all()
               for v in jax.tree_util.tree_leaves(g))
    print("GRAD_OK")
""")


@pytest.fixture(scope="module")
def subprocess_run():
    return run_script(_SCRIPT)


@pytest.mark.parametrize("marker", ["MOE_OK", "GRAD_OK"])
def test_moe_shardmap_matches_dense(subprocess_run, marker):
    assert_marker(subprocess_run, marker)
