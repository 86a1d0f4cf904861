"""Elastic rescale: a checkpoint written under one mesh restores onto
another (the node-failure / rescale recovery path). Subprocess with 8
devices: save sharded over 8, restore sharded over 4 and over 2×2."""

import textwrap

import pytest

from conftest import assert_marker, run_script

_SCRIPT = textwrap.dedent("""
    import os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.training import checkpoint as ckpt

    tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
            "b": jnp.ones((8,)), "step": jnp.int32(7)}

    mesh8 = jax.make_mesh((8,), ("data",))
    sh8 = {"w": NamedSharding(mesh8, P("data", None)),
           "b": NamedSharding(mesh8, P("data")),
           "step": NamedSharding(mesh8, P())}
    placed = jax.tree_util.tree_map(jax.device_put, tree, sh8)

    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 5, placed)

        # restore onto a 4-device mesh (simulates losing half the slice)
        mesh4 = jax.make_mesh((4,), ("data",), devices=jax.devices()[:4])
        sh4 = {"w": NamedSharding(mesh4, P("data", None)),
               "b": NamedSharding(mesh4, P("data")),
               "step": NamedSharding(mesh4, P())}
        r4, _ = ckpt.restore(d, 5, tree, sh4)
        for k in tree:
            np.testing.assert_array_equal(np.asarray(tree[k]),
                                          np.asarray(r4[k]))
        assert r4["w"].sharding.mesh.devices.size == 4
        print("RESHARD_4_OK")

        # restore onto a 2x2 2-D mesh (different topology entirely)
        mesh22 = jax.make_mesh((2, 2), ("data", "model"),
                               devices=jax.devices()[:4])
        sh22 = {"w": NamedSharding(mesh22, P("data", "model")),
                "b": NamedSharding(mesh22, P(("data", "model"))),
                "step": NamedSharding(mesh22, P())}
        r22, _ = ckpt.restore(d, 5, tree, sh22)
        for k in tree:
            np.testing.assert_array_equal(np.asarray(tree[k]),
                                          np.asarray(r22[k]))
        print("RESHARD_2x2_OK")
""")


@pytest.fixture(scope="module")
def subprocess_run():
    return run_script(_SCRIPT)


@pytest.mark.parametrize("marker", ["RESHARD_4_OK", "RESHARD_2x2_OK"])
def test_elastic_reshard(subprocess_run, marker):
    assert_marker(subprocess_run, marker)
