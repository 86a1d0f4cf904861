"""Numerical equivalence of the §Perf shard_map aggregation paths.

The receiver-partitioned paths need >1 device, so the comparison runs in a
subprocess with 8 forced host devices (the main test process keeps the
default single device, per the dry-run-only rule for device forcing).

Data contract exercised here (and documented in DESIGN.md §8b): edges are
grouped by receiver block (block = receiver // (N / n_shards)) and padded
per block to a common count, so edge-shard i contains exactly the edges
whose receivers live in node-block i.
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

    from repro.configs.base import GNNConfig
    from repro.core import b2sr as b2sr_mod
    from repro.core import ops as b2sr_ops
    from repro.data import graphs as G
    from repro.models.gnn import gatedgcn
    from repro.models.gnn.common import GraphBatch

    P_SHARDS = 8
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    N, t = 256, 8
    n_local = N // P_SHARDS
    rng = np.random.default_rng(0)

    rows, cols = G.block_graph(N, n_blocks=8, intra_density=0.2, seed=1)

    # --- receiver-block partition + per-block padding (the data contract) --
    blk = cols // n_local
    per_block = [np.flatnonzero(blk == b) for b in range(P_SHARDS)]
    width = max(len(ix) for ix in per_block)
    pr = np.zeros((P_SHARDS, width), np.int64)
    pc = np.zeros((P_SHARDS, width), np.int64)
    msk = np.zeros((P_SHARDS, width), bool)
    for b, ix in enumerate(per_block):
        pr[b, :len(ix)] = rows[ix]
        pc[b, :len(ix)] = cols[ix]
        pc[b, len(ix):] = b * n_local          # padding stays in-block
        msk[b, :len(ix)] = True
    pr, pc, msk = pr.ravel(), pc.ravel(), msk.ravel()

    feat = rng.standard_normal((N, 16)).astype(np.float32)
    batch = GraphBatch(
        node_feat=jnp.asarray(feat),
        senders=jnp.asarray(pr.astype(np.int32)),
        receivers=jnp.asarray(pc.astype(np.int32)),
        node_mask=jnp.ones(N, bool),
        edge_mask=jnp.asarray(msk),
        labels=jnp.zeros(N, jnp.int32),
        train_mask=jnp.ones(N, bool),
        graph_ids=jnp.zeros(N, jnp.int32),
    )

    cfg0 = GNNConfig(name="t", family="gatedgcn", n_layers=2, d_hidden=16,
                     d_in=16, n_classes=4)
    cfg1 = dataclasses.replace(cfg0, shardmap_agg_axes=("data", "model"))
    params = gatedgcn.init_params(cfg0, jax.random.PRNGKey(0))

    with mesh:
        ref = gatedgcn.forward(params, batch, cfg0)
        out = gatedgcn.forward(params, batch, cfg1)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=2e-4, atol=2e-4)
    print("GATEDGCN_OK")

    # --- B2SR shard_map SpMM vs local SpMM (tile-rows partitioned) --------
    mat = b2sr_mod.coo_to_b2sr(rows, cols, N, N, t)
    ell = b2sr_mod.to_ell(mat, pad_tile_rows_to=P_SHARDS)
    x = jnp.asarray(rng.standard_normal((N, 16)).astype(np.float32))
    x_pad = jnp.pad(x, ((0, ell.n_tile_rows * t - N), (0, 0)))
    ell_full = dataclasses.replace(ell, n_rows=ell.n_tile_rows * t,
                                   n_cols=ell.n_tile_rows * t)
    with mesh:
        ref2 = b2sr_ops.spmm_b2sr(ell_full, x_pad)
        out2 = b2sr_ops.spmm_b2sr_shardmap(ell_full, x_pad,
                                           ("data", "model"))
    np.testing.assert_allclose(np.asarray(ref2), np.asarray(out2),
                               rtol=1e-5, atol=1e-5)
    print("SPMM_OK")
""")


@pytest.fixture(scope="module")
def subprocess_run():
    return run_script(_SCRIPT)


@pytest.mark.parametrize("marker", ["GATEDGCN_OK", "SPMM_OK"])
def test_shardmap_matches_dense(subprocess_run, marker):
    assert_marker(subprocess_run, marker)
