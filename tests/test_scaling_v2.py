"""Communication-avoiding sharded execution v2 (ISSUE 10, DESIGN.md §16).

Host-side: the nnz-balanced partitioner's quality contract (balance ≤ 1.1
on skewed R-MAT), the ragged-block round-trip, the static exchange-plan
invariants, the pre-trace ``row_chunk`` rejection, ``shard()`` argument
validation, the partition-quality gauges, and plan-key isolation of the
two comm layouts. Execution parity — every registered sharded row
bit-exact between ``combine="exchange"`` and the single-device twin on
both b2sr backends, one row between the two combines (tests/test_partition.py
checks every row under ``combine="gather"``), plus whole algorithms through
``GraphMatrix.shard(..., combine="exchange")`` — needs >1 device and runs
in a subprocess with 8 forced host devices (the dry-run-only rule for
device forcing, same as tests/test_partition.py).
"""

from __future__ import annotations

import textwrap

import numpy as np
import pytest

from conftest import assert_marker, run_script
from repro.core import partition as pm
from repro.core.b2sr import coo_to_b2sr
from repro.data import graphs as G

BALANCE_GATE = 1.1


def _skewed_mat(n=1024, skew=16, tile_dim=8, seed=7):
    rows, cols = G.rmat_graph(n, avg_degree=4 + 2 * skew, seed=seed)
    return coo_to_b2sr(rows % n, cols % n, n, n, tile_dim)


# ---------------------------------------------------------------------------
# partition quality (host-side, meshless)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_shards", (2, 4, 8))
def test_balance_skew16_rmat(n_shards):
    mat = _skewed_mat()
    part = pm.partition_rows(mat, n_shards)
    assert part.balance() <= BALANCE_GATE
    # and the split is doing real work: the v1 equal blocks are worse (or
    # at best equal) on the same skewed graph
    equal = pm.partition_rows(mat, n_shards, balanced=False)
    assert part.balance() <= equal.balance()


@pytest.mark.parametrize("tile_dim", (4, 8, 16, 32))
def test_ragged_roundtrip(tile_dim):
    mat = _skewed_mat(n=320, tile_dim=tile_dim)
    part = pm.partition_rows(mat, 4)
    # the balanced split of a skewed graph is genuinely ragged
    lens = [part.row_starts[p + 1] - part.row_starts[p] for p in range(4)]
    assert len(set(lens)) > 1
    assert part.row_starts[0] == 0
    assert part.row_starts[-1] == part.n_tile_rows
    assert all(a <= b for a, b in zip(part.row_starts, part.row_starts[1:]))
    assert part.rows_per_shard == max(lens)
    back = pm.unpartition(part)
    assert np.array_equal(np.asarray(back.tile_row_ptr),
                          np.asarray(mat.tile_row_ptr))
    assert np.array_equal(np.asarray(back.tile_col_idx),
                          np.asarray(mat.tile_col_idx))
    assert np.array_equal(np.asarray(back.bit_tiles),
                          np.asarray(mat.bit_tiles))


def test_equal_fallback_matches_v1_layout():
    mat = _skewed_mat(n=320)
    part = pm.partition_rows(mat, 4, balanced=False)
    r_eq = -(-mat.n_tile_rows // 4)
    assert part.row_starts == tuple(
        min(p * r_eq, mat.n_tile_rows) for p in range(5))


def test_gather_idx_is_the_stacked_permutation():
    mat = _skewed_mat(n=320)
    part = pm.partition_rows(mat, 4)
    gi = np.asarray(part.gather_idx)
    assert gi.shape == (part.n_tile_rows,)
    # global tile-row i lives at stacked position p*rows_per_shard + local
    for p in range(4):
        lo, hi = part.row_starts[p], part.row_starts[p + 1]
        assert np.array_equal(
            gi[lo:hi], p * part.rows_per_shard + np.arange(hi - lo))


# ---------------------------------------------------------------------------
# exchange plan statics (host-side, meshless)
# ---------------------------------------------------------------------------

def test_exchange_plan_invariants():
    mat = _skewed_mat(n=512)
    part = pm.partition_rows(mat, 4)
    xp = pm.build_exchange_plan(part)
    assert xp.n_shards == 4
    assert xp.n_tc_pad == 4 * xp.c_eq >= part.n_tile_cols
    assert 4 * xp.r_eq >= part.n_tile_rows
    # schedule shapes: one [P, W] index pair per nonempty ring offset
    assert len(xp.rhs_offsets) == len(xp.rhs_send_idx) == len(xp.rhs_recv_pos)
    assert len(xp.out_offsets) == len(xp.out_send_idx) == len(xp.out_recv_pos)
    for si, rp in zip(xp.rhs_send_idx, xp.rhs_recv_pos):
        assert si.shape == rp.shape and si.shape[0] == 4
    # the communication-avoiding claim, statically: scheduled exchange
    # lanes undercut the all-gather lane count on a sparse graph
    assert xp.exchanged_lanes() == xp.rhs_lanes + xp.out_lanes
    assert xp.exchanged_lanes() < xp.gather_lanes


def test_exchange_plan_none_for_single_shard():
    part = pm.partition_rows(_skewed_mat(n=320), 1)
    assert pm.build_exchange_plan(part) is None


# ---------------------------------------------------------------------------
# generic-layer guards + gauges + plan isolation (in-process, 1-device mesh)
# ---------------------------------------------------------------------------

def _one_device_graph(combine="gather"):
    import jax
    from jax.sharding import Mesh
    from repro.core.graphblas import GraphMatrix
    rng = np.random.default_rng(3)
    d = (rng.random((48, 48)) < 0.1).astype(np.uint8)
    g = GraphMatrix.from_dense(d, tile_dim=8)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    return g, g.shard(mesh, ("data",), combine=combine)


def test_row_chunk_rejected_before_trace_with_op_name():
    import jax.numpy as jnp
    from repro.core.operands import BitVector
    from repro.core.semiring import ARITHMETIC
    g, gs = _one_device_graph()
    x = jnp.ones((48,), jnp.float32)
    bv = BitVector.pack(x, 8)
    with pytest.raises(ValueError, match="mxv"):
        gs.mxv(x, ARITHMETIC, row_chunk=16)
    with pytest.raises(ValueError, match="mxv"):
        gs.vxm(bv, row_chunk=16)          # transposed path rejects too
    with pytest.raises(ValueError, match="mxm"):
        gs.mxm(jnp.ones((48, 4), jnp.float32), row_chunk=16)
    with pytest.raises(ValueError, match="mxm_sum"):
        gs.tri_count(row_chunk=16)
    # the unsharded twin still accepts chunked evaluation
    assert g.mxv(x, ARITHMETIC, row_chunk=16) is not None


def test_shard_combine_validation():
    import jax
    from jax.sharding import Mesh
    from repro.core.graphblas import GraphMatrix
    g = GraphMatrix.from_dense(np.eye(16, dtype=np.uint8), tile_dim=8)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    with pytest.raises(ValueError, match="combine"):
        g.shard(mesh, ("data",), combine="broadcast")
    mesh2 = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("a", "b"))
    with pytest.raises(ValueError, match="one mesh axis"):
        g.shard(mesh2, ("a", "b"), combine="exchange")
    # gather over two axes stays allowed (the PR 5 contract)
    assert g.shard(mesh2, ("a", "b")).sharded


def test_partition_quality_gauges_published():
    from repro.obs import metrics
    if not metrics.enabled():
        pytest.skip("metrics disabled via REPRO_OBS_DISABLED")
    _, gs = _one_device_graph()
    reg = metrics.get_registry()
    for name in ("partition_balance", "partition_edge_cut"):
        gauge = reg.get(name)
        assert gauge is not None
        labels = dict(orientation="forward", shards="1")
        key = tuple(labels[k] for k in gauge.labelnames)
        assert key in gauge._series


def test_plan_key_isolates_comm_layouts():
    from repro.engine.planner import plan_key
    _, g_gather = _one_device_graph("gather")
    _, g_exch = _one_device_graph("exchange")
    k1 = plan_key(g_gather, "bfs", 1)
    k2 = plan_key(g_exch, "bfs", 1)
    assert k1.mesh != k2.mesh
    assert k1.mesh[-1] == "gather" and k2.mesh[-1] == "exchange"


# ---------------------------------------------------------------------------
# execution parity (8 forced host devices, subprocess)
# ---------------------------------------------------------------------------

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import Mesh

    from repro.algorithms.bfs import bfs
    from repro.algorithms.cc import connected_components
    from repro.algorithms.pagerank import pagerank
    from repro.core.graphblas import GraphMatrix
    from repro.core.operands import BitVector, FrontierBatch, BitMatrix
    from repro.core.semiring import ARITHMETIC, MIN_PLUS
    from repro.engine.queries import msbfs
    from repro.obs import metrics

    assert len(jax.devices()) == 8

    def ring(p):
        return Mesh(np.asarray(jax.devices()[:p]), ("data",))

    def build(n, t, seed, density=0.08):
        rng = np.random.RandomState(seed)
        d = (rng.random((n, n)) < density).astype(np.uint8)
        d[seed % n] |= (rng.random(n) < 0.6)   # hub rows: ragged split
        return GraphMatrix.from_dense(d, tile_dim=t), d

    # --- every sharded row x backend x buckets, exchange combine ----------
    # A tile dim changes the packing, not which shard_map twin runs (the
    # roundtrip tests above cover every dim): one dim per backend, 4 and 32
    # between them. The sharded side runs under jit, as the algorithms run
    # it; eager shard_map dispatches op by op, ~25x slower on host devices.
    # tests/test_partition.py checks every row under the gather combine.
    reg = metrics.get_registry()

    def words(name):
        c = reg.get(name)
        return 0.0 if c is None else sum(float(v) for v in c._series.values())

    paired = {"gather_words_total": 0.0, "exchange_words_total": 0.0}
    for backend, t in (("b2sr", 4), ("b2sr_pallas", 32)):
        g, d = build(96, t, seed=t)
        g = g.with_backend(backend)
        rng = np.random.RandomState(100 + t)
        x = jnp.asarray(rng.rand(96).astype(np.float32))
        bv = BitVector.pack(jnp.asarray(rng.rand(96) > 0.5), t)
        mk = BitVector.pack(jnp.asarray(rng.rand(96) > 0.5), t)
        fb = FrontierBatch.pack(jnp.asarray(rng.rand(96, 5) > 0.5), t)
        bm = BitMatrix.pack(
            jnp.asarray(rng.rand(96, 6).astype(np.float32)) - 0.5, t)
        X = jnp.asarray(rng.rand(96, 6).astype(np.float32))
        gx = g.shard(ring(4), combine="exchange")
        for ub in (True, False):
            a, b = g.with_buckets(ub), gx.with_buckets(ub)

            def same(row, atol=None):
                want = np.asarray(row(a))
                got = np.asarray(jax.jit(lambda: row(b))())
                if atol is None:
                    assert np.array_equal(got, want), (backend, ub)
                else:
                    assert np.allclose(got, want, atol=atol), (backend, ub)

            same(lambda m: m.mxv(bv).words)
            same(lambda m: m.mxv(bv, mask=mk, complement=True).words)
            same(lambda m: m.mxv(bv, ARITHMETIC, out_dtype=jnp.int32))
            same(lambda m: m.mxv(x), atol=1e-5)
            same(lambda m: m.mxv(x, MIN_PLUS))
            same(lambda m: m.mxm(X), atol=1e-4)
            same(lambda m: m.mxm(fb).words)
            same(lambda m: m.mxm(bm), atol=1e-4)
            same(lambda m: m.vxm(bv).words)
        # SpGEMM rows + the fused tri reduction (gather/psum combine)
        assert gx.mxm(g).nnz == g.mxm(g).nnz
        assert np.array_equal(np.asarray(gx.mxm(g, ARITHMETIC)),
                              np.asarray(g.mxm(g, ARITHMETIC)))
        assert float(gx.tri_count()) == float(g.tri_count())
        # gather and exchange agree on one row, eagerly, and the counters
        # see that one op mix through both layouts
        gg = g.shard(ring(4), combine="gather")
        before = {k: words(k) for k in paired}
        assert np.array_equal(np.asarray(gg.mxv(bv).words),
                              np.asarray(gx.mxv(bv).words))
        for k in paired:
            paired[k] += words(k) - before[k]
    print("XROWS_OK")

    # --- the comm counters witness the communication-avoiding claim -------
    gw = paired["gather_words_total"]
    xw = paired["exchange_words_total"]
    assert gw > 0 and xw > 0
    # same op mix ran through both layouts above; exchange moved fewer words
    assert xw < gw, (xw, gw)
    print("XCOMM_OK")

    # --- whole algorithms through shard(combine="exchange"), 8 shards -----
    t = 8
    g, d = build(128, t, seed=11)
    gx = g.shard(ring(8), combine="exchange")
    assert gx.xplan is not None and gx.xplan.n_shards == 8
    assert np.array_equal(np.asarray(bfs(gx, 3).levels),
                          np.asarray(bfs(g, 3).levels))
    assert np.allclose(np.asarray(pagerank(gx).ranks),
                       np.asarray(pagerank(g).ranks), atol=1e-7)
    assert np.array_equal(np.asarray(connected_components(gx).labels),
                          np.asarray(connected_components(g).labels))
    srcs = [1, 9, 17, 33]
    assert np.array_equal(np.asarray(msbfs(gx, srcs).levels),
                          np.asarray(msbfs(g, srcs).levels))
    print("XALGOS_OK")
""")

MARKERS = ["XROWS_OK", "XCOMM_OK", "XALGOS_OK"]


@pytest.fixture(scope="module")
def exchange_parity_run():
    return run_script(_SCRIPT)


@pytest.mark.parametrize("marker", MARKERS)
def test_exchange_parity(exchange_parity_run, marker):
    assert_marker(exchange_parity_run, marker)
