"""Sharded B2SR tests (ISSUE 5, DESIGN.md §11).

Host-side partition/unpartition round-trips and stats run in-process (they
never touch a mesh). The execution-parity half — every sharded Table row
bit-exact against its single-device twin, descriptors, plan-cache mesh
isolation, and whole algorithms through ``GraphMatrix.shard`` with zero
call-site changes — needs >1 device, so it runs in a subprocess with 8
forced host devices (the dry-run-only rule for device forcing), using
``launch.mesh.make_debug_mesh`` as the mesh factory.
"""

from __future__ import annotations

import textwrap

import numpy as np
import pytest

from conftest import assert_marker, run_script
from repro.core import partition as pm
from repro.core.b2sr import b2sr_to_dense, coo_to_b2sr, to_ell

TILE_DIMS = (4, 8, 16, 32)
SHARD_COUNTS = (1, 2, 3, 8)        # 3 and 8 leave a ragged last shard


def rand_coo(n, seed=0, density=0.08, skew_hubs=2, hub_deg=None):
    rng = np.random.default_rng(seed)
    m = int(n * n * density)
    rows = rng.integers(0, n, m)
    cols = rng.integers(0, n, m)
    if skew_hubs:
        hd = hub_deg or 4 * max(int(n * density), 1)
        hubs = rng.choice(n, skew_hubs, replace=False)
        rows = np.concatenate([rows, np.repeat(hubs, hd)])
        cols = np.concatenate([cols, rng.integers(0, n, skew_hubs * hd)])
    # dedupe: B2SR ORs duplicates away, so round-trip nnz is bit population
    key = np.unique(rows * n + cols)
    return key // n, key % n


# ---------------------------------------------------------------------------
# partition/unpartition round-trip (host-side, meshless)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tile_dim", TILE_DIMS)
@pytest.mark.parametrize("n_shards", SHARD_COUNTS)
def test_partition_roundtrip(tile_dim, n_shards):
    n = 70                                  # not a multiple of any tile_dim
    rows, cols = rand_coo(n, seed=tile_dim + n_shards)
    mat = coo_to_b2sr(rows, cols, n, n, tile_dim)
    part = pm.partition_rows(mat, n_shards)
    assert part.n_shards == n_shards
    assert part.n_shards * part.rows_per_shard >= mat.n_tile_rows
    back = pm.unpartition(part)
    # array-identical reconstruction, not just equal structure
    assert np.array_equal(np.asarray(back.tile_row_ptr),
                          np.asarray(mat.tile_row_ptr))
    assert np.array_equal(np.asarray(back.tile_col_idx),
                          np.asarray(mat.tile_col_idx))
    assert np.array_equal(np.asarray(back.bit_tiles),
                          np.asarray(mat.bit_tiles))
    assert back.nnz == mat.nnz
    assert np.array_equal(b2sr_to_dense(back), b2sr_to_dense(mat))


def test_partition_accepts_ell_view():
    rows, cols = rand_coo(50, seed=3)
    mat = coo_to_b2sr(rows, cols, 50, 50, 8)
    a = pm.partition_rows(mat, 3)
    b = pm.partition_rows(to_ell(mat), 3)
    assert np.array_equal(np.asarray(a.tile_col_idx),
                          np.asarray(b.tile_col_idx))
    assert a.shard_tiles == b.shard_tiles


def test_partition_empty_and_tiny_graphs():
    empty = coo_to_b2sr(np.array([]), np.array([]), 16, 16, 8)
    part = pm.partition_rows(empty, 4)
    assert part.balance() == 1.0 and part.edge_cut() == 0.0
    assert pm.unpartition(part).nnz == 0
    # more shards than tile rows: trailing shards are pure padding
    tiny = coo_to_b2sr(np.array([0]), np.array([1]), 4, 4, 4)
    part = pm.partition_rows(tiny, 8)
    assert part.rows_per_shard == 1
    assert pm.unpartition(part).nnz == 1


def test_partition_stats():
    rows, cols = rand_coo(96, seed=5, skew_hubs=3)
    mat = coo_to_b2sr(rows, cols, 96, 96, 8)
    part = pm.partition_rows(mat, 4)
    assert sum(part.shard_tiles) == mat.n_tiles
    assert part.balance() >= 1.0
    assert 0.0 <= part.edge_cut() <= 1.0
    # single shard: everything local, perfectly balanced
    solo = pm.partition_rows(mat, 1)
    assert solo.balance() == 1.0 and solo.edge_cut() == 0.0


def test_harmonised_buckets_share_structure():
    rows, cols = rand_coo(128, seed=7, density=0.02, skew_hubs=2,
                          hub_deg=100)
    mat = coo_to_b2sr(rows, cols, 128, 128, 4)
    part = pm.partition_rows(mat, 4)
    assert part.n_buckets >= 2               # skew spreads the histogram
    R = part.rows_per_shard
    for c, t, r in zip(part.bucket_col_idx, part.bucket_bit_tiles,
                       part.bucket_rows):
        # one slab per bucket, stacked across all shards with one width
        assert c.shape[0] == part.n_shards and t.shape[:3] == c.shape[:3]
        ra = np.asarray(r)
        assert ra.shape[0] == part.n_shards
        assert ra.min() >= 0 and ra.max() <= R   # R == the garbage row
    # every real (non-empty) tile row appears in exactly one bucket
    counts = np.asarray(part.row_n_tiles)
    for p in range(part.n_shards):
        seen = np.concatenate([np.asarray(r)[p] for r in part.bucket_rows])
        seen = seen[seen < R]
        expect = np.flatnonzero(counts[p] > 0)
        assert np.array_equal(np.sort(seen), expect)


def test_partition_rejects_bad_args():
    mat = coo_to_b2sr(np.array([0]), np.array([1]), 8, 8, 4)
    with pytest.raises(ValueError, match="n_shards"):
        pm.partition_rows(mat, 0)


def test_mesh_helpers_validate():
    import jax
    mesh = jax.make_mesh((1,), ("data",))
    assert pm.shard_count(mesh, ("data",)) == 1
    with pytest.raises(ValueError, match="no axis"):
        pm.shard_count(mesh, ("model",))
    fp = pm.mesh_fingerprint(mesh, ("data",))
    assert fp[0] == ("data",) and fp[2] == ("data",)


def test_shard_respects_use_buckets_and_falls_back():
    # shard() only builds harmonised bucket slabs when the bucketed path is
    # on; toggling buckets on afterwards must stay *correct* via the ELL
    # slab fallback (just without the SELL split) — a single-device mesh
    # exercises the real shard_map rows in-process
    import jax
    import jax.numpy as jnp
    from repro.core import BitVector, GraphMatrix
    rng = np.random.RandomState(8)
    d = (rng.random((48, 48)) < 0.15).astype(np.uint8)
    g = GraphMatrix.from_dense(d, tile_dim=8)
    mesh = jax.make_mesh((1,), ("data",))
    gs_nb = g.with_buckets(False).shard(mesh)
    assert gs_nb.partitioned.n_buckets == 0        # nothing built
    assert g.shard(mesh).partitioned.n_buckets >= 1
    bv = BitVector.pack(jnp.asarray(rng.rand(48) > 0.5), 8)
    want = np.asarray(g.mxv(bv).words)
    assert np.array_equal(np.asarray(gs_nb.mxv(bv).words), want)
    # bucketed dispatch on a bucketless partition: ELL fallback, same bits
    assert np.array_equal(
        np.asarray(gs_nb.with_buckets(True).mxv(bv).words), want)


def test_make_debug_mesh_rejects_non_divisible():
    # the satellite fix: no more silent device dropping
    import jax
    from repro.launch.mesh import make_debug_mesh
    with pytest.raises(ValueError, match="not divisible"):
        make_debug_mesh(n_devices=1, model=2)
    with pytest.raises(ValueError, match="out of range"):
        make_debug_mesh(n_devices=len(jax.devices()) + 1)
    mesh = make_debug_mesh(n_devices=1, model=1)
    assert mesh.devices.shape == (1, 1)


# ---------------------------------------------------------------------------
# sharded execution parity (8 forced host devices, subprocess)
# ---------------------------------------------------------------------------

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax, jax.numpy as jnp

    from repro.algorithms.bfs import bfs
    from repro.algorithms.cc import connected_components
    from repro.algorithms.pagerank import pagerank
    from repro.algorithms.tc import triangle_count
    from repro.core.descriptor import Descriptor
    from repro.core.graphblas import GraphMatrix
    from repro.core.operands import BitVector, FrontierBatch
    from repro.core.semiring import ARITHMETIC, MIN_PLUS
    from repro.engine.planner import PlanCache, plan_key
    from repro.engine.queries import batched_ppr, msbfs
    from repro.launch.mesh import make_debug_mesh

    assert len(jax.devices()) == 8
    mesh = make_debug_mesh(8, model=2)            # (data=4, model=2)

    def build(n, t, seed, density=0.08):
        rng = np.random.RandomState(seed)
        d = (rng.random((n, n)) < density).astype(np.uint8)
        # two hub rows so the bucket histogram has >1 bucket
        d[seed % n] |= (rng.random(n) < 0.6)
        return GraphMatrix.from_dense(d, tile_dim=t), d

    # --- every kernel row, buckets on/off ---------------------------------
    # A tile dim changes the packing, not which shard_map twin runs: each
    # (row, buckets) pair runs once, and the dims rotate across the rows
    # so that 4, 8, 16 and 32 all run sharded.
    TILE_DIMS = (4, 8, 16, 32)
    cases = {}
    for t in TILE_DIMS:
        g, d = build(96, t, seed=t)
        rng = np.random.RandomState(100 + t)
        cases[t] = (g, g.shard(mesh), dict(
            x=jnp.asarray(rng.rand(96).astype(np.float32)),
            bv=BitVector.pack(jnp.asarray(rng.rand(96) > 0.5), t),
            fb=FrontierBatch.pack(jnp.asarray(rng.rand(96, 5) > 0.5), t),
            X=jnp.asarray(rng.rand(96, 6).astype(np.float32))))

    def mxv_bin(a, b, o):
        assert np.array_equal(np.asarray(b.mxv(o["bv"]).words),
                              np.asarray(a.mxv(o["bv"]).words))

    def mxv_count(a, b, o):
        assert np.array_equal(
            np.asarray(b.mxv(o["bv"], ARITHMETIC, out_dtype=jnp.int32)),
            np.asarray(a.mxv(o["bv"], ARITHMETIC, out_dtype=jnp.int32)))

    # float ⊕ rows: same per-row reduction order, but allow for
    # shape-dependent XLA lowering; bit-level rows stay bit-exact
    def mxv_float(a, b, o):
        assert np.allclose(np.asarray(b.mxv(o["x"])),
                           np.asarray(a.mxv(o["x"])), atol=1e-6)

    def mxv_min_plus(a, b, o):
        assert np.array_equal(np.asarray(b.mxv(o["x"], MIN_PLUS)),
                              np.asarray(a.mxv(o["x"], MIN_PLUS)))

    def mxm_float(a, b, o):
        assert np.allclose(np.asarray(b.mxm(o["X"])),
                           np.asarray(a.mxm(o["X"])), atol=1e-5)

    def mxm_frontier(a, b, o):
        assert np.array_equal(np.asarray(b.mxm(o["fb"]).words),
                              np.asarray(a.mxm(o["fb"]).words))

    ROWS = (mxv_bin, mxv_count, mxv_float, mxv_min_plus, mxm_float,
            mxm_frontier)
    for ub in (True, False):
        for i, row in enumerate(ROWS):
            g, gs, o = cases[TILE_DIMS[(i + 2 * ub) % len(TILE_DIMS)]]
            row(g.with_buckets(ub), gs.with_buckets(ub), o)
    # SpGEMM rows (bin + count) and the fused tri reduction
    g, gs, _ = cases[16]
    pa, pb = g.mxm(g), gs.mxm(g)
    assert pa.nnz == pb.nnz
    assert np.array_equal(np.asarray(pa.csr.col_idx),
                          np.asarray(pb.csr.col_idx))
    assert np.array_equal(np.asarray(gs.mxm(g, ARITHMETIC)),
                          np.asarray(g.mxm(g, ARITHMETIC)))
    print("ROWS_OK")

    # --- masked + transposed descriptors ----------------------------------
    t = 8
    g, d = build(96, t, seed=41)
    gs = g.shard(mesh)
    rng = np.random.RandomState(5)
    bv = BitVector.pack(jnp.asarray(rng.rand(96) > 0.5), t)
    mask = BitVector.pack(jnp.asarray(rng.rand(96) > 0.5), t)
    fb = FrontierBatch.pack(jnp.asarray(rng.rand(96, 3) > 0.5), t)
    fmask = FrontierBatch.pack(jnp.asarray(rng.rand(96, 3) > 0.5), t)
    x = jnp.asarray(rng.rand(96).astype(np.float32))
    dmask = jnp.asarray((rng.rand(96) > 0.5).astype(np.float32))
    for tr in (False, True):
        dsc = Descriptor(mask=mask, complement=True, transpose_a=tr)
        assert np.array_equal(np.asarray(gs.mxv(bv, desc=dsc).words),
                              np.asarray(g.mxv(bv, desc=dsc).words))
        dsc = Descriptor(mask=dmask, complement=tr, transpose_a=tr)
        assert np.allclose(np.asarray(gs.mxv(x, ARITHMETIC, dsc)),
                           np.asarray(g.mxv(x, ARITHMETIC, dsc)), atol=1e-6)
        dsc = Descriptor(mask=fmask, complement=True, transpose_a=tr)
        assert np.array_equal(np.asarray(gs.mxm(fb, desc=dsc).words),
                              np.asarray(g.mxm(fb, desc=dsc).words))
    ma, mb = g.mxm(g, mask=g, complement=True), gs.mxm(g, mask=g,
                                                       complement=True)
    assert ma.nnz == mb.nnz
    assert np.array_equal(
        np.asarray(gs.mxm(g, ARITHMETIC, mask=g, complement=True)),
        np.asarray(g.mxm(g, ARITHMETIC, mask=g, complement=True)))
    print("DESC_OK")

    # --- whole algorithms through shard(mesh), zero call-site changes -----
    sym = ((d | d.T) & ~np.eye(96, dtype=bool)).astype(np.uint8)
    h = GraphMatrix.from_dense(sym, tile_dim=8)
    hs = h.shard(mesh)
    assert np.array_equal(np.asarray(bfs(gs, 3).levels),
                          np.asarray(bfs(g, 3).levels))
    assert np.allclose(np.asarray(pagerank(gs).ranks),
                       np.asarray(pagerank(g).ranks), atol=1e-7)
    assert np.array_equal(np.asarray(connected_components(gs).labels),
                          np.asarray(connected_components(g).labels))
    assert triangle_count(hs) == triangle_count(h)
    print("ALGOS_OK")

    # --- engine: one mesh serves a whole batch; plan-cache mesh isolation --
    pc = PlanCache()
    mesh_b = make_debug_mesh(4, model=2)          # (2, 2): different shape
    gs_b = g.shard(mesh_b)
    srcs = [1, 9, 17, 33]
    ref = msbfs(g, srcs, planner=pc)
    for gg in (gs, gs_b):
        got = msbfs(gg, srcs, planner=pc)
        assert np.array_equal(np.asarray(got.levels), np.asarray(ref.levels))
    assert pc.misses == 3 and pc.hits == 0        # three distinct plans
    keys = pc.keys()
    assert len({k.mesh for k in keys}) == 3       # None + two mesh shapes
    msbfs(gs, srcs, planner=pc)                   # same mesh: cache hit
    assert pc.hits == 1 and pc.misses == 3
    pr_ref = batched_ppr(g, [2, 7], planner=pc)
    pr_got = batched_ppr(gs, [2, 7], planner=pc)
    assert np.allclose(np.asarray(pr_got.ranks), np.asarray(pr_ref.ranks),
                       atol=1e-6)
    # sharding over a subset of mesh axes is its own plan too
    gs_data = g.shard(mesh, axes=("data",))
    assert gs_data.partitioned.n_shards == 4
    assert np.array_equal(np.asarray(msbfs(gs_data, srcs).levels),
                          np.asarray(ref.levels))
    print("ENGINE_OK")

    # --- sharded pallas-backend graph + error contracts -------------------
    gp = g.with_backend("b2sr_pallas").shard(mesh)
    assert np.array_equal(np.asarray(bfs(gp, 3).levels),
                          np.asarray(bfs(g, 3).levels))
    try:
        g.with_backend("csr").shard(mesh)
        raise SystemExit("csr shard must raise")
    except ValueError:
        pass
    try:
        gs.mxv(x, ARITHMETIC, Descriptor(row_chunk=16))
        raise SystemExit("sharded row_chunk must raise")
    except ValueError:
        pass
    assert gs.unshard().sharded is False
    assert np.array_equal(np.asarray(gs.unshard().mxv(bv).words),
                          np.asarray(g.mxv(bv).words))
    print("GUARDS_OK")
""")

MARKERS = ["ROWS_OK", "DESC_OK", "ALGOS_OK", "ENGINE_OK", "GUARDS_OK"]


@pytest.fixture(scope="module")
def sharded_parity_run():
    return run_script(_SCRIPT)


@pytest.mark.parametrize("marker", MARKERS)
def test_sharded_parity(sharded_parity_run, marker):
    assert_marker(sharded_parity_run, marker)
