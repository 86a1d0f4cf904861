"""Batched multi-source query engine: parity, planner, batcher (DESIGN.md §9).

The engine is a pure batching transform — every multi-source result must
equal a loop of single-source runs (for BFS, a plain numpy BFS over the
dense adjacency): bit-exact for BFS/k-hop/SSSP (boolean/integer ops),
allclose for PPR (the multi-vector spmm sums in a different float order
than the scanned bmv). Pinned across tile dims 4/8/16/32, all three
backends, bucketed on/off, and ragged batch sizes (1, word-width,
non-pow2, > 32 sources). Plus: the packed frontier-matrix scheme itself,
the plan cache (hit/miss/eviction), the request batcher, and the
GraphMatrix memoization satellites.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.algorithms import bfs, khop_frontier, pagerank, ppr, sssp
from repro.core import (
    TILE_DIMS, GraphMatrix, coo_to_b2sr, pack_bitvector, pack_frontier_matrix,
    to_bucketed, to_ell, unpack_bitvector, unpack_frontier_matrix,
)
from repro.core import ops
from repro.engine import (
    BatchFlushError, PlanCache, QueryBatcher, QueryGroupError, batched_ppr,
    ms_sssp, msbfs, mskhop, plan_key,
)

BACKENDS = ("b2sr", "b2sr_pallas", "csr")


def skewed_coo(n, seed, hub_deg=25, base_deg=3):
    rng = np.random.default_rng(seed)
    rows = np.concatenate([
        np.repeat(np.arange(n, dtype=np.int64), base_deg),
        np.repeat(rng.choice(n, 2, replace=False).astype(np.int64), hub_deg),
    ])
    cols = rng.integers(0, n, rows.size)
    return rows, cols


def build(n=96, t=8, backend="b2sr", seed=0, use_buckets=True):
    rows, cols = skewed_coo(n, seed)
    g = GraphMatrix.from_coo(rows, cols, n, n, tile_dim=t, backend=backend)
    return g.with_buckets(use_buckets)


# ---------------------------------------------------------------------------
# frontier-matrix packing + the spmm_bin_bin_bin scheme
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t", TILE_DIMS)
@pytest.mark.parametrize("s", (1, 5, 33))
def test_frontier_matrix_roundtrip(t, s):
    n = 70
    rng = np.random.default_rng(t + s)
    f = rng.random((n, s)) > 0.5
    fp = pack_frontier_matrix(jnp.asarray(f), t, n)
    assert fp.shape == (-(-n // t), t, -(-s // 32))
    assert np.array_equal(np.asarray(unpack_frontier_matrix(fp, n, s,
                                                            jnp.bool_)), f)


@pytest.mark.parametrize("t", TILE_DIMS)
def test_spmm_bbb_equals_per_source_bmv(t):
    n = 80
    rows, cols = skewed_coo(n, seed=t)
    ell = to_ell(coo_to_b2sr(rows, cols, n, n, t))
    bk = to_bucketed(ell)
    rng = np.random.default_rng(t)
    s = 37                                   # 2 source words, ragged
    f = rng.random((n, s)) > 0.6
    fp = pack_frontier_matrix(jnp.asarray(f), t, n)
    y = ops.spmm_bin_bin_bin(ell, fp)
    # bucketed twin is bit-identical
    assert np.array_equal(np.asarray(y),
                          np.asarray(ops.spmm_bin_bin_bin_bucketed(bk, fp)))
    # column s == the single-frontier bmv scheme
    yd = unpack_frontier_matrix(y, n, s, jnp.bool_)
    for col in (0, 17, 36):
        xp = pack_bitvector(jnp.asarray(f[:, col]), t, n)
        want = unpack_bitvector(ops.bmv_bin_bin_bin(ell, xp), t, n, jnp.bool_)
        assert np.array_equal(np.asarray(yd[:, col]), np.asarray(want)), col
    # §V mask-at-store, plain and complemented, both paths
    m = rng.random((n, s)) > 0.5
    mp = pack_frontier_matrix(jnp.asarray(m), t, n)
    for comp in (True, False):
        want = np.asarray(y & (~mp if comp else mp))
        assert np.array_equal(
            np.asarray(ops.spmm_bin_bin_bin_masked(ell, fp, mp, comp)), want)
        assert np.array_equal(
            np.asarray(ops.spmm_bin_bin_bin_bucketed_masked(bk, fp, mp,
                                                            comp)), want)


@pytest.mark.parametrize("t", (4, 8, 32))
def test_pallas_spmm_bbb_matches_jnp_and_ref(t):
    from repro.kernels.spmm import ops as kops, ref as kref
    n = 64
    rows, cols = skewed_coo(n, seed=t, hub_deg=15, base_deg=2)
    ell = to_ell(coo_to_b2sr(rows, cols, n, n, t))
    bk = to_bucketed(ell)
    rng = np.random.default_rng(t)
    s = 34
    f = rng.random((n, s)) > 0.5
    m = rng.random((n, s)) > 0.4
    fp = pack_frontier_matrix(jnp.asarray(f), t, n)
    mp = pack_frontier_matrix(jnp.asarray(m), t, n)
    want = np.asarray(ops.spmm_bin_bin_bin(ell, fp))
    assert np.array_equal(np.asarray(kops.spmm_bin_bin_bin(ell, fp)), want)
    assert np.array_equal(np.asarray(kref.spmm_bbb(ell, fp)), want)
    want_m = want & ~np.asarray(mp)
    assert np.array_equal(
        np.asarray(kops.spmm_bin_bin_bin(ell, fp, mp, True)), want_m)
    assert np.array_equal(
        np.asarray(kops.spmm_bin_bin_bin_bucketed(bk, fp, mp, True)), want_m)


# ---------------------------------------------------------------------------
# multi-source parity vs looped single-source runs
# ---------------------------------------------------------------------------

def reference_levels(g, source):
    """Hop levels by a plain numpy BFS over the dense adjacency."""
    adj = np.zeros((g.n_rows, g.n_cols), bool)
    adj[np.asarray(g.csr.row_idx), np.asarray(g.csr.col_idx)] = True
    levels = np.full(g.n_rows, -1, np.int32)
    levels[source] = 0
    frontier, depth = levels == 0, 0
    while frontier.any():
        depth += 1
        frontier = adj[frontier].any(axis=0) & (levels < 0)
        levels[frontier] = depth
    return levels


def assert_msbfs_matches(g, sources):
    res = msbfs(g, sources)
    assert res.levels.shape == (g.n_rows, len(sources))
    want = {s: reference_levels(g, s) for s in map(int, sources)}
    for i, s in enumerate(sources):
        assert np.array_equal(np.asarray(res.levels[:, i]),
                              want[int(s)]), f"source {s}"


@pytest.mark.parametrize("t", TILE_DIMS)
def test_msbfs_parity_tile_dims(t):
    g = build(n=96, t=t, seed=t)
    assert_msbfs_matches(g, [0, 9, 31, 64])


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("use_buckets", (True, False))
def test_msbfs_parity_backends(backend, use_buckets):
    g = build(n=80, t=8, backend=backend, seed=5, use_buckets=use_buckets)
    assert_msbfs_matches(g, [0, 3, 41])


@pytest.mark.parametrize("s_batch", (1, 8, 33, 70))
def test_msbfs_ragged_batch_sizes(s_batch):
    g = build(n=72, t=8, seed=2)
    rng = np.random.default_rng(s_batch)
    sources = rng.integers(0, g.n_rows, s_batch)   # duplicates allowed
    assert_msbfs_matches(g, list(sources))


@pytest.mark.parametrize("backend", BACKENDS)
def test_mskhop_parity(backend):
    g = build(n=80, t=8, backend=backend, seed=7)
    sources = [0, 11, 42, 42]
    for k in (1, 3):
        got = mskhop(g, sources, k)
        for i, s in enumerate(sources):
            want = khop_frontier(g, int(s), k)
            assert np.array_equal(np.asarray(got[:, i]),
                                  np.asarray(want)), (k, s)


@pytest.mark.parametrize("t", (4, 32))
def test_mskhop_parity_tile_dims(t):
    g = build(n=64, t=t, seed=t + 1)
    got = mskhop(g, [1, 30], 2)
    for i, s in enumerate((1, 30)):
        assert np.array_equal(np.asarray(got[:, i]),
                              np.asarray(khop_frontier(g, s, 2)))


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("edge_weight", (1.0, 0.5))
def test_ms_sssp_parity(backend, edge_weight):
    g = build(n=80, t=16, backend=backend, seed=3)
    sources = [2, 19, 55]
    res = ms_sssp(g, sources, edge_weight=edge_weight)
    for i, s in enumerate(sources):
        want = sssp(g, int(s), edge_weight=edge_weight).distances
        assert np.array_equal(np.asarray(res.distances[:, i]),
                              np.asarray(want)), s


@pytest.mark.parametrize("backend", BACKENDS)
def test_batched_ppr_allclose(backend):
    g = build(n=64, t=8, backend=backend, seed=9)
    seeds = [0, 7, 33]
    res = batched_ppr(g, seeds, alpha=0.85, max_iters=8, eps=0.0)
    assert res.n_iterations == 8
    for i, s in enumerate(seeds):
        want = ppr(g, int(s), alpha=0.85, max_iters=8, eps=0.0).ranks
        assert np.allclose(np.asarray(res.ranks[:, i]), np.asarray(want),
                           atol=1e-5), s


def test_batched_ppr_restart_matrix():
    g = build(n=64, t=8, seed=4)
    n = g.n_rows
    r = np.zeros((n, 2), np.float32)
    r[10, 0] = 1.0
    r[[4, 5], 1] = 0.5                       # a 2-node restart distribution
    res = batched_ppr(g, r, max_iters=6, eps=0.0)
    want0 = ppr(g, 10, max_iters=6, eps=0.0).ranks
    want1 = ppr(g, r[:, 1], max_iters=6, eps=0.0).ranks
    assert np.allclose(np.asarray(res.ranks[:, 0]), np.asarray(want0),
                       atol=1e-5)
    assert np.allclose(np.asarray(res.ranks[:, 1]), np.asarray(want1),
                       atol=1e-5)
    # ranks concentrate around the seed's neighbourhood, sanity: positive
    assert float(res.ranks[10, 0]) > 0


def test_ppr_uniform_restart_equals_pagerank():
    g = build(n=64, t=8, seed=6)
    n = g.n_rows
    uniform = np.full(n, 1.0 / n, np.float32)
    a = ppr(g, uniform, max_iters=10, eps=0.0).ranks
    b = pagerank(g, max_iters=10, eps=0.0).ranks
    assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_bfs_sssp_array_source_wrappers():
    g = build(n=72, t=8, seed=8)
    res = bfs(g, np.array([0, 5]))
    assert res.levels.shape == (72, 2)
    assert np.array_equal(np.asarray(res.levels[:, 1]),
                          np.asarray(bfs(g, 5).levels))
    d = sssp(g, [0, 5])
    assert d.distances.shape == (72, 2)
    assert np.array_equal(np.asarray(d.distances[:, 0]),
                          np.asarray(sssp(g, 0).distances))


def test_graphmatrix_entry_points():
    g = build(n=64, t=8, seed=10)
    res = g.msbfs([1, 2, 3])
    assert np.array_equal(np.asarray(res.levels[:, 2]),
                          np.asarray(bfs(g, 3).levels))
    pr = g.ppr([4, 6], max_iters=5, eps=0.0)
    assert np.allclose(np.asarray(pr.ranks[:, 0]),
                       np.asarray(ppr(g, 4, max_iters=5, eps=0.0).ranks),
                       atol=1e-5)


def test_msbfs_source_validation():
    g = build(n=32, t=8)
    with pytest.raises(ValueError):
        msbfs(g, [])
    with pytest.raises(ValueError):
        msbfs(g, [32])


# ---------------------------------------------------------------------------
# planner: cache hits, width quantisation, eviction, key sensitivity
# ---------------------------------------------------------------------------

def test_planner_cache_hit_and_eviction():
    pc = PlanCache(capacity=2)
    g = build(n=64, t=8, seed=11)
    msbfs(g, [0, 1], planner=pc)
    s = pc.stats()
    assert (s["hits"], s["misses"]) == (0, 1)
    # the historical attributes remain as read-only views of the snapshot
    assert (pc.hits, pc.misses, pc.evictions) == (0, 1, 0)
    msbfs(g, [2, 3, 4], planner=pc)          # same padded width -> hit
    s = pc.stats()
    assert (s["hits"], s["misses"]) == (1, 1)
    msbfs(g, np.arange(40), planner=pc)      # wider batch -> new plan
    s = pc.stats()
    assert (s["hits"], s["misses"]) == (1, 2)
    assert s["size"] == 2 and s["evictions"] == 0
    mskhop(g, [0], 2, planner=pc)            # third key -> LRU eviction
    s = pc.stats()
    assert s["evictions"] == 1 and s["size"] == 2 == len(pc)
    # the evicted (oldest) entry was the first msbfs plan: re-miss
    msbfs(g, [5], planner=pc)
    assert pc.stats()["misses"] == 4
    pc.reset_stats()
    assert pc.stats() == {"hits": 0, "misses": 0, "evictions": 0,
                          "size": 2, "capacity": 2}


def test_plan_key_distinguishes_layout_and_backend():
    g = build(n=64, t=8, seed=12)
    k1 = plan_key(g, "msbfs", 32)
    assert plan_key(g, "msbfs", 32) == k1             # deterministic
    assert plan_key(g, "msbfs", 64) != k1             # width
    assert plan_key(g, "mskhop", 32) != k1            # kernel
    assert plan_key(g.with_backend("csr"), "msbfs", 32) != k1
    assert plan_key(g.with_buckets(False), "msbfs", 32) != k1
    # same structure in a fresh wrapper -> same fingerprint, same key
    g2 = build(n=64, t=8, seed=12)
    assert plan_key(g2, "msbfs", 32) == k1
    # different structure -> different fingerprint
    g3 = build(n=64, t=8, seed=13)
    assert plan_key(g3, "msbfs", 32) != k1


def test_planner_shared_across_query_kinds():
    pc = PlanCache()
    g = build(n=64, t=8, seed=14)
    batched_ppr(g, [0, 1], max_iters=3, planner=pc)
    batched_ppr(g, [2], max_iters=3, planner=pc)
    assert pc.stats()["hits"] == 1 and pc.stats()["misses"] == 1
    plan = pc.get(plan_key(g, "ppr", 32), lambda: None)
    assert plan.n_calls == 2


# ---------------------------------------------------------------------------
# batcher: coalescing, pow2 padding, scatter-back
# ---------------------------------------------------------------------------

def test_batcher_coalesces_and_scatters():
    pc = PlanCache()
    qb = QueryBatcher(planner=pc)
    g = build(n=72, t=8, seed=15)
    handles = [qb.bfs(g, s) for s in (0, 9, 33, 40, 40)]
    hk = qb.khop(g, 7, k=2)
    hp = qb.ppr(g, 3, max_iters=5, eps=0.0)
    assert qb.pending() == 7
    # result() on any handle flushes everything, one launch per group
    lv = handles[0].result()
    assert qb.pending() == 0
    assert qb.n_launches == 3 and qb.n_queries == 7
    for h, s in zip(handles, (0, 9, 33, 40, 40)):
        assert np.array_equal(np.asarray(h.result()),
                              np.asarray(bfs(g, s).levels)), s
    assert np.array_equal(np.asarray(lv), np.asarray(bfs(g, 0).levels))
    assert np.array_equal(np.asarray(hk.result()),
                          np.asarray(khop_frontier(g, 7, 2)))
    assert np.allclose(np.asarray(hp.result()),
                       np.asarray(ppr(g, 3, max_iters=5, eps=0.0).ranks),
                       atol=1e-5)


def test_batcher_pow2_padding_reuses_plans():
    pc = PlanCache()
    qb = QueryBatcher(planner=pc)
    g = build(n=64, t=8, seed=16)
    for s in (0, 1, 2):                       # batch of 3 -> padded to 4
        qb.bfs(g, s)
    qb.flush()
    for s in (3, 4):                          # batch of 2 -> padded... to 2
        qb.bfs(g, s)
    qb.flush()
    # both land on the same word-padded plan width (32): 1 miss, 1 hit
    assert pc.stats()["misses"] == 1 and pc.stats()["hits"] == 1
    # different params split the group
    qb.bfs(g, 0)
    qb.bfs(g, 1, max_iters=2)
    qb.flush()
    assert qb.n_launches == 4


def test_batcher_groups_by_graph():
    qb = QueryBatcher(planner=PlanCache())
    g1 = build(n=64, t=8, seed=17)
    g2 = build(n=64, t=8, seed=18)
    h1 = qb.bfs(g1, 0)
    h2 = qb.bfs(g2, 0)
    qb.flush()
    assert qb.n_launches == 2
    assert np.array_equal(np.asarray(h1.result()),
                          np.asarray(bfs(g1, 0).levels))
    assert np.array_equal(np.asarray(h2.result()),
                          np.asarray(bfs(g2, 0).levels))


def test_batcher_sssp_kind():
    qb = QueryBatcher(planner=PlanCache())
    g = build(n=64, t=8, seed=19)
    h = qb.sssp(g, 5, edge_weight=2.0)
    assert np.array_equal(np.asarray(h.result()),
                          np.asarray(sssp(g, 5, edge_weight=2.0).distances))


def test_batcher_rejects_unknown_kind():
    qb = QueryBatcher()
    g = build(n=32, t=8)
    with pytest.raises(ValueError):
        qb.submit(g, "tarjan", 0)


def test_batcher_validates_source_at_submit():
    qb = QueryBatcher()
    g = build(n=32, t=8)
    with pytest.raises(ValueError):
        qb.bfs(g, 32)
    with pytest.raises(ValueError):
        qb.bfs(g, -1)
    assert qb.pending() == 0                  # nothing half-enqueued


def test_batcher_group_failure_isolated():
    qb = QueryBatcher(planner=PlanCache())
    g = build(n=64, t=8, seed=24)
    ok = qb.bfs(g, 3)
    bad = qb.ppr(g, 5, max_iters="nope")      # fails inside its group
    # a healthy handle's result() flushes quietly: the sibling group's
    # failure stays on the sibling's handles, not this call
    assert np.array_equal(np.asarray(ok.result()),
                          np.asarray(bfs(g, 3).levels))
    assert ok.done() and bad.done()
    with pytest.raises(QueryGroupError):
        bad.result()
    # an explicit flush is loud about its own groups' failures
    qb.ppr(g, 5, max_iters="nope")
    with pytest.raises(BatchFlushError):
        qb.flush()


def test_batcher_multi_group_failures_keep_context():
    # regression (ISSUE 5): with several failing groups in one flush, each
    # handle's error must identify *its own* group (kind + params) and
    # chain the original traceback; the aggregate lists every group in
    # submission order instead of reporting only the first
    qb = QueryBatcher(planner=PlanCache())
    g = build(n=64, t=8, seed=26)
    h_ppr = qb.ppr(g, 5, max_iters="nope")        # TypeError inside jit
    h_ok = qb.bfs(g, 3)
    h_khop = qb.khop(g, 4, k=0)                   # ValueError: k >= 1
    qb.flush(raise_errors=False)                  # quiet sweep, all groups run
    assert h_ok.done() and h_ppr.done() and h_khop.done()
    assert np.array_equal(np.asarray(h_ok.result()),
                          np.asarray(bfs(g, 3).levels))
    with pytest.raises(QueryGroupError, match="'ppr'") as ei:
        h_ppr.result()
    assert ei.value.kind == "ppr"
    assert ("max_iters", "nope") in ei.value.params
    assert ei.value.__cause__ is not None          # original traceback kept
    with pytest.raises(QueryGroupError, match="'khop'") as ei:
        h_khop.result()
    assert ei.value.kind == "khop"
    assert isinstance(ei.value.__cause__, ValueError)
    # loud flush: one aggregate naming every dead group, submission order
    a = qb.ppr(g, 5, max_iters="nope")
    b = qb.khop(g, 4, k=0)
    with pytest.raises(BatchFlushError) as ei:
        qb.flush()
    kinds = [e.kind for e in ei.value.errors]
    assert kinds == ["ppr", "khop"]
    assert a.done() and b.done()


def test_single_source_scalars_keep_single_api():
    g = build(n=64, t=8, seed=25)
    # 0-d arrays / numpy scalars are single queries, not batches
    res = bfs(g, np.array(3))
    assert res.levels.shape == (64,)
    assert np.array_equal(np.asarray(res.levels),
                          np.asarray(bfs(g, 3).levels))
    d = sssp(g, np.int64(3))
    assert d.distances.shape == (64,)
    # batched sources reject row_chunk instead of silently dropping it
    with pytest.raises(ValueError):
        bfs(g, np.array([0, 1]), row_chunk=8)
    with pytest.raises(ValueError):
        sssp(g, [0, 1], row_chunk=8)


def test_ppr_seed_validation():
    g = build(n=32, t=8)
    with pytest.raises(ValueError):
        ppr(g, 32)
    with pytest.raises(ValueError):
        ppr(g, -1)


# ---------------------------------------------------------------------------
# memoization satellites: degrees, transposed, fingerprint invalidation
# ---------------------------------------------------------------------------

def test_degrees_memoized_and_correct():
    g = build(n=64, t=8, seed=20)
    d1 = g.degrees()
    assert g.degrees() is d1
    ptr = np.asarray(g.csr.row_ptr)
    assert np.array_equal(np.asarray(d1), np.diff(ptr).astype(np.float32))
    # the transpose gets its *own* cache (in-degrees, not a stale copy)
    gt = g.transposed()
    tptr = np.asarray(gt.csr.row_ptr)
    assert np.array_equal(np.asarray(gt.degrees()),
                          np.diff(tptr).astype(np.float32))


def test_transposed_memoized_involution():
    g = build(n=64, t=8, seed=21)
    gt = g.transposed()
    assert g.transposed() is gt               # cached
    assert gt.transposed() is g               # back-reference
    # backend/bucket toggles drop the stale cached transpose
    gc = g.with_backend("csr")
    assert gc.transposed_cache is None
    assert gc.transposed().backend == "csr"
    gu = g.with_buckets(False)
    assert gu.transposed_cache is None
    assert not gu.transposed().use_buckets


def test_fingerprint_memoized_and_structure_only():
    g = build(n=64, t=8, seed=22)
    fp = g.fingerprint()
    assert g.fingerprint() is g.fingerprint_cache
    assert g.with_backend("csr").fingerprint() == fp      # backend-agnostic
    assert build(n=64, t=8, seed=22).fingerprint() == fp  # content hash
    assert build(n=64, t=8, seed=23).fingerprint() != fp
    assert g.transposed().fingerprint() != fp             # Aᵀ != A here
