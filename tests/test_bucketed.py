"""Bucketed (SELL-style) ELL vs the single-max ELL path: bit-exact parity.

The bucketed representation (DESIGN.md §2) is a pure load-balancing
transform — every scheme must produce *identical* outputs through it:
bmv (all three Table II schemes + masks), spmm, mxm (bin and full, +mask),
across all tile dims, on skewed random graphs, including the permutation
round-trip, empty-bucket edge cases, the Pallas bucketed entry points, and
backend-transparent GraphMatrix/algorithms dispatch.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from conftest import optional_hypothesis

given, settings, st = optional_hypothesis()

from repro.core import (
    TILE_DIMS, GraphMatrix, b2sr_to_dense, coo_to_b2sr, ell_fill_ratio,
    pack_bitvector, to_bucketed, to_ell,
)
from repro.core import ops
from repro.core.semiring import ARITHMETIC, MIN_PLUS, MAX_TIMES
from repro.data import graphs as graph_gen


def skewed_coo(n, seed, hub_deg=40, base_deg=3):
    """Directed COO with a few hub rows (power-law-ish row skew)."""
    rng = np.random.default_rng(seed)
    rows = np.concatenate([
        np.repeat(np.arange(n, dtype=np.int64), base_deg),
        np.repeat(rng.choice(n, 3, replace=False).astype(np.int64), hub_deg),
    ])
    cols = rng.integers(0, n, rows.size)
    return rows, cols


def build(n, t, seed=0, **kw):
    rows, cols = skewed_coo(n, seed, **kw)
    mat = coo_to_b2sr(rows, cols, n, n, t)
    ell = to_ell(mat)
    return ell, to_bucketed(ell)


# ---------------------------------------------------------------------------
# structure: permutation round-trip, bucket invariants, fill accounting
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t", TILE_DIMS)
def test_bucket_permutation_roundtrip(t):
    n = 100
    ell, bk = build(n, t, seed=t)
    counts = np.asarray(ell.row_n_tiles)
    # every non-empty tile-row appears in exactly one bucket
    all_rows = np.concatenate([np.asarray(r) for r in bk.rows])
    assert sorted(all_rows.tolist()) == np.flatnonzero(counts > 0).tolist()
    # slabs hold exactly the original row contents (left-justified ELL)
    ell_col = np.asarray(ell.tile_col_idx)
    ell_tiles = np.asarray(ell.bit_tiles)
    for col, tiles, rows in zip(bk.col_idx, bk.bit_tiles, bk.rows):
        k_b = col.shape[1]
        assert np.array_equal(np.asarray(col), ell_col[np.asarray(rows), :k_b])
        assert np.array_equal(np.asarray(tiles),
                              ell_tiles[np.asarray(rows), :k_b])
        # no real entry of a bucketed row lives beyond its slab width
        assert (counts[np.asarray(rows)] <= k_b).all()
    # bucketing never holds more padded slots than the single-max ELL
    assert bk.real_words() == int((ell_col >= 0).sum())
    assert bk.padded_words() <= ell_col.size
    assert bk.fill_ratio() >= ell_fill_ratio(ell)


def test_bucket_width_merge_cap():
    n = 256
    ell, _ = build(n, 4, seed=9, hub_deg=60, base_deg=1)
    for max_buckets in (1, 2, 4):
        bk = to_bucketed(ell, max_buckets=max_buckets)
        assert bk.n_buckets <= max_buckets
        # merging only widens slabs; contents stay complete
        counts = np.asarray(ell.row_n_tiles)
        got = np.concatenate([np.asarray(r) for r in bk.rows])
        assert sorted(got.tolist()) == np.flatnonzero(counts > 0).tolist()


@pytest.mark.parametrize("t", (4, 16))
def test_empty_matrix_has_no_buckets(t):
    empty = np.array([], dtype=np.int64)
    ell = to_ell(coo_to_b2sr(empty, empty, 20, 20, t))
    bk = to_bucketed(ell)
    assert bk.n_buckets == 0
    xp = pack_bitvector(jnp.ones(20), t, 20)
    assert np.array_equal(np.asarray(ops.bmv_bin_bin_full_bucketed(bk, xp)),
                          np.asarray(ops.bmv_bin_bin_full(ell, xp)))
    assert np.array_equal(np.asarray(ops.bmv_bin_bin_bin_bucketed(bk, xp)),
                          np.asarray(ops.bmv_bin_bin_bin(ell, xp)))
    y = ops.bmv_bin_full_full_bucketed(bk, jnp.ones(20), MIN_PLUS)
    assert np.all(np.isinf(np.asarray(y)))


def test_uniform_rows_single_bucket():
    # identity matrix: every tile-row exactly 1 tile -> one bucket
    n = 64
    rows = np.arange(n, dtype=np.int64)
    cols = np.arange(n, dtype=np.int64)
    ell = to_ell(coo_to_b2sr(rows, cols, n, n, 8))
    bk = to_bucketed(ell)
    assert bk.n_buckets == 1
    xp = pack_bitvector(jnp.arange(n) % 3 == 0, 8, n)
    assert np.array_equal(np.asarray(ops.bmv_bin_bin_full_bucketed(bk, xp)),
                          np.asarray(ops.bmv_bin_bin_full(ell, xp)))


# ---------------------------------------------------------------------------
# jnp scheme parity (bit-exact) across tile dims
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t", TILE_DIMS)
def test_bmv_schemes_match(t):
    n = 120
    ell, bk = build(n, t, seed=t + 1)
    rng = np.random.default_rng(t)
    x = jnp.asarray(rng.random(n).astype(np.float32))
    xp = pack_bitvector(x > 0.5, t, n)
    mp = pack_bitvector(x > 0.3, t, n)

    assert np.array_equal(np.asarray(ops.bmv_bin_bin_bin(ell, xp)),
                          np.asarray(ops.bmv_bin_bin_bin_bucketed(bk, xp)))
    assert np.array_equal(
        np.asarray(ops.bmv_bin_bin_bin_masked(ell, xp, mp, complement=True)),
        np.asarray(ops.bmv_bin_bin_bin_bucketed_masked(bk, xp, mp,
                                                       complement=True)))
    assert np.array_equal(
        np.asarray(ops.bmv_bin_bin_full(ell, xp, jnp.int32)),
        np.asarray(ops.bmv_bin_bin_full_bucketed(bk, xp, jnp.int32)))
    for sr in (ARITHMETIC, MIN_PLUS, MAX_TIMES):
        assert np.array_equal(
            np.asarray(ops.bmv_bin_full_full(ell, x, sr, a_value=1.0)),
            np.asarray(ops.bmv_bin_full_full_bucketed(bk, x, sr,
                                                      a_value=1.0))), sr.name


@pytest.mark.parametrize("t", TILE_DIMS)
def test_spmm_matches(t):
    n = 96
    ell, bk = build(n, t, seed=t + 2)
    rng = np.random.default_rng(t)
    x = jnp.asarray(rng.random((n, 9)).astype(np.float32))
    assert np.array_equal(np.asarray(ops.spmm_b2sr(ell, x)),
                          np.asarray(ops.spmm_b2sr_bucketed(bk, x)))


@pytest.mark.parametrize("t", TILE_DIMS)
def test_mxm_matches(t):
    n = 72
    ell, bk = build(n, t, seed=t + 3, hub_deg=25, base_deg=2)
    # boolean grid, plain + masked/complement
    assert np.array_equal(np.asarray(ops.mxm_bin_bin_bin(ell, ell)),
                          np.asarray(ops.mxm_bin_bin_bin_bucketed(bk, ell)))
    for comp in (False, True):
        assert np.array_equal(
            np.asarray(ops.mxm_bin_bin_bin(ell, ell, mask=ell,
                                           complement=comp)),
            np.asarray(ops.mxm_bin_bin_bin_bucketed(bk, ell, mask=ell,
                                                    complement=comp)))
    # count SpGEMM, plain + masked
    assert np.array_equal(np.asarray(ops.mxm_bin_bin_full(ell, ell)),
                          np.asarray(ops.mxm_bin_bin_full_bucketed(bk, ell)))
    assert np.array_equal(
        np.asarray(ops.mxm_bin_bin_full_masked(ell, ell, ell)),
        np.asarray(ops.mxm_bin_bin_full_masked_bucketed(bk, ell, ell)))


def test_rmat_graph_parity_and_skew():
    n = 256
    rows, cols = graph_gen.rmat_graph(n, avg_degree=8, seed=5,
                                      symmetric=False)
    assert rows.size > 0 and (rows != cols).all()
    ell = to_ell(coo_to_b2sr(rows, cols, n, n, 8))
    bk = to_bucketed(ell)
    counts = np.asarray(ell.row_n_tiles)
    nz = counts[counts > 0]
    assert nz.max() / nz.mean() > 2.0  # power-law rows are actually skewed
    xp = pack_bitvector(jnp.arange(n) % 2 == 0, 8, n)
    assert np.array_equal(np.asarray(ops.bmv_bin_bin_full(ell, xp)),
                          np.asarray(ops.bmv_bin_bin_full_bucketed(bk, xp)))


# ---------------------------------------------------------------------------
# Pallas bucketed entry points (interpret mode) vs the jnp bucketed path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t", (4, 8, 32))
def test_pallas_bucketed_bmv(t):
    from repro.kernels.bmv import ops as kbmv
    # the smallest graphs with more than one bucket; at t=32 these skewed
    # graphs stay one bucket at every size up to n=320
    n = {4: 17, 8: 33, 32: 96}[t]
    ell, bk = build(n, t, seed=t + 4)
    assert bk.n_buckets > 1 or t == 32
    rng = np.random.default_rng(t)
    x = jnp.asarray(rng.random(n).astype(np.float32))
    xp = pack_bitvector(x > 0.5, t, n)
    mp = pack_bitvector(x > 0.2, t, n)
    assert np.array_equal(
        np.asarray(kbmv.bmv_bin_bin_full_bucketed(bk, xp, jnp.int32)),
        np.asarray(ops.bmv_bin_bin_full_bucketed(bk, xp, jnp.int32)))
    assert np.array_equal(
        np.asarray(kbmv.bmv_bin_bin_bin_bucketed(bk, xp, mp, True)),
        np.asarray(ops.bmv_bin_bin_bin_bucketed_masked(bk, xp, mp, True)))
    for sr in (ARITHMETIC, MIN_PLUS):
        assert np.allclose(
            np.asarray(kbmv.bmv_bin_full_full_bucketed(bk, x, sr)),
            np.asarray(ops.bmv_bin_full_full_bucketed(bk, x, sr)),
            atol=1e-5), sr.name


@pytest.mark.parametrize("t", (8, 16))
def test_pallas_bucketed_spmm_mxm(t):
    from repro.kernels.spmm import ops as kspmm
    from repro.kernels.spgemm import ops as kspgemm
    n = 64
    ell, bk = build(n, t, seed=t + 5, hub_deg=20, base_deg=2)
    rng = np.random.default_rng(t)
    x = jnp.asarray(rng.random((n, 8)).astype(np.float32))
    assert np.allclose(np.asarray(kspmm.spmm_bucketed(bk, x)),
                       np.asarray(ops.spmm_b2sr_bucketed(bk, x)), atol=1e-5)
    assert np.array_equal(
        np.asarray(kspgemm.mxm_bucketed(bk, ell, mask=ell, complement=True)),
        np.asarray(ops.mxm_bin_bin_bin_bucketed(bk, ell, mask=ell,
                                                complement=True)))


# ---------------------------------------------------------------------------
# GraphMatrix dispatch: bucketed default == unbucketed, zero call-site change
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ("b2sr", "b2sr_pallas"))
def test_graphmatrix_transparent(backend):
    from repro.algorithms import bfs, sssp, pagerank
    n = 25                      # the smallest graph with more than one bucket
    rows, cols = skewed_coo(n, seed=11, hub_deg=20, base_deg=2)
    g_b = GraphMatrix.from_coo(rows, cols, n, n, tile_dim=8, backend=backend)
    g_u = g_b.with_buckets(False)
    assert g_b.use_buckets and not g_u.use_buckets
    assert g_b.buckets().n_buckets > 1

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.random(n).astype(np.float32))
    xp = g_b.pack(x > 0.5)
    assert np.array_equal(np.asarray(g_b.mxv_bool(xp)),
                          np.asarray(g_u.mxv_bool(xp)))
    assert np.array_equal(np.asarray(g_b.mxv_count(xp, jnp.int32)),
                          np.asarray(g_u.mxv_count(xp, jnp.int32)))
    assert np.allclose(np.asarray(g_b.mxv(x)), np.asarray(g_u.mxv(x)),
                       atol=1e-5)
    assert np.allclose(np.asarray(g_b.spmm(x[:, None])),
                       np.asarray(g_u.spmm(x[:, None])), atol=1e-5)
    # algorithms ride the bucketed path with zero call-site changes
    lv_b = bfs(g_b, source=0).levels
    lv_u = bfs(g_u, source=0).levels
    assert np.array_equal(np.asarray(lv_b), np.asarray(lv_u))
    if backend == "b2sr":
        d_b = sssp(g_b, source=0).distances
        d_u = sssp(g_u, source=0).distances
        assert np.array_equal(np.asarray(d_b), np.asarray(d_u))
        pr_b = pagerank(g_b, max_iters=5).ranks
        pr_u = pagerank(g_u, max_iters=5).ranks
        assert np.allclose(np.asarray(pr_b), np.asarray(pr_u), atol=1e-6)
        assert float(g_b.tri_count()) == float(g_u.tri_count())
        c_b = b2sr_to_dense_of(g_b.mxm(g_b))
        c_u = b2sr_to_dense_of(g_u.mxm(g_u))
        assert np.array_equal(c_b, c_u)
        assert np.array_equal(np.asarray(g_b.mxm_count(g_b)),
                              np.asarray(g_u.mxm_count(g_u)))


def b2sr_to_dense_of(g: GraphMatrix) -> np.ndarray:
    from repro.core import csr as csr_mod
    return np.asarray(csr_mod.to_dense(g.csr))


def test_transposed_swaps_and_caches_buckets():
    n = 60
    rows, cols = skewed_coo(n, seed=3)
    g = GraphMatrix.from_coo(rows, cols, n, n, tile_dim=8)
    g.buckets()                       # force lazy build on the forward view
    gt = g.transposed()
    # transposed() builds the transpose's buckets eagerly and caches them on
    # g, so repeated transposed()/vxm calls don't re-run host bucketing
    assert g.ell_buckets_t is not None
    assert gt.ell_buckets is g.ell_buckets_t
    assert gt.ell_buckets_t is g.ell_buckets
    assert g.transposed().ell_buckets is gt.ell_buckets
    # vxm == mxv on the transpose, bucketed on both sides
    x = jnp.asarray(np.random.default_rng(1).random(n).astype(np.float32))
    assert np.allclose(np.asarray(g.vxm(x)), np.asarray(gt.mxv(x)), atol=1e-6)


def test_bfs_termination_word_sum_regression():
    """frontier word-sums that overflow uint32 must not stop BFS early.

    A star graph from node 0 makes iteration-1's frontier words dense;
    with the old uint64-astype (truncated to uint32 without x64) a
    carefully-sized frontier could sum to 0 mod 2^32. jnp.any is exact;
    here we just pin the behaviour: all nodes get level 1.
    """
    n = 128
    rows = np.zeros(n - 1, np.int64)
    cols = np.arange(1, n, dtype=np.int64)
    from repro.algorithms import bfs
    g = GraphMatrix.from_coo(rows, cols, n, n, tile_dim=32)
    res = bfs(g, source=0)
    lv = np.asarray(res.levels)
    assert lv[0] == 0 and (lv[1:] == 1).all()


# ---------------------------------------------------------------------------
# property test: bucketing is invisible for any COO set (optional hypothesis)
# ---------------------------------------------------------------------------

@given(st.data())
@settings(max_examples=25, deadline=None, derandomize=True)
def test_bucketed_bmv_property(data):
    n = data.draw(st.integers(min_value=1, max_value=64), label="n")
    t = data.draw(st.sampled_from(TILE_DIMS), label="t")
    m = data.draw(st.integers(min_value=0, max_value=200), label="nnz")
    seed = data.draw(st.integers(min_value=0, max_value=2**31), label="seed")
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, n, m)
    cols = rng.integers(0, n, m)
    ell = to_ell(coo_to_b2sr(rows, cols, n, n, t))
    bk = to_bucketed(ell)
    xp = pack_bitvector(jnp.asarray(rng.random(n) > 0.4), t, n)
    assert np.array_equal(np.asarray(ops.bmv_bin_bin_full(ell, xp)),
                          np.asarray(ops.bmv_bin_bin_full_bucketed(bk, xp)))
    assert np.array_equal(np.asarray(ops.bmv_bin_bin_bin(ell, xp)),
                          np.asarray(ops.bmv_bin_bin_bin_bucketed(bk, xp)))
