"""B2SR SpGEMM (mxm) vs the dense boolean-matmul oracle.

Covers the Table III bin·bin→bin scheme (packed B2SR output) and the
bin·bin→full count variant, across all tile dims, all three GraphMatrix
backends, masked/complement forms, the Pallas kernel vs its ref oracle,
the packing helpers, tri_count-via-mxm, and k-hop reachability.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from conftest import optional_hypothesis

given, settings, st = optional_hypothesis()

from repro.core import (
    TILE_DIMS, GraphMatrix, b2sr_to_coo, b2sr_to_dense, coo_to_b2sr,
    dense_to_b2sr, ell_to_packed_grid, pack_tile_bits, packed_grid_to_b2sr,
    to_ell, unpack_tiles,
)
from repro.core import csr as csr_mod
from repro.core import ops
from repro.kernels.spgemm import ops as spgemm_ops, ref as spgemm_ref


def random_dense(n, m, density, seed):
    rng = np.random.default_rng(seed)
    return (rng.random((n, m)) < density).astype(np.uint8)


def dense_bool_matmul(a, b):
    return (a.astype(np.int64) @ b.astype(np.int64) > 0).astype(np.uint8)


def grid_to_dense(grid, n, m):
    return b2sr_to_dense(packed_grid_to_b2sr(np.asarray(grid), n, m))


# ---------------------------------------------------------------------------
# packing / accumulation helpers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t", TILE_DIMS)
def test_pack_tile_bits_roundtrip(t):
    d = random_dense(3 * t, 2 * t, 0.3, seed=t)
    mat = dense_to_b2sr(d, t)
    bits = unpack_tiles(mat.bit_tiles, t, jnp.uint32)
    assert np.array_equal(np.asarray(pack_tile_bits(bits, t)),
                          np.asarray(mat.bit_tiles))


@pytest.mark.parametrize("t", TILE_DIMS)
def test_ell_grid_roundtrip(t):
    d = random_dense(70, 50, 0.08, seed=t)
    mat = dense_to_b2sr(d, t)
    grid = ell_to_packed_grid(to_ell(mat))
    back = packed_grid_to_b2sr(np.asarray(grid), 70, 50)
    assert np.array_equal(b2sr_to_dense(back), d)
    assert back.nnz == int(d.sum())


@pytest.mark.parametrize("t", TILE_DIMS)
def test_b2sr_to_coo_roundtrip(t):
    d = random_dense(45, 61, 0.1, seed=t + 1)
    rows, cols = b2sr_to_coo(dense_to_b2sr(d, t))
    back = np.zeros_like(d)
    back[rows, cols] = 1
    assert np.array_equal(back, d)


# ---------------------------------------------------------------------------
# core mxm schemes vs dense oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t", TILE_DIMS)
@pytest.mark.parametrize("n,k,m,density", [(30, 40, 50, 0.15),
                                           (64, 64, 64, 0.05),
                                           (17, 33, 9, 0.3)])
def test_mxm_bin_bin_bin(t, n, k, m, density):
    da = random_dense(n, k, density, seed=n + t)
    db = random_dense(k, m, density, seed=m + t)
    grid = ops.mxm_bin_bin_bin(to_ell(dense_to_b2sr(da, t)),
                               to_ell(dense_to_b2sr(db, t)))
    assert np.array_equal(grid_to_dense(grid, n, m), dense_bool_matmul(da, db))


@pytest.mark.parametrize("t", TILE_DIMS)
@pytest.mark.parametrize("complement", [False, True])
def test_mxm_bin_bin_bin_masked(t, complement):
    da = random_dense(40, 56, 0.12, seed=t)
    db = random_dense(56, 40, 0.12, seed=2 * t)
    dm = random_dense(40, 40, 0.4, seed=3 * t)
    grid = ops.mxm_bin_bin_bin(
        to_ell(dense_to_b2sr(da, t)), to_ell(dense_to_b2sr(db, t)),
        mask=to_ell(dense_to_b2sr(dm, t)), complement=complement)
    want = dense_bool_matmul(da, db) * (1 - dm if complement else dm)
    assert np.array_equal(grid_to_dense(grid, 40, 40), want)


@pytest.mark.parametrize("t", TILE_DIMS)
def test_mxm_bin_bin_full_counts(t):
    da = random_dense(35, 42, 0.2, seed=t)
    db = random_dense(42, 28, 0.2, seed=t + 5)
    ea, eb = to_ell(dense_to_b2sr(da, t)), to_ell(dense_to_b2sr(db, t))
    counts = ops.mxm_bin_bin_full(ea, eb)
    want = da.astype(np.int64) @ db.astype(np.int64)
    assert np.array_equal(np.asarray(counts), want)
    assert np.array_equal(np.asarray(spgemm_ref.mxm_counts(ea, eb)), want)


@pytest.mark.parametrize("t", [4, 16])
@pytest.mark.parametrize("complement", [False, True])
def test_mxm_bin_bin_full_masked(t, complement):
    da = random_dense(32, 32, 0.2, seed=t)
    dm = random_dense(32, 32, 0.5, seed=t + 9)
    counts = ops.mxm_bin_bin_full_masked(
        to_ell(dense_to_b2sr(da, t)), to_ell(dense_to_b2sr(da, t)),
        to_ell(dense_to_b2sr(dm, t)), complement=complement)
    keep = (1 - dm) if complement else dm
    want = (da.astype(np.int64) @ da.astype(np.int64)) * keep
    assert np.array_equal(np.asarray(counts), want)


@pytest.mark.parametrize("t", [8, 32])
def test_mxm_row_chunked(t):
    da = random_dense(4 * t, 4 * t, 0.1, seed=t)
    ea = to_ell(dense_to_b2sr(da, t))
    full = ops.mxm_bin_bin_bin(ea, ea)
    chunked = ops.mxm_bin_bin_bin(ea, ea, row_chunk=2)
    assert np.array_equal(np.asarray(full), np.asarray(chunked))


# ---------------------------------------------------------------------------
# Pallas kernel vs oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t", TILE_DIMS)
@pytest.mark.parametrize("n,density", [(30, 0.15), (64, 0.05)])
def test_spgemm_kernel_vs_ref(t, n, density):
    da = random_dense(n, n, density, seed=n + t)
    db = random_dense(n, n, density, seed=n + t + 1)
    ea, eb = to_ell(dense_to_b2sr(da, t)), to_ell(dense_to_b2sr(db, t))
    got = spgemm_ops.mxm(ea, eb)
    want = spgemm_ref.mxm(ea, eb)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("t", [4, 32])
@pytest.mark.parametrize("complement", [False, True])
def test_spgemm_kernel_masked(t, complement):
    da = random_dense(40, 40, 0.1, seed=t)
    dm = random_dense(40, 40, 0.4, seed=t + 2)
    ea = to_ell(dense_to_b2sr(da, t))
    em = to_ell(dense_to_b2sr(dm, t))
    got = spgemm_ops.mxm(ea, ea, mask=em, complement=complement)
    want = spgemm_ref.mxm(ea, ea, mask=em, complement=complement)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_spgemm_dim_mismatch_raises():
    ea = to_ell(dense_to_b2sr(random_dense(8, 8, 0.3, 0), 4))
    eb = to_ell(dense_to_b2sr(random_dense(12, 8, 0.3, 1), 4))
    with pytest.raises(ValueError):
        spgemm_ops.mxm(ea, eb)
    with pytest.raises(ValueError):
        ops.mxm_bin_bin_bin(ea, eb)


# ---------------------------------------------------------------------------
# GraphMatrix.mxm across backends
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t", TILE_DIMS)
@pytest.mark.parametrize("backend", ["b2sr", "b2sr_pallas", "csr"])
def test_graphmatrix_mxm_backends(t, backend):
    d = random_dense(60, 60, 0.08, seed=t)
    g = GraphMatrix.from_dense(d, t, backend=backend)
    c = g.mxm()
    got = (csr_mod.to_dense(c.csr) > 0).astype(np.uint8)
    assert np.array_equal(got, dense_bool_matmul(d, d))
    assert c.backend == backend
    assert c.tile_dim == t


@pytest.mark.parametrize("backend", ["b2sr", "b2sr_pallas", "csr"])
@pytest.mark.parametrize("complement", [False, True])
def test_graphmatrix_mxm_masked(backend, complement):
    t = 8
    d = random_dense(48, 48, 0.1, seed=11)
    dm = random_dense(48, 48, 0.4, seed=12)
    g = GraphMatrix.from_dense(d, t, backend=backend)
    m = GraphMatrix.from_dense(dm, t, backend=backend)
    c = g.mxm(g, mask=m, complement=complement)
    got = (csr_mod.to_dense(c.csr) > 0).astype(np.uint8)
    want = dense_bool_matmul(d, d) * (1 - dm if complement else dm)
    assert np.array_equal(got, want)


def test_graphmatrix_mxm_rectangular():
    t = 8
    da = random_dense(24, 40, 0.15, seed=21)
    db = random_dense(40, 16, 0.15, seed=22)
    a = GraphMatrix.from_dense(da, t)
    b = GraphMatrix.from_dense(db, t)
    c = a.mxm(b)
    got = (csr_mod.to_dense(c.csr) > 0).astype(np.uint8)
    assert np.array_equal(got, dense_bool_matmul(da, db))
    assert (c.n_rows, c.n_cols) == (24, 16)


def test_graphmatrix_mxm_count():
    t = 8
    d = random_dense(40, 40, 0.15, seed=31)
    g = GraphMatrix.from_dense(d, t)
    counts = np.asarray(g.mxm_count())
    assert np.array_equal(counts, d.astype(np.int64) @ d.astype(np.int64))


# ---------------------------------------------------------------------------
# tri_count via mxm == algorithms.tc
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t", TILE_DIMS)
@pytest.mark.parametrize("backend", ["b2sr", "b2sr_pallas", "csr"])
def test_tri_count_matches_tc(t, backend):
    from repro.algorithms.tc import triangle_count
    rng = np.random.default_rng(t)
    n = 50
    d = (rng.random((n, n)) < 0.12).astype(np.uint8)
    d = ((d + d.T) > 0).astype(np.uint8)
    np.fill_diagonal(d, 0)
    g = GraphMatrix.from_dense(d, t, backend=backend)
    assert int(g.tri_count()) == int(triangle_count(g))


def test_tri_count_known_graph():
    # K4 has 4 triangles
    d = 1 - np.eye(4, dtype=np.uint8)
    for backend in ("b2sr", "b2sr_pallas", "csr"):
        g = GraphMatrix.from_dense(d, 4, backend=backend)
        assert int(g.tri_count()) == 4


# ---------------------------------------------------------------------------
# k-hop reachability via repeated masked mxm
# ---------------------------------------------------------------------------

def dense_khop(d, k):
    dl = d.astype(np.int64)
    acc, p = dl.copy(), dl.copy()
    for _ in range(k - 1):
        p = (p @ dl > 0).astype(np.int64)
        acc = ((acc + p) > 0).astype(np.int64)
    return acc.astype(np.uint8)


@pytest.mark.parametrize("t", [4, 16])
@pytest.mark.parametrize("backend", ["b2sr", "b2sr_pallas", "csr"])
def test_khop_reachability(t, backend):
    from repro.algorithms.khop import khop_reachability
    d = random_dense(40, 40, 0.06, seed=t)
    np.fill_diagonal(d, 0)
    g = GraphMatrix.from_dense(d, t, backend=backend)
    for k in (1, 2, 4):
        r = khop_reachability(g, k)
        got = (csr_mod.to_dense(r.reach.csr) > 0).astype(np.uint8)
        assert np.array_equal(got, dense_khop(d, k)), (t, backend, k)


def test_khop_early_exit():
    from repro.algorithms.khop import khop_reachability
    # path graph 0->1->2: diameter 2, so k=10 stops after 2 iterations
    d = np.zeros((3, 3), np.uint8)
    d[0, 1] = d[1, 2] = 1
    g = GraphMatrix.from_dense(d, 4)
    r = khop_reachability(g, 10)
    assert r.n_iterations <= 3
    want = np.zeros((3, 3), np.uint8)
    want[0, 1] = want[1, 2] = want[0, 2] = 1
    got = (csr_mod.to_dense(r.reach.csr) > 0).astype(np.uint8)
    assert np.array_equal(got, want)


def test_khop_frontier_matches_matrix_row():
    from repro.algorithms.khop import khop_frontier
    d = random_dense(40, 40, 0.06, seed=9)
    np.fill_diagonal(d, 0)
    g = GraphMatrix.from_dense(d, 8)
    got = np.asarray(khop_frontier(g, 0, 3))
    want = dense_khop(d, 3)[0].astype(bool)
    want[0] = False   # BFS seed semantics: source excluded
    assert np.array_equal(got, want)


# ---------------------------------------------------------------------------
# property-based cross-check (hypothesis, optional)
# ---------------------------------------------------------------------------

@given(st.sampled_from(TILE_DIMS), st.integers(2, 70), st.integers(0, 500))
@settings(max_examples=15, deadline=None, derandomize=True)
def test_mxm_property(t, n, seed):
    da = random_dense(n, n, 0.1, seed=seed)
    db = random_dense(n, n, 0.1, seed=seed + 1)
    grid = ops.mxm_bin_bin_bin(to_ell(dense_to_b2sr(da, t)),
                               to_ell(dense_to_b2sr(db, t)))
    assert np.array_equal(grid_to_dense(grid, n, n), dense_bool_matmul(da, db))
