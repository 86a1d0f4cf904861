"""B2SR format: roundtrip, transpose, ELL view, storage accounting."""

import numpy as np
import jax.numpy as jnp
import pytest

from conftest import optional_hypothesis

given, settings, st = optional_hypothesis()

from repro.core import (
    TILE_DIMS, b2sr_to_dense, bit_transpose_words, compression_ratio,
    coo_to_b2sr, csr_storage_bytes, dense_to_b2sr, occupancy, pack_bitvector,
    pack_dense_tiles, to_ell, transpose, unpack_bitvector, unpack_tiles,
)
from repro.kernels.bmv.ref import dense_from_ell


def random_dense(n, m, density, seed):
    rng = np.random.default_rng(seed)
    return (rng.random((n, m)) < density).astype(np.uint8)


@pytest.mark.parametrize("t", TILE_DIMS)
@pytest.mark.parametrize("n,m,density", [(7, 7, 0.3), (64, 64, 0.05),
                                         (100, 37, 0.1), (33, 129, 0.02)])
def test_roundtrip(t, n, m, density):
    d = random_dense(n, m, density, seed=n * m + t)
    mat = dense_to_b2sr(d, t)
    assert np.array_equal(b2sr_to_dense(mat), d)
    assert mat.nnz == int(d.sum())


@pytest.mark.parametrize("t", TILE_DIMS)
def test_transpose(t):
    d = random_dense(50, 70, 0.1, seed=t)
    mat = dense_to_b2sr(d, t)
    assert np.array_equal(b2sr_to_dense(transpose(mat)), d.T)


@pytest.mark.parametrize("t", TILE_DIMS)
def test_ell_view_matches_dense(t):
    d = random_dense(60, 60, 0.08, seed=2 * t)
    ell = to_ell(dense_to_b2sr(d, t))
    back = np.asarray(dense_from_ell(ell))
    assert np.array_equal(back, d.astype(np.float32))


def test_empty_matrix():
    mat = coo_to_b2sr(np.array([]), np.array([]), 16, 16, 8)
    assert mat.n_tiles == 0
    assert np.array_equal(b2sr_to_dense(mat), np.zeros((16, 16), np.uint8))


def test_storage_accounting_table1():
    """Paper Table I: per-tile packed bytes vs 4-byte-float dense tile."""
    per_tile_bytes = {4: 4, 8: 8, 16: 32, 32: 128}
    savings = {4: 16, 8: 32, 16: 32, 32: 32}
    for t in TILE_DIMS:
        d = np.ones((t, t), np.uint8)  # one full tile
        mat = dense_to_b2sr(d, t)
        tile_bytes = mat.storage_bytes() - 4 * (mat.n_tile_rows + 1) - 4 * mat.n_tiles
        assert tile_bytes == per_tile_bytes[t]
        dense_tile_bytes = t * t * 4
        assert dense_tile_bytes // tile_bytes == savings[t]


def test_compression_beats_csr_on_diagonal():
    n = 512
    rows = np.arange(n - 1)
    cols = rows + 1
    rows = np.concatenate([rows, cols])
    cols = np.concatenate([cols, rows[: n - 1]])
    mat = coo_to_b2sr(rows, cols, n, n, 8)
    assert compression_ratio(mat) < 1.0


def test_occupancy_monotone_tile_effects():
    """Paper Fig. 3b: occupancy within non-empty tiles falls as t grows."""
    d = random_dense(256, 256, 0.02, seed=9)
    occ = [occupancy(dense_to_b2sr(d, t)) for t in TILE_DIMS]
    assert occ[0] >= occ[-1]


@given(st.integers(1, 80), st.integers(1, 80),
       st.sampled_from(TILE_DIMS), st.integers(0, 10_000))
@settings(max_examples=25, deadline=None, derandomize=True)
def test_roundtrip_property(n, m, t, seed):
    d = random_dense(n, m, 0.15, seed)
    mat = dense_to_b2sr(d, t)
    assert np.array_equal(b2sr_to_dense(mat), d)


@given(st.sampled_from(TILE_DIMS), st.integers(0, 1000))
@settings(max_examples=20, deadline=None, derandomize=True)
def test_bit_transpose_involution(t, seed):
    rng = np.random.default_rng(seed)
    words = jnp.asarray(
        rng.integers(0, 2 ** t, size=(5, t), dtype=np.uint64).astype(np.uint32))
    tt = bit_transpose_words(bit_transpose_words(words, t), t)
    assert np.array_equal(np.asarray(tt), np.asarray(words))


@given(st.sampled_from(TILE_DIMS), st.integers(1, 200), st.integers(0, 999))
@settings(max_examples=20, deadline=None, derandomize=True)
def test_pack_unpack_vector(t, n, seed):
    rng = np.random.default_rng(seed)
    x = (rng.random(n) < 0.4)
    words = pack_bitvector(jnp.asarray(x), t, n)
    back = unpack_bitvector(words, t, n, jnp.int32)
    assert np.array_equal(np.asarray(back), x.astype(np.int32))


def test_pack_dense_tiles_matches_converter():
    d = random_dense(40, 56, 0.2, seed=3)
    for t in TILE_DIMS:
        words = np.asarray(pack_dense_tiles(jnp.asarray(d), t))
        mat = dense_to_b2sr(d, t)
        ell = to_ell(mat)
        # every non-empty tile's words must match the dense packing
        col = np.asarray(ell.tile_col_idx)
        tiles = np.asarray(ell.bit_tiles)
        for i in range(ell.n_tile_rows):
            for k in range(ell.max_tiles_per_row):
                if col[i, k] >= 0:
                    assert np.array_equal(tiles[i, k], words[i, col[i, k]])


# ---------------------------------------------------------------------------
# construction at scale: lazy ELL, bucketing from the tile CSR, word-level
# transpose — each pinned bit-exact against a direct (unpacking) oracle
# ---------------------------------------------------------------------------

def _construction_graphs():
    from repro.data import graphs as G
    return {"rmat": G.rmat_graph(300, avg_degree=6, seed=3),
            "diagonal": G.diagonal_graph(300, bandwidth=4, seed=3)}


def _ell_oracle(mat):
    """The single-max ELL by a per-row loop over the tile CSR."""
    ptr = np.asarray(mat.tile_row_ptr)
    k = max(int(np.diff(ptr).max()), 1)
    col = np.full((mat.n_tile_rows, k), -1, np.int32)
    tiles = np.zeros((mat.n_tile_rows, k, mat.tile_dim), np.uint32)
    for i in range(mat.n_tile_rows):
        s, e = ptr[i], ptr[i + 1]
        col[i, :e - s] = mat.tile_col_idx[s:e]
        tiles[i, :e - s] = mat.bit_tiles[s:e]
    return col, tiles


@pytest.mark.parametrize("t", (8, 32))
@pytest.mark.parametrize("kind", ("rmat", "diagonal"))
def test_lazy_ell_matches_eager_build(kind, t):
    from repro.core import GraphMatrix
    r, c = _construction_graphs()[kind]
    g = GraphMatrix.from_coo(r, c, 300, 300, tile_dim=t)
    assert g.ell_cache is None and g.ell_t_cache is None
    col, tiles = _ell_oracle(coo_to_b2sr(r, c, 300, 300, t))
    assert np.array_equal(np.asarray(g.ell.tile_col_idx), col)
    assert np.array_equal(np.asarray(g.ell.bit_tiles), tiles)
    col_t, tiles_t = _ell_oracle(coo_to_b2sr(c, r, 300, 300, t))
    assert np.array_equal(np.asarray(g.ell_t.tile_col_idx), col_t)
    assert np.array_equal(np.asarray(g.ell_t.bit_tiles), tiles_t)


@pytest.mark.parametrize("t", (8, 32))
@pytest.mark.parametrize("kind", ("rmat", "diagonal"))
@pytest.mark.parametrize("max_buckets", (1, 2, 8))
def test_bucketing_from_tile_csr(kind, t, max_buckets):
    from repro.core import to_bucketed
    r, c = _construction_graphs()[kind]
    mat = coo_to_b2sr(r, c, 300, 300, t)
    col, tiles = _ell_oracle(mat)
    counts = np.diff(np.asarray(mat.tile_row_ptr))
    bk = to_bucketed(mat, max_buckets=max_buckets)
    assert bk.n_buckets <= max_buckets
    ranges = np.ceil(np.log2(np.maximum(counts, 1))).astype(int)
    uniq = np.unique(ranges[counts > 0])
    widest = []
    for cb, tb, rows in zip(bk.col_idx, bk.bit_tiles, bk.rows):
        rows = np.asarray(rows)
        k_b = cb.shape[1]
        # each slab is exactly the left-justified rows of the ELL view
        assert np.array_equal(np.asarray(cb), col[rows, :k_b])
        assert np.array_equal(np.asarray(tb), tiles[rows, :k_b])
        assert k_b == counts[rows].max()
        widest.append(ranges[rows].max())
    # the cap merges the narrowest ranges: every range above the merged
    # one keeps a slab of its own
    keep = uniq[max(uniq.size - max_buckets, 0) + 1:] if uniq.size > \
        max_buckets else uniq
    assert set(keep.tolist()) <= set(widest)
    assert bk.real_words() == mat.n_tiles


@pytest.mark.parametrize("t", (8, 32))
@pytest.mark.parametrize("kind", ("rmat", "diagonal"))
def test_word_level_transpose_matches_repack(kind, t):
    from repro.core.b2sr import bit_transpose_np
    r, c = _construction_graphs()[kind]
    mat = coo_to_b2sr(r, c, 300, 300, t)
    got = transpose(mat)
    want = coo_to_b2sr(c, r, 300, 300, t)           # repack of swapped COO
    for f in ("tile_row_ptr", "tile_col_idx", "bit_tiles"):
        assert np.array_equal(np.asarray(getattr(got, f)),
                              np.asarray(getattr(want, f))), f
    # the block-swap transpose against the unpack/swap/repack formula
    words = np.asarray(mat.bit_tiles)
    bits = (words[:, :, None] >> np.arange(t, dtype=np.uint32)) & 1
    oracle = (np.swapaxes(bits, 1, 2) << np.arange(t, dtype=np.uint32)).sum(
        axis=-1, dtype=np.uint32)
    assert np.array_equal(bit_transpose_np(words, t), oracle)
