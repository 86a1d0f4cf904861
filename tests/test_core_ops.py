"""Word-level jnp GraphBLAS ops vs dense oracles (all schemes, all tile sizes)."""

import numpy as np
import jax.numpy as jnp
import pytest

from conftest import optional_hypothesis

given, settings, st = optional_hypothesis()

from repro.core import (
    ARITHMETIC, BOOLEAN, MAX_TIMES, MIN_PLUS, TILE_DIMS, GraphMatrix,
    dense_to_b2sr, pack_bitvector, to_ell, unpack_bitvector,
)
from repro.core import ops


def random_dense(n, m, density, seed):
    rng = np.random.default_rng(seed)
    return (rng.random((n, m)) < density).astype(np.uint8)


@pytest.mark.parametrize("t", TILE_DIMS)
@pytest.mark.parametrize("n", [16, 65, 130])
def test_bmv_bin_bin_full(t, n):
    d = random_dense(n, n, 0.1, seed=n + t)
    ell = to_ell(dense_to_b2sr(d, t))
    rng = np.random.default_rng(t)
    x = rng.random(n) < 0.4
    xp = pack_bitvector(jnp.asarray(x), t, n)
    y = ops.bmv_bin_bin_full(ell, xp)
    assert np.allclose(np.asarray(y), d.astype(np.float64) @ x)


@pytest.mark.parametrize("t", TILE_DIMS)
def test_bmv_bin_bin_bin_masked(t):
    n = 90
    d = random_dense(n, n, 0.15, seed=t)
    ell = to_ell(dense_to_b2sr(d, t))
    rng = np.random.default_rng(t + 1)
    x = rng.random(n) < 0.3
    visited = rng.random(n) < 0.5
    xp = pack_bitvector(jnp.asarray(x), t, n)
    vp = pack_bitvector(jnp.asarray(visited), t, n)
    y = ops.bmv_bin_bin_bin_masked(ell, xp, vp, complement=True)
    got = np.asarray(unpack_bitvector(y, t, n, jnp.int32))
    ref = ((d @ x) > 0) & ~visited
    assert np.array_equal(got, ref.astype(np.int32))


@pytest.mark.parametrize("t", TILE_DIMS)
@pytest.mark.parametrize("semiring,a_value", [
    (ARITHMETIC, 1.0), (MIN_PLUS, 1.0), (MIN_PLUS, 2.5), (MAX_TIMES, 1.0),
])
def test_bmv_bin_full_full(t, semiring, a_value):
    n = 75
    d = random_dense(n, n, 0.12, seed=t)
    ell = to_ell(dense_to_b2sr(d, t))
    rng = np.random.default_rng(t + 2)
    x = rng.random(n).astype(np.float32) + 0.1
    y = np.asarray(ops.bmv_bin_full_full(ell, jnp.asarray(x), semiring, a_value))
    if semiring is ARITHMETIC:
        ref = d @ (a_value * x)
    elif semiring is MIN_PLUS:
        ref = np.where(d > 0, x[None, :] + a_value, np.inf).min(axis=1)
    else:
        ref = np.where(d > 0, x[None, :] * a_value, -np.inf).max(axis=1)
    assert np.allclose(y, ref, rtol=1e-5)


@pytest.mark.parametrize("t", [8, 32])
def test_bmv_masked_full(t):
    n = 66
    d = random_dense(n, n, 0.1, seed=t)
    ell = to_ell(dense_to_b2sr(d, t))
    rng = np.random.default_rng(5)
    x = rng.random(n).astype(np.float32)
    mask = (rng.random(n) < 0.5).astype(np.float32)
    y = np.asarray(ops.bmv_bin_full_full_masked(
        ell, jnp.asarray(x), jnp.asarray(mask), MIN_PLUS, 1.0, complement=False))
    full = np.where(d > 0, x[None, :] + 1.0, np.inf).min(axis=1)
    ref = np.where(mask != 0, full, np.inf)
    assert np.allclose(y, ref)


@pytest.mark.parametrize("t", TILE_DIMS)
@pytest.mark.parametrize("dfeat", [1, 7, 32])
def test_spmm(t, dfeat):
    n = 70
    d = random_dense(n, n, 0.1, seed=t + dfeat)
    ell = to_ell(dense_to_b2sr(d, t))
    rng = np.random.default_rng(7)
    X = rng.standard_normal((n, dfeat)).astype(np.float32)
    y = np.asarray(ops.spmm_b2sr(ell, jnp.asarray(X)))
    assert np.allclose(y, d @ X, rtol=1e-4, atol=1e-4)


def test_row_chunked_paths_match():
    n = 128
    t = 8
    d = random_dense(n, n, 0.1, seed=0)
    ell = to_ell(dense_to_b2sr(d, t), pad_tile_rows_to=4)
    rng = np.random.default_rng(1)
    x = rng.random(n).astype(np.float32)
    full = ops.bmv_bin_full_full(ell, jnp.asarray(x), ARITHMETIC)
    chunked = ops.bmv_bin_full_full(ell, jnp.asarray(x), ARITHMETIC, row_chunk=4)
    assert np.allclose(np.asarray(full), np.asarray(chunked), rtol=1e-6)
    X = rng.random((n, 5)).astype(np.float32)
    f2 = ops.spmm_b2sr(ell, jnp.asarray(X))
    c2 = ops.spmm_b2sr(ell, jnp.asarray(X), row_chunk=8)
    assert np.allclose(np.asarray(f2), np.asarray(c2), rtol=1e-6)


@pytest.mark.parametrize("t", TILE_DIMS)
def test_bmm_masked_triangle(t):
    n = 60
    d = random_dense(n, n, 0.15, seed=t)
    d = np.triu(d, 1)
    d = d + d.T  # symmetric simple graph
    L = np.tril(d, -1)
    eL = to_ell(dense_to_b2sr(L, t))
    eLT = to_ell(dense_to_b2sr(L.T, t))
    got = float(ops.bmm_bin_bin_sum_masked(eL, eLT, eL))
    ref = float(((L @ L.T) * L).sum())
    assert got == ref


@given(st.sampled_from(TILE_DIMS), st.integers(2, 90), st.integers(0, 500))
@settings(max_examples=15, deadline=None, derandomize=True)
def test_property_bmv_semiring_agreement(t, n, seed):
    """Property: count scheme == arithmetic bin_full_full on a 0/1 vector."""
    d = random_dense(n, n, 0.2, seed)
    ell = to_ell(dense_to_b2sr(d, t))
    rng = np.random.default_rng(seed + 1)
    x = rng.random(n) < 0.5
    xp = pack_bitvector(jnp.asarray(x), t, n)
    counts = np.asarray(ops.bmv_bin_bin_full(ell, xp))
    full = np.asarray(ops.bmv_bin_full_full(
        ell, jnp.asarray(x.astype(np.float32)), ARITHMETIC))
    assert np.allclose(counts, full)


@given(st.integers(2, 64), st.integers(0, 300))
@settings(max_examples=15, deadline=None, derandomize=True)
def test_property_backend_parity(n, seed):
    """Property: b2sr and csr GraphMatrix backends agree on mxv."""
    d = random_dense(n, n, 0.25, seed)
    g = GraphMatrix.from_dense(d, tile_dim=8)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.random(n).astype(np.float32))
    yb = np.asarray(g.with_backend("b2sr").mxv(x, ARITHMETIC))
    yc = np.asarray(g.with_backend("csr").mxv(x, ARITHMETIC))
    assert np.allclose(yb, yc, rtol=1e-5)
    ybm = np.asarray(g.with_backend("b2sr").mxv(x, MIN_PLUS))
    ycm = np.asarray(g.with_backend("csr").mxv(x, MIN_PLUS))  # csr values are 1.0
    refm = np.where(d > 0, np.asarray(x)[None, :] + 1.0, np.inf).min(axis=1)
    assert np.allclose(ybm, refm)
    assert np.allclose(ycm, refm)
