"""Pallas kernels (interpret mode) vs pure-jnp ref.py oracles.

Sweeps shapes, tile sizes, dtypes per the kernel-test contract: for each
kernel, assert_allclose against the ref.py oracle.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from conftest import optional_hypothesis

given, settings, st = optional_hypothesis()

from repro.core import (
    ARITHMETIC, MIN_PLUS, MAX_TIMES, TILE_DIMS, dense_to_b2sr, pack_bitvector,
    to_ell,
)
from repro.kernels.bmv import ops as bmv_ops, ref as bmv_ref
from repro.kernels.bmm import ops as bmm_ops, ref as bmm_ref
from repro.kernels.spmm import ops as spmm_ops, ref as spmm_ref
from repro.kernels.bitpack import ops as bp_ops, ref as bp_ref


def random_dense(n, m, density, seed):
    rng = np.random.default_rng(seed)
    return (rng.random((n, m)) < density).astype(np.uint8)


@pytest.mark.parametrize("t", TILE_DIMS)
@pytest.mark.parametrize("n,density", [(32, 0.3), (100, 0.08), (257, 0.02)])
def test_bmv_bin_bin_full_kernel(t, n, density):
    d = random_dense(n, n, density, seed=n + t)
    ell = to_ell(dense_to_b2sr(d, t))
    rng = np.random.default_rng(0)
    xp = pack_bitvector(jnp.asarray(rng.random(n) < 0.4), t, n)
    got = bmv_ops.bmv_bin_bin_full(ell, xp)
    want = bmv_ref.bmv_bin_bin_full(ell, xp)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("t", TILE_DIMS)
@pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.int32, jnp.bfloat16])
def test_bmv_bin_bin_full_dtypes(t, out_dtype):
    n = 64
    d = random_dense(n, n, 0.2, seed=t)
    ell = to_ell(dense_to_b2sr(d, t))
    rng = np.random.default_rng(1)
    xp = pack_bitvector(jnp.asarray(rng.random(n) < 0.4), t, n)
    got = bmv_ops.bmv_bin_bin_full(ell, xp, out_dtype=out_dtype)
    assert got.dtype == out_dtype
    want = bmv_ref.bmv_bin_bin_full(ell, xp, out_dtype=out_dtype)
    np.testing.assert_allclose(np.asarray(got, np.float64),
                               np.asarray(want, np.float64))


@pytest.mark.parametrize("t", TILE_DIMS)
@pytest.mark.parametrize("complement", [True, False])
def test_bmv_bin_bin_bin_kernel(t, complement):
    n = 120
    d = random_dense(n, n, 0.1, seed=t)
    ell = to_ell(dense_to_b2sr(d, t))
    rng = np.random.default_rng(2)
    xp = pack_bitvector(jnp.asarray(rng.random(n) < 0.3), t, n)
    mp = pack_bitvector(jnp.asarray(rng.random(n) < 0.5), t, n)
    got = bmv_ops.bmv_bin_bin_bin(ell, xp, mp, complement=complement)
    want = bmv_ref.bmv_bin_bin_bin(ell, xp, mp, complement=complement)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("t", TILE_DIMS)
@pytest.mark.parametrize("semiring,a_value", [
    (ARITHMETIC, 1.0), (MIN_PLUS, 1.0), (MAX_TIMES, 0.5),
])
def test_bmv_bin_full_full_kernel(t, semiring, a_value):
    n = 77
    d = random_dense(n, n, 0.12, seed=t)
    ell = to_ell(dense_to_b2sr(d, t))
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.random(n).astype(np.float32) + 0.1)
    got = bmv_ops.bmv_bin_full_full(ell, x, semiring, a_value)
    want = bmv_ref.bmv_bin_full_full(ell, x, semiring, a_value)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("t", [4, 8, 16, 32])
@pytest.mark.parametrize("n,d_feat", [(40, 16), (96, 33), (130, 8)])
def test_spmm_kernel(t, n, d_feat):
    d = random_dense(n, n, 0.1, seed=t + n)
    ell = to_ell(dense_to_b2sr(d, t))
    rng = np.random.default_rng(4)
    X = jnp.asarray(rng.standard_normal((n, d_feat)).astype(np.float32))
    got = spmm_ops.spmm(ell, X)
    want = spmm_ref.spmm(ell, X)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("t", TILE_DIMS)
def test_bmm_kernel_triangle(t):
    n = 64
    d = random_dense(n, n, 0.15, seed=t)
    d = np.triu(d, 1); d = d + d.T
    L = np.tril(d, -1)
    eL = to_ell(dense_to_b2sr(L, t))
    eLT = to_ell(dense_to_b2sr(L.T, t))
    got = float(bmm_ops.bmm_bin_bin_sum_masked(eL, eLT, eL))
    want = float(bmm_ref.bmm_bin_bin_sum_masked(eL, eLT, eL))
    assert got == want


@pytest.mark.parametrize("t", TILE_DIMS)
@pytest.mark.parametrize("col_major", [False, True])
def test_bitpack_kernel(t, col_major):
    d = jnp.asarray(random_dense(70, 41, 0.3, seed=t))
    got = bp_ops.pack_dense(d, t, col_major=col_major)
    want = bp_ref.pack_dense(d, t, col_major=col_major)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@given(st.sampled_from(TILE_DIMS), st.integers(4, 120), st.integers(0, 400))
@settings(max_examples=10, deadline=None, derandomize=True)
def test_property_kernel_vs_oracle(t, n, seed):
    d = random_dense(n, n, 0.2, seed)
    ell = to_ell(dense_to_b2sr(d, t))
    rng = np.random.default_rng(seed)
    xp = pack_bitvector(jnp.asarray(rng.random(n) < 0.5), t, n)
    got = bmv_ops.bmv_bin_bin_full(ell, xp)
    want = bmv_ref.bmv_bin_bin_full(ell, xp)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))
