"""Jitted wide-RHS wrappers over the BMV kernels (dense features, packed
activations, packed frontier matrices): one kernel pass per RHS column."""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import ops as core_ops
from repro.core.b2sr import B2SRBucketedEll, B2SREll, ceil_div
from repro.kernels import common
from repro.kernels.bmv import bmv as kernels
from repro.kernels.bmv import ops as bmv_ops


def _per_column(fn, cols: jax.Array) -> jax.Array:
    """Run ``fn`` on each column of ``cols`` in sequence -> ``[..., d]``.

    One column's gathered operand is as large as the slab itself, so the
    columns run one after another (``lax.map``) and never coexist. An
    all-zero column maps to zeros without a pass: every row here is linear
    with a zero identity (Σ, popcount, OR), and batches pad their source
    axis with zero columns.
    """
    def one(xc):
        zero = jax.eval_shape(fn, xc)
        return jax.lax.cond(jnp.any(xc != 0), fn,
                            lambda _: jnp.zeros(zero.shape, zero.dtype), xc)

    return jnp.moveaxis(jax.lax.map(one, jnp.moveaxis(cols, -1, 0)), 0, -1)


@partial(jax.jit, static_argnames=("n_rows", "interpret"))
def _spmm(col, tiles, x3, n_rows, interpret):
    t = tiles.shape[-1]
    col, tiles_w = bmv_ops.slab(col, tiles)

    def one(xc):
        out = kernels.vals_pallas(tiles_w, bmv_ops.gather_vals(col, xc), t=t,
                                  mode="sum", interpret=interpret)
        return out.reshape(-1)

    return _per_column(one, x3)[:n_rows]


def spmm(ell: B2SREll, x: jax.Array,
         interpret: Optional[bool] = None) -> jax.Array:
    """Y = A @ X for dense X [n_cols, d] (one value-kernel pass per column)."""
    interpret = common.interpret_default() if interpret is None else interpret
    t = ell.tile_dim
    n_tc = ell.n_tile_cols
    x_pad = jnp.pad(x, ((0, n_tc * t - x.shape[0]), (0, 0)))
    return _spmm(ell.tile_col_idx, ell.bit_tiles,
                 x_pad.reshape(n_tc, t, x.shape[1]), ell.n_rows, interpret)


def spmm_bucketed(b: B2SRBucketedEll, x: jax.Array,
                  interpret: Optional[bool] = None) -> jax.Array:
    """Y = A @ X with bucketed A: one pass per bucket (k_b-sized grids),
    feature rows scatter-merged through the row permutation."""
    d = x.shape[1]
    out = jnp.zeros((b.n_tile_rows, b.tile_dim, d), x.dtype)
    for i, rows in enumerate(b.rows):
        y = spmm(common.bucket_ell(b, i), x, interpret)     # [rows_b*t, d]
        out = out.at[rows].set(y.reshape(-1, b.tile_dim, d))
    return out.reshape(-1, d)[: b.n_rows]


# ---------------------------------------------------------------------------
# Packed-RHS path: activation matrices (bin·bin→full, BitGNN layers)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("n_rows", "out_dtype", "interpret"))
def _spmm_bbf(col, tiles, xw, n_rows, out_dtype, interpret):
    t = tiles.shape[-1]
    col, tiles_w = bmv_ops.slab(col, tiles)

    def one(words):
        out = kernels.bits_pallas(tiles_w, bmv_ops.gather_words(col, words),
                                  t=t, count=True, interpret=interpret)
        return out.reshape(-1)

    return _per_column(one, xw)[:n_rows].astype(out_dtype)


def spmm_bin_bin_full(ell: B2SREll, xw: jax.Array, out_dtype=jnp.float32,
                      interpret: Optional[bool] = None) -> jax.Array:
    """BitGNN aggregation: packed adjacency × BitMatrix words → dense counts.

    ``xw``: ``uint32[n_tile_cols, d]`` (one word column per feature); both
    operands stay packed end-to-end — the kernel is AND + popcount
    accumulation, never an unpack-and-matmul.
    """
    interpret = common.interpret_default() if interpret is None else interpret
    return _spmm_bbf(ell.tile_col_idx, ell.bit_tiles, xw, ell.n_rows,
                     jnp.dtype(out_dtype), interpret)


def spmm_bin_bin_full_bucketed(b: B2SRBucketedEll, xw: jax.Array,
                               out_dtype=jnp.float32,
                               interpret: Optional[bool] = None) -> jax.Array:
    """Bucketed BitGNN aggregation: per-bucket k_b grids, scatter-merged."""
    d = xw.shape[1]
    out = jnp.zeros((b.n_tile_rows, b.tile_dim, d), out_dtype)
    for i, rows in enumerate(b.rows):
        y = spmm_bin_bin_full(common.bucket_ell(b, i), xw, out_dtype,
                              interpret)
        out = out.at[rows].set(y.reshape(-1, b.tile_dim, d))
    return out.reshape(-1, d)[: b.n_rows]


# ---------------------------------------------------------------------------
# Packed-RHS path: frontier matrices (bin·bin→bin with a wide RHS, engine/)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("interpret",))
def _spmm_bbb(col, tiles, f3, interpret):
    t = tiles.shape[-1]
    n_tile_rows = col.shape[0]
    col, tiles_w = bmv_ops.slab(col, tiles)

    def one(fw):
        out = kernels.vals_pallas(tiles_w, bmv_ops.gather_vals(col, fw), t=t,
                                  mode="or", ident=0, interpret=interpret)
        return out.reshape(-1, t)

    return _per_column(one, f3)[:n_tile_rows]


def spmm_bin_bin_bin(ell: B2SREll, f_packed: jax.Array,
                     mask_packed: Optional[jax.Array] = None,
                     complement: bool = True,
                     interpret: Optional[bool] = None) -> jax.Array:
    """Multi-frontier boolean traversal: packed frontier matrix in/out.

    ``f_packed``: ``uint32[n_tile_cols, t, W]`` (``pack_frontier_matrix``);
    returns ``uint32[n_tile_rows, t, W]``. Each source word column is one
    ∨.∧ value-kernel pass (32 sources per pass); the §V mask (per-source
    visited sets, output layout) is ANDed onto the result.
    """
    interpret = common.interpret_default() if interpret is None else interpret
    out = _spmm_bbb(ell.tile_col_idx, ell.bit_tiles, f_packed, interpret)
    out = out[: ceil_div(ell.n_rows, ell.tile_dim)]
    if mask_packed is not None:
        out = core_ops.apply_frontier_mask(out, mask_packed, complement)
    return out


def spmm_bin_bin_bin_bucketed(b: B2SRBucketedEll, f_packed: jax.Array,
                              mask_packed: Optional[jax.Array] = None,
                              complement: bool = True,
                              interpret: Optional[bool] = None) -> jax.Array:
    """Bucketed multi-frontier traversal: one pass per bucket slab,
    scatter-merged; the mask is ANDed after the merge (still pre-store, §V)."""
    out = jnp.zeros((b.n_tile_rows, b.tile_dim, f_packed.shape[2]),
                    jnp.uint32)
    for i, rows in enumerate(b.rows):
        out = out.at[rows].set(spmm_bin_bin_bin(common.bucket_ell(b, i),
                                                f_packed,
                                                interpret=interpret))
    if mask_packed is not None:
        out = core_ops.apply_frontier_mask(out, mask_packed, complement)
    return out


# ---------------------------------------------------------------------------
# Dispatch-registry entries: the "b2sr_pallas" wide-RHS mxm rows
# (dense feature SpMM + packed frontier matrices, DESIGN.md §10)
# ---------------------------------------------------------------------------

from repro.core.dispatch import apply_output_mask, register  # noqa: E402


@register("mxm", "dense", "full", "b2sr_pallas", bucketed=False, masked=False)
def _mxm_dense(g, x, call):
    return spmm(g.ell, x)


@register("mxm", "dense", "full", "b2sr_pallas", bucketed=False, masked=True)
def _mxm_dense_masked(g, x, call):
    y = spmm(g.ell, x)
    return apply_output_mask(y, call.mask, call.complement,
                             call.semiring.identity_for(y.dtype))


@register("mxm", "dense", "full", "b2sr_pallas", bucketed=True, masked=False)
def _mxm_dense_bucketed(g, x, call):
    return spmm_bucketed(g.buckets(), x)


@register("mxm", "dense", "full", "b2sr_pallas", bucketed=True, masked=True)
def _mxm_dense_bucketed_masked(g, x, call):
    y = spmm_bucketed(g.buckets(), x)
    return apply_output_mask(y, call.mask, call.complement,
                             call.semiring.identity_for(y.dtype))


def _bitmat_dtype(call):
    return call.out_dtype if call.out_dtype is not None else jnp.float32


@register("mxm", "bitmat", "full", "b2sr_pallas", bucketed=False,
          masked=False)
def _mxm_bitmat(g, xw, call):
    return spmm_bin_bin_full(g.ell, xw, _bitmat_dtype(call))


@register("mxm", "bitmat", "full", "b2sr_pallas", bucketed=False, masked=True)
def _mxm_bitmat_masked(g, xw, call):
    y = spmm_bin_bin_full(g.ell, xw, _bitmat_dtype(call))
    return apply_output_mask(y, call.mask, call.complement,
                             call.semiring.identity_for(y.dtype))


@register("mxm", "bitmat", "full", "b2sr_pallas", bucketed=True, masked=False)
def _mxm_bitmat_bucketed(g, xw, call):
    return spmm_bin_bin_full_bucketed(g.buckets(), xw, _bitmat_dtype(call))


@register("mxm", "bitmat", "full", "b2sr_pallas", bucketed=True, masked=True)
def _mxm_bitmat_bucketed_masked(g, xw, call):
    y = spmm_bin_bin_full_bucketed(g.buckets(), xw, _bitmat_dtype(call))
    return apply_output_mask(y, call.mask, call.complement,
                             call.semiring.identity_for(y.dtype))


@register("mxm", "frontier", "bin", "b2sr_pallas", bucketed=False)
def _mxm_frontier(g, fw, call):
    return spmm_bin_bin_bin(g.ell, fw, call.mask, call.complement)


@register("mxm", "frontier", "bin", "b2sr_pallas", bucketed=True)
def _mxm_frontier_bucketed(g, fw, call):
    return spmm_bin_bin_bin_bucketed(g.buckets(), fw, call.mask,
                                     call.complement)


# The batched pull rows reuse the masked multi-frontier kernel: a per-row
# early exit over S stacked frontiers only fires when *all* sources'
# allowed lanes are saturated (word granularity across 32 sources), which
# on mixed-depth batches is rare enough that the fused masked sweep is the
# faster schedule — the decision record is DESIGN.md §12. Parity with the
# single-source pull row is inherited from the shared block math.

@register("mxm_pull", "frontier", "bin", "b2sr_pallas", bucketed=False,
          masked=True)
def _mxm_pull(g, fw, call):
    return spmm_bin_bin_bin(g.ell, fw, call.mask, call.complement)


@register("mxm_pull", "frontier", "bin", "b2sr_pallas", bucketed=True,
          masked=True)
def _mxm_pull_bucketed(g, fw, call):
    return spmm_bin_bin_bin_bucketed(g.buckets(), fw, call.mask,
                                     call.complement)
