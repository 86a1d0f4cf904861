"""Jitted wrapper for the Pallas SpGEMM kernel (pad + dispatch + unpad)."""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.b2sr import B2SRBucketedEll, B2SREll
from repro.core.ops import apply_grid_mask
from repro.kernels import common
from repro.kernels.spgemm import spgemm as kernels

#: why the SpGEMM kernel does not compile for the chip (ROADMAP 2.2)
_NO_TPU = ("it maps the whole B operand into VMEM and gathers it in-kernel, "
           "and its dense packed-grid output does not fit a real graph")


@partial(jax.jit, static_argnames=("t", "n_tile_cols", "mask_mode", "block_r",
                                   "interpret"))
def _mxm(a_col, a_tiles, b_col, b_tiles, m_col, m_tiles, t, n_tile_cols,
         mask_mode, block_r, interpret):
    return kernels.mxm_bin_bin_bin_pallas(
        a_col, a_tiles, b_col, b_tiles, m_col, m_tiles, t=t,
        n_tile_cols=n_tile_cols, mask_mode=mask_mode, block_r=block_r,
        interpret=interpret)


def mxm(a: B2SREll, b: B2SREll, mask: Optional[B2SREll] = None,
        complement: bool = False, block_r: int = 8,
        interpret: Optional[bool] = None) -> jax.Array:
    """Packed boolean SpGEMM grid uint32[a.n_tile_rows, b.n_tile_cols, t].

    Same contract as ``repro.core.ops.mxm_bin_bin_bin`` (compress with
    ``b2sr.packed_grid_to_b2sr``); the mask, when given, is applied in-kernel
    right before the store.
    """
    if a.tile_dim != b.tile_dim:
        raise ValueError(f"tile_dim mismatch: {a.tile_dim} vs {b.tile_dim}")
    if a.n_cols != b.n_rows:
        raise ValueError(f"inner-dim mismatch: A is {a.n_rows}x{a.n_cols}, "
                         f"B is {b.n_rows}x{b.n_cols}")
    interpret = common.interpret_default() if interpret is None else interpret
    common.refuse_on_tpu("spgemm mxm", interpret, _NO_TPU)
    t = a.tile_dim
    R = a.tile_col_idx.shape[0]
    a_col = common.pad_to(a.tile_col_idx, 0, block_r, fill=-1)
    a_tiles = common.pad_to(a.bit_tiles, 0, block_r)
    if mask is None:
        mask_mode = "none"
        m_col = jnp.full((a_col.shape[0], 1), -1, jnp.int32)
        m_tiles = jnp.zeros((a_col.shape[0], 1, t), jnp.uint32)
    else:
        if mask.tile_dim != t:
            raise ValueError("mask tile_dim mismatch")
        mask_mode = "complement" if complement else "keep"
        m_col = common.pad_to(mask.tile_col_idx, 0, block_r, fill=-1)
        m_tiles = common.pad_to(mask.bit_tiles, 0, block_r)
    out = _mxm(a_col, a_tiles, b.tile_col_idx, b.bit_tiles, m_col, m_tiles,
               t, b.n_tile_cols, mask_mode, block_r, interpret)
    return out[:R]


def mxm_bucketed(a: B2SRBucketedEll, b: B2SREll,
                 mask: Optional[B2SREll] = None, complement: bool = False,
                 block_r: int = 8,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Bucketed boolean SpGEMM grid uint32[a.n_tile_rows, b.n_tile_cols, t].

    A's tile-rows run per bucket (one pallas_call each, Ka = the bucket's
    k_b); B stays a single ELL operand gathered in-VMEM. The mask is ANDed
    after the scatter-merge — still right before the caller's store (§V).
    """
    t = a.tile_dim
    if t != b.tile_dim:
        raise ValueError(f"tile_dim mismatch: {t} vs {b.tile_dim}")
    if a.n_cols != b.n_rows:
        raise ValueError(f"inner-dim mismatch: A is {a.n_rows}x{a.n_cols}, "
                         f"B is {b.n_rows}x{b.n_cols}")
    if mask is not None and mask.tile_dim != t:
        raise ValueError("mask tile_dim mismatch")
    out = jnp.zeros((a.n_tile_rows, b.n_tile_cols, t), jnp.uint32)
    for i, rows in enumerate(a.rows):
        grid = mxm(common.bucket_ell(a, i), b, None, False, block_r,
                   interpret)                               # [rows_b, C, t]
        out = out.at[rows].set(grid)
    return apply_grid_mask(out, mask, complement)


# ---------------------------------------------------------------------------
# Dispatch-registry entries: the "b2sr_pallas" SpGEMM rows (DESIGN.md §10).
# The count rows (bin·bin→full) have no Pallas kernel yet — they register
# the jnp schemes, which is where the pre-registry dispatch sent them too.
# ---------------------------------------------------------------------------

from repro.core import ops as core_ops  # noqa: E402
from repro.core.dispatch import register  # noqa: E402


@register("mxm", "graph", "bin", "b2sr_pallas", bucketed=False)
def _mxm_graph(g, other, call):
    m_ell = call.mask.ell if call.mask is not None else None
    return mxm(g.ell, other.ell, m_ell, call.complement)


@register("mxm", "graph", "bin", "b2sr_pallas", bucketed=True)
def _mxm_graph_bucketed(g, other, call):
    m_ell = call.mask.ell if call.mask is not None else None
    return mxm_bucketed(g.buckets(), other.ell, m_ell, call.complement)


@register("mxm", "graph", "full", "b2sr_pallas", bucketed=False, masked=False)
def _mxm_graph_count(g, other, call):
    return core_ops.mxm_bin_bin_full(g.ell, other.ell,
                                     row_chunk=call.row_chunk)


@register("mxm", "graph", "full", "b2sr_pallas", bucketed=False, masked=True)
def _mxm_graph_count_masked(g, other, call):
    return core_ops.mxm_bin_bin_full_masked(g.ell, other.ell, call.mask.ell,
                                            call.complement,
                                            row_chunk=call.row_chunk)


@register("mxm", "graph", "full", "b2sr_pallas", bucketed=True, masked=False)
def _mxm_graph_count_bucketed(g, other, call):
    return core_ops.mxm_bin_bin_full_bucketed(g.buckets(), other.ell)


@register("mxm", "graph", "full", "b2sr_pallas", bucketed=True, masked=True)
def _mxm_graph_count_bucketed_masked(g, other, call):
    return core_ops.mxm_bin_bin_full_masked_bucketed(
        g.buckets(), other.ell, call.mask.ell, call.complement)
