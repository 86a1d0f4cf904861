"""Jitted wrapper for the Pallas masked-BMM kernel."""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.b2sr import B2SREll, bit_transpose_words
from repro.kernels import common
from repro.kernels.bmm import bmm as kernels


@partial(jax.jit, static_argnames=("block_r", "interpret"))
def _bmm(a_col, a_tiles, b_col, b_tiles_T, m_col, m_tiles, block_r, interpret):
    t = a_tiles.shape[-1]
    return kernels.bmm_bin_bin_sum_masked_pallas(
        a_col, a_tiles, b_col, b_tiles_T, m_col, m_tiles, t=t,
        block_r=block_r, interpret=interpret)


def bmm_bin_bin_sum_masked(a: B2SREll, b: B2SREll, mask: B2SREll,
                           block_r: int = 8,
                           interpret: Optional[bool] = None) -> jax.Array:
    """Σ mask ⊙ (A·B). ``b`` is given row-major; the column-major packing the
    kernel needs is produced here via the word-level bit transpose (the
    conversion-time path stores it; this wrapper recomputes when absent)."""
    interpret = common.interpret_default() if interpret is None else interpret
    common.refuse_on_tpu("bmm_bin_bin_sum_masked", interpret,
                         "it maps the whole B operand into VMEM and gathers "
                         "it in-kernel (ROADMAP 2.2)")
    a_col = common.pad_to(a.tile_col_idx, 0, block_r, fill=-1)
    a_tiles = common.pad_to(a.bit_tiles, 0, block_r)
    m_col = common.pad_to(mask.tile_col_idx, 0, block_r, fill=-1)
    m_tiles = common.pad_to(mask.bit_tiles, 0, block_r)
    b_tiles_T = bit_transpose_words(b.bit_tiles, b.tile_dim)
    out = _bmm(a_col, a_tiles, b.tile_col_idx, b_tiles_T, m_col, m_tiles,
               block_r, interpret)
    return out.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Dispatch-registry entry: the fully-fused Σ mask ⊙ (A·B) reduction
# (tri_count's "b2sr_pallas" row; bucketing does not apply to the fused
# kernel, so both flags land on the same implementation — DESIGN.md §10)
# ---------------------------------------------------------------------------

from repro.core.dispatch import BOTH, register  # noqa: E402


@register("mxm_sum", "tri", "full", "b2sr_pallas", bucketed=BOTH, masked=True)
def _tri_sum(g, tri, call):
    return bmm_bin_bin_sum_masked(tri.ell, tri.ell_t, tri.ell)
