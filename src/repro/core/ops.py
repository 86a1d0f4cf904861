"""GraphBLAS-style operations over B2SR (jnp reference path).

This module is the device-side *algorithm* layer: every scheme from the paper
(Tables II & III) implemented with word-level bit operations in pure jnp. The
Pallas kernels in ``repro.kernels`` implement the same schemes with explicit
VMEM tiling; both paths are interchangeable behind ``repro.core.graphblas``.

Scheme naming follows the paper:
  bmv_bin_bin_bin     A:1-bit, x:1-bit, y:1-bit        (boolean semiring)
  bmv_bin_bin_full    A:1-bit, x:1-bit, y:32-bit       (counts)
  bmv_bin_full_full   A:1-bit, x:full,  y:full          (any semiring)
  *_masked            mask applied right before the output store (paper §V)
  bmm_bin_bin_sum     A,B:1-bit, out: scalar sum        (+ masked, for TC)
  mxm_bin_bin_bin     A,B:1-bit, C:1-bit packed grid     (boolean SpGEMM)
  mxm_bin_bin_full    A,B:1-bit, C:32-bit dense counts   (count SpGEMM)

TPU mapping: AND+popcount over uint32 words == the paper's __popc(a & b);
everything is batched over the ELL view so shapes are static.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.b2sr import (
    B2SRBucketedEll,
    B2SREll,
    ceil_div,
    ell_to_packed_grid,
    or_reduce_words,
    pack_bitvector,
    unpack_bitvector,
    unpack_tiles,
)
from repro.core.semiring import Semiring, ARITHMETIC, BOOLEAN, MIN_PLUS


def _popcount(x: jax.Array) -> jax.Array:
    return jax.lax.population_count(x)

def _reduce(semiring: Semiring, arr: jax.Array, axis) -> jax.Array:
    """⊕-reduction along ``axis`` for the supported monoids."""
    if semiring.add is jnp.add:
        return jnp.sum(arr, axis=axis)
    if semiring.add is jnp.minimum:
        return jnp.min(arr, axis=axis)
    if semiring.add is jnp.maximum:
        return jnp.max(arr, axis=axis)
    if semiring.add is jnp.logical_or:
        return jnp.any(arr, axis=axis)
    raise NotImplementedError(semiring.name)


def _gather_words(x_words: jax.Array, col_idx: jax.Array) -> jax.Array:
    """Gather packed vector words by tile-col index; padding (-1) -> word 0."""
    safe = jnp.clip(col_idx, 0, x_words.shape[0] - 1)
    g = x_words[safe]
    return jnp.where(col_idx >= 0, g, jnp.uint32(0))


def _row_chunks(n_rows: int, row_chunk: Optional[int]) -> int:
    if row_chunk is None or row_chunk >= n_rows:
        return n_rows
    return row_chunk


def _mapped_over_rows(fn, arrays, n_rows: int, row_chunk: Optional[int]):
    """Apply ``fn`` to row-chunks of the leading axis and concatenate.

    Bounded-memory evaluation for large graphs (lax.map over chunks).
    """
    c = _row_chunks(n_rows, row_chunk)
    if c == n_rows:
        return fn(*arrays)
    if n_rows % c != 0:
        raise ValueError(f"row_chunk {c} must divide n_rows {n_rows} (pad the ELL view)")
    nb = n_rows // c
    reshaped = tuple(a.reshape((nb, c) + a.shape[1:]) for a in arrays)
    out = jax.lax.map(lambda xs: fn(*xs), reshaped)
    return jax.tree_util.tree_map(
        lambda o: o.reshape((nb * c,) + o.shape[2:]) if o.ndim >= 2 else o.reshape(-1),
        out,
    )


# ---------------------------------------------------------------------------
# BMV schemes
#
# Each scheme's per-slab math lives in a ``_*_block`` helper taking raw
# ``(col_idx, tiles)`` ELL arrays, so the single-ELL path (mapped over row
# chunks) and the bucketed path (one call per bucket slab, scatter-merged
# through the row permutation) run the exact same computation.
# ---------------------------------------------------------------------------

def _bmv_bbb_block(col_idx: jax.Array, tiles: jax.Array, x_packed: jax.Array,
                   t: int) -> jax.Array:
    """bin·bin→bin on one ELL slab: packed words uint32[R]."""
    xw = _gather_words(x_packed, col_idx)              # [R, K]
    hit = (tiles & xw[:, :, None]) != 0                # [R, K, t]
    bits = jnp.any(hit, axis=1)                        # [R, t]
    shifts = jnp.arange(t, dtype=jnp.uint32)
    return jnp.sum(bits.astype(jnp.uint32) << shifts[None, :], axis=1,
                   dtype=jnp.uint32)


def bmv_bin_bin_bin(ell: B2SREll, x_packed: jax.Array,
                    row_chunk: Optional[int] = None) -> jax.Array:
    """Boolean mxv (Table II row bin·bin→bin): packed frontier in/out.

    y_bit[i*t+r] = OR_j A[i*t+r, j] & x[j]  == any(word_r & x_word != 0).
    """
    def chunk(col_idx, tiles):
        return _bmv_bbb_block(col_idx, tiles, x_packed, ell.tile_dim)
    return _mapped_over_rows(chunk, (ell.tile_col_idx, ell.bit_tiles),
                             ell.n_tile_rows, row_chunk)


def bmv_bin_bin_bin_bucketed(b: B2SRBucketedEll, x_packed: jax.Array) -> jax.Array:
    """Bucketed boolean mxv: per-bucket slabs, outputs scattered by row id.

    Empty tile-rows are in no bucket and keep the zero word (OR-identity).
    """
    out = jnp.zeros((b.n_tile_rows,), jnp.uint32)
    for col, tiles, rows in zip(b.col_idx, b.bit_tiles, b.rows):
        out = out.at[rows].set(_bmv_bbb_block(col, tiles, x_packed, b.tile_dim))
    return out


def bmv_bin_bin_bin_bucketed_masked(b: B2SRBucketedEll, x_packed: jax.Array,
                                    mask_packed: jax.Array,
                                    complement: bool = True) -> jax.Array:
    """Masked bucketed boolean mxv (§V mask ANDed right before the store)."""
    y = bmv_bin_bin_bin_bucketed(b, x_packed)
    m = mask_packed if not complement else ~mask_packed
    return y & m


def bmv_bin_bin_bin_masked(ell: B2SREll, x_packed: jax.Array,
                           mask_packed: jax.Array, complement: bool = True,
                           row_chunk: Optional[int] = None) -> jax.Array:
    """Masked boolean mxv (Table II bin·bin→bin + §V mask): the BFS kernel.

    The mask is ANDed right before the output store; ``complement=True``
    keeps bits where the mask bit is 0 (unvisited).
    """
    y = bmv_bin_bin_bin(ell, x_packed, row_chunk)
    m = mask_packed if not complement else ~mask_packed
    return y & m


def bmv_bin_bin_bin_pull(ell: B2SREll, x_packed: jax.Array,
                         mask_packed: jax.Array, complement: bool = True,
                         row_chunk: Optional[int] = None) -> jax.Array:
    """Pull-direction boolean mxv: the jnp twin of the fused pull kernel.

    Pull traversal is the *same* bin·bin→bin reduction over the transposed
    operand the caller already passes (``direction`` never re-transposes);
    what differs is the evaluation order — the Pallas twin
    (``kernels.bmv.ops.bmv_bin_bin_bin_pull``) stops computing a row block
    once every §V-allowed lane of it is set. jnp has no data-dependent row
    exit (SIMD over
    the whole slab), so this twin runs the identical ``_bmv_bbb_block``
    math as masked push — which is exactly what makes the pull row
    bit-exact against push by construction (DESIGN.md §12).
    """
    return bmv_bin_bin_bin_masked(ell, x_packed, mask_packed, complement,
                                  row_chunk)


def bmv_bin_bin_bin_pull_bucketed(b: B2SRBucketedEll, x_packed: jax.Array,
                                  mask_packed: jax.Array,
                                  complement: bool = True) -> jax.Array:
    """Bucketed jnp pull twin — same `_bmv_bbb_block` math, same parity."""
    return bmv_bin_bin_bin_bucketed_masked(b, x_packed, mask_packed,
                                           complement)


def _bmv_bbf_block(col_idx: jax.Array, tiles: jax.Array, x_packed: jax.Array,
                   out_dtype) -> jax.Array:
    """bin·bin→full on one ELL slab: counts [R, t]."""
    xw = _gather_words(x_packed, col_idx)               # [R, K]
    counts = _popcount(tiles & xw[:, :, None])          # [R, K, t]
    return jnp.sum(counts, axis=1).astype(out_dtype)    # [R, t]


def bmv_bin_bin_full(ell: B2SREll, x_packed: jax.Array,
                     out_dtype=jnp.float32,
                     row_chunk: Optional[int] = None) -> jax.Array:
    """Count mxv (Table II row bin·bin→full): per-row AND+popcount sums.

    y[i*t+r] = Σ popcount(word_r & x_word) — the paper's __popc(a & b)
    over uint32 VREG lanes.
    """
    def chunk(col_idx, tiles):
        return _bmv_bbf_block(col_idx, tiles, x_packed, out_dtype)

    out = _mapped_over_rows(chunk, (ell.tile_col_idx, ell.bit_tiles),
                            ell.n_tile_rows, row_chunk)
    return out.reshape(-1)[: ell.n_rows]


def bmv_bin_bin_full_bucketed(b: B2SRBucketedEll, x_packed: jax.Array,
                              out_dtype=jnp.float32) -> jax.Array:
    """Bucketed count mxv: empty tile-rows keep the 0 count (Σ-identity)."""
    out = jnp.zeros((b.n_tile_rows, b.tile_dim), out_dtype)
    for col, tiles, rows in zip(b.col_idx, b.bit_tiles, b.rows):
        out = out.at[rows].set(_bmv_bbf_block(col, tiles, x_packed, out_dtype))
    return out.reshape(-1)[: b.n_rows]


def bmv_bin_bin_full_masked(ell: B2SREll, x_packed: jax.Array, mask: jax.Array,
                            complement: bool = False, out_dtype=jnp.float32,
                            row_chunk: Optional[int] = None) -> jax.Array:
    """Masked count mxv (Table II bin·bin→full + §V mask-at-store)."""
    y = bmv_bin_bin_full(ell, x_packed, out_dtype, row_chunk)
    keep = (mask == 0) if complement else (mask != 0)
    return jnp.where(keep, y, jnp.zeros((), out_dtype))


def bmv_bin_full_full(ell: B2SREll, x: jax.Array,
                      semiring: Semiring = ARITHMETIC,
                      a_value: float = 1.0,
                      row_chunk: Optional[int] = None) -> jax.Array:
    """General-semiring mxv (Table II row bin·full→full).

    y_i = ⊕_j  (A_ij ? a_value ⊗ x_j : ⊕-identity).
    The paper's SSSP/PR/CC workhorse (min-plus uses a_value=edge weight 1).
    Scans over the K (tiles-per-row) axis for bounded memory.
    """
    x3, ident, av = _bff_setup(ell.n_tile_cols, ell.tile_dim, x, semiring,
                               a_value)

    def chunk(col_idx, tiles):
        return _bmv_bff_block(col_idx, tiles, x3, semiring, av, ident,
                              ell.tile_dim)

    out = _mapped_over_rows(chunk, (ell.tile_col_idx, ell.bit_tiles),
                            ell.n_tile_rows, row_chunk)
    return out.reshape(-1)[: ell.n_rows]


def _bff_setup(n_tc: int, t: int, x: jax.Array, semiring: Semiring,
               a_value: float):
    """Shared bin·full→full operand prep: padded x tiles, identity, a_value."""
    ident = semiring.identity_for(x.dtype)
    x_pad = jnp.pad(x, (0, n_tc * t - x.shape[0]), constant_values=ident)
    return x_pad.reshape(n_tc, t), ident, jnp.asarray(a_value, dtype=x.dtype)


def _bmv_bff_block(col_idx: jax.Array, tiles: jax.Array, x3: jax.Array,
                   semiring: Semiring, av: jax.Array, ident, t: int) -> jax.Array:
    """bin·full→full on one ELL slab: ⊕-accumulated values [R, t]."""
    n_tc = x3.shape[0]
    K = col_idx.shape[1]

    def step(acc, k):
        cols = col_idx[:, k]                                # [R]
        words = tiles[:, k]                                 # [R, t]
        bits = unpack_tiles(words, t, dtype=jnp.bool_)      # [R, t(row), t(col)]
        xk = x3[jnp.clip(cols, 0, n_tc - 1)]                # [R, t]
        xk = jnp.where((cols >= 0)[:, None], xk, ident)
        contrib = jnp.where(bits, semiring.mul(av, xk[:, None, :]), ident)
        red = _reduce(semiring, contrib, axis=2)
        return semiring.add(acc, red), None

    acc0 = jnp.full((col_idx.shape[0], t), ident, dtype=x3.dtype)
    acc, _ = jax.lax.scan(step, acc0, jnp.arange(K))
    return acc


def bmv_bin_full_full_bucketed(b: B2SRBucketedEll, x: jax.Array,
                               semiring: Semiring = ARITHMETIC,
                               a_value: float = 1.0) -> jax.Array:
    """Bucketed general-semiring mxv: empty tile-rows keep the ⊕-identity."""
    x3, ident, av = _bff_setup(b.n_tile_cols, b.tile_dim, x, semiring, a_value)
    out = jnp.full((b.n_tile_rows, b.tile_dim), ident, dtype=x.dtype)
    for col, tiles, rows in zip(b.col_idx, b.bit_tiles, b.rows):
        out = out.at[rows].set(
            _bmv_bff_block(col, tiles, x3, semiring, av, ident, b.tile_dim))
    return out.reshape(-1)[: b.n_rows]


def bmv_bin_full_full_masked(ell: B2SREll, x: jax.Array, mask: jax.Array,
                             semiring: Semiring = ARITHMETIC,
                             a_value: float = 1.0, complement: bool = False,
                             row_chunk: Optional[int] = None) -> jax.Array:
    """Masked general-semiring mxv (Table II bin·full→full + §V mask)."""
    y = bmv_bin_full_full(ell, x, semiring, a_value, row_chunk)
    keep = (mask == 0) if complement else (mask != 0)
    return jnp.where(keep, y, semiring.identity_for(y.dtype))


def vxm(ell_T: B2SREll, x, **kw):
    """vxm (Table II, pull direction): vᵀ·A == Aᵀ·v.

    Callers pass the transposed B2SR — the paper stores both layouts.
    """
    return bmv_bin_full_full(ell_T, x, **kw)


# ---------------------------------------------------------------------------
# SpMM: B2SR × dense feature matrix (GNN aggregation hot path)
# ---------------------------------------------------------------------------

def spmm_b2sr(ell: B2SREll, x: jax.Array, out_dtype=None,
              row_chunk: Optional[int] = None,
              vma_axes: tuple = ()) -> jax.Array:
    """Y = A @ X with binary A in B2SR and dense X [n_cols, d].

    The Table II bin·full→full scheme widened to a dense right-hand matrix
    (the GraphBLAST mxm-with-dense analogue; not a paper table row).

    TPU-native formulation: each bit tile is unpacked (VPU shifts) into a
    t×t 0/1 matrix that feeds the MXU against the gathered X tile — HBM
    traffic is 1 bit/element, compute is dense matmul. Scan over K bounds
    memory. This is the paper's technique promoted to the GNN hot path.
    """
    t = ell.tile_dim
    n_tc = ell.n_tile_cols
    d = x.shape[1]
    out_dtype = out_dtype or x.dtype
    x_pad = jnp.pad(x, ((0, n_tc * t - x.shape[0]), (0, 0)))
    x3 = x_pad.reshape(n_tc, t, d)

    def chunk(col_idx, tiles):
        return _spmm_block(col_idx, tiles, x3, t, out_dtype, vma_axes)

    out = _mapped_over_rows(chunk, (ell.tile_col_idx, ell.bit_tiles),
                            ell.n_tile_rows, row_chunk)
    return out.reshape(-1, d)[: ell.n_rows]


def _spmm_block(col_idx: jax.Array, tiles: jax.Array, x3: jax.Array, t: int,
                out_dtype, vma_axes: tuple = ()) -> jax.Array:
    """SpMM on one ELL slab: accumulated feature tiles [R, t, d]."""
    n_tc, _, d = x3.shape
    K = col_idx.shape[1]

    def step(acc, k):
        cols = col_idx[:, k]
        words = tiles[:, k]
        bits = unpack_tiles(words, t, dtype=x3.dtype)       # [R, t, t]
        xk = x3[jnp.clip(cols, 0, n_tc - 1)]                # [R, t, d]
        xk = jnp.where((cols >= 0)[:, None, None], xk, 0)
        return acc + jnp.einsum("rab,rbd->rad", bits, xk,
                                preferred_element_type=out_dtype), None

    acc0 = jnp.zeros((col_idx.shape[0], t, d), dtype=out_dtype)
    if vma_axes:
        # under shard_map the body output varies over the mesh axes;
        # the init carry must be marked varying too (scan-vma rule)
        acc0 = jax.lax.pvary(acc0, tuple(vma_axes))
    acc, _ = jax.lax.scan(step, acc0, jnp.arange(K))
    return acc


def spmm_b2sr_bucketed(b: B2SRBucketedEll, x: jax.Array,
                       out_dtype=None) -> jax.Array:
    """Bucketed SpMM: each bucket runs with its own static k_b, outputs
    scattered back through the row permutation. Empty tile-rows stay 0."""
    t = b.tile_dim
    n_tc = b.n_tile_cols
    d = x.shape[1]
    out_dtype = out_dtype or x.dtype
    x_pad = jnp.pad(x, ((0, n_tc * t - x.shape[0]), (0, 0)))
    x3 = x_pad.reshape(n_tc, t, d)
    out = jnp.zeros((b.n_tile_rows, t, d), dtype=out_dtype)
    for col, tiles, rows in zip(b.col_idx, b.bit_tiles, b.rows):
        out = out.at[rows].set(_spmm_block(col, tiles, x3, t, out_dtype))
    return out.reshape(-1, d)[: b.n_rows]


# the GNN-facing scheme name (ISSUE 9 / DESIGN.md §15): bin adjacency ×
# full activations → full output is exactly the widened Table II scheme
spmm_bin_full_full = spmm_b2sr
spmm_bin_full_full_bucketed = spmm_b2sr_bucketed


# ---------------------------------------------------------------------------
# SpMM over packed *activation* matrices: bin·bin→full with a wide RHS
# (the fully-binarized BitGNN layer, DESIGN.md §15)
# ---------------------------------------------------------------------------

def _spmm_bbf_block(col_idx: jax.Array, tiles: jax.Array, xw: jax.Array,
                    out_dtype) -> jax.Array:
    """bin·bin→full on one ELL slab against a BitMatrix word array.

    ``xw`` is ``uint32[n_tile_cols, d]`` (:class:`BitMatrix` words: node
    axis tile-packed, one word column per feature). Per output element:
    ``y[i*t+r, j] = Σ_k popcount(tile_word_r(i, k) & xw[col(i, k), j])``
    — the feature-wide generalisation of ``_bmv_bbf_block``, scanned over
    K for bounded memory. Returns counts ``[R, t, d]``.
    """
    n_tc = xw.shape[0]
    K = col_idx.shape[1]

    def step(acc, k):
        cols = col_idx[:, k]                                # [R]
        words = tiles[:, k]                                 # [R, t]
        xk = xw[jnp.clip(cols, 0, n_tc - 1)]                # [R, d]
        xk = jnp.where((cols >= 0)[:, None], xk, jnp.uint32(0))
        counts = _popcount(words[:, :, None] & xk[:, None, :])  # [R, t, d]
        return acc + counts.astype(out_dtype), None

    acc0 = jnp.zeros((col_idx.shape[0], tiles.shape[2], xw.shape[1]),
                     out_dtype)
    acc, _ = jax.lax.scan(step, acc0, jnp.arange(K))
    return acc


def spmm_bin_bin_full(ell: B2SREll, xw: jax.Array, out_dtype=jnp.float32,
                      row_chunk: Optional[int] = None) -> jax.Array:
    """BitGNN aggregation (Table II bin·bin→full, widened RHS).

    ``xw``: packed binarized activations ``uint32[n_tile_cols, d]``
    (:class:`~repro.core.operands.BitMatrix` words); returns the dense
    popcount-accumulated counts ``[n_rows, d]`` — the (+, AND) semiring of
    the XNOR formulation. α-scale/sign reconstruction is the caller's
    (``repro.gnn_bit``) affine epilogue, never the kernel's.
    """
    def chunk(col_idx, tiles):
        return _spmm_bbf_block(col_idx, tiles, xw, out_dtype)

    out = _mapped_over_rows(chunk, (ell.tile_col_idx, ell.bit_tiles),
                            ell.n_tile_rows, row_chunk)
    return out.reshape(-1, xw.shape[1])[: ell.n_rows]


def spmm_bin_bin_full_bucketed(b: B2SRBucketedEll, xw: jax.Array,
                               out_dtype=jnp.float32) -> jax.Array:
    """Bucketed BitGNN aggregation: empty tile-rows keep the 0 count."""
    out = jnp.zeros((b.n_tile_rows, b.tile_dim, xw.shape[1]), out_dtype)
    for col, tiles, rows in zip(b.col_idx, b.bit_tiles, b.rows):
        out = out.at[rows].set(_spmm_bbf_block(col, tiles, xw, out_dtype))
    return out.reshape(-1, xw.shape[1])[: b.n_rows]


# ---------------------------------------------------------------------------
# SpMM over packed frontier *matrices*: bin·bin→bin with a wide RHS
# (the engine/ multi-source traversal workhorse, DESIGN.md §9)
# ---------------------------------------------------------------------------

def _spmm_bbb_block(col_idx: jax.Array, tiles: jax.Array, f3: jax.Array,
                    t: int) -> jax.Array:
    """bin·bin→bin on one ELL slab against a packed frontier matrix.

    ``f3`` is ``uint32[n_tile_cols, t, W]`` (``pack_frontier_matrix``):
    source-axis words, node-axis tile grouping. Output word
    ``[i, r, w] = OR_c (A_tile[r, c] ? f3[col, c, w] : 0)`` — the mxm
    AND/shift word algorithm with a dense bit RHS: A's tiles stream once
    for *all* S sources instead of once per source (vs S bmv calls).
    Returns ``uint32[R, t, W]``.
    """
    n_tc = f3.shape[0]
    K = col_idx.shape[1]

    def step(acc, k):
        cols = col_idx[:, k]                                   # [R]
        a_bits = unpack_tiles(tiles[:, k], t, jnp.uint32)      # [R, t(r), t(c)]
        fk = f3[jnp.clip(cols, 0, n_tc - 1)]                   # [R, t(c), W]
        fk = jnp.where((cols >= 0)[:, None, None], fk, jnp.uint32(0))
        contrib = jnp.where((a_bits != 0)[..., None],
                            fk[:, None, :, :], jnp.uint32(0))  # [R, t, t, W]
        return acc | or_reduce_words(contrib, (2,)), None

    acc0 = jnp.zeros((col_idx.shape[0], t, f3.shape[2]), jnp.uint32)
    acc, _ = jax.lax.scan(step, acc0, jnp.arange(K))
    return acc


def spmm_bin_bin_bin(ell: B2SREll, f_packed: jax.Array,
                     row_chunk: Optional[int] = None) -> jax.Array:
    """Multi-frontier boolean traversal (Table II bin·bin→bin, widened RHS).

    ``f_packed``: packed frontier matrix ``uint32[n_tile_cols, t, W]``;
    returns the packed next-frontier matrix ``uint32[n_tile_rows, t, W]``.
    Column ``s`` equals ``bmv_bin_bin_bin`` on frontier ``s`` bit-for-bit —
    the batch amortises the A-tile traffic over all S sources.
    """
    def chunk(col_idx, tiles):
        return _spmm_bbb_block(col_idx, tiles, f_packed, ell.tile_dim)
    return _mapped_over_rows(chunk, (ell.tile_col_idx, ell.bit_tiles),
                             ell.n_tile_rows, row_chunk)


def apply_frontier_mask(y: jax.Array, mask_packed: jax.Array,
                        complement: bool) -> jax.Array:
    """AND a packed per-source visited mask into a frontier matrix (§V).

    Shared by every multi-frontier path (jnp, Pallas-bucketed, csr) so the
    mask semantics live in one place — the frontier-matrix twin of
    ``apply_grid_mask``.
    """
    return y & (~mask_packed if complement else mask_packed)


def spmm_bin_bin_bin_masked(ell: B2SREll, f_packed: jax.Array,
                            mask_packed: jax.Array, complement: bool = True,
                            row_chunk: Optional[int] = None) -> jax.Array:
    """Masked multi-frontier traversal (§V mask-at-store): the msBFS kernel.

    ``mask_packed`` has the output layout ``uint32[n_tile_rows, t, W]`` —
    per-source visited sets; ``complement=True`` keeps unvisited bits.
    """
    y = spmm_bin_bin_bin(ell, f_packed, row_chunk)
    return apply_frontier_mask(y, mask_packed, complement)


def spmm_bin_bin_bin_bucketed(b: B2SRBucketedEll,
                              f_packed: jax.Array) -> jax.Array:
    """Bucketed multi-frontier traversal: per-bucket slabs, scatter-merged.

    Empty tile-rows are in no bucket and keep the zero word (OR-identity).
    """
    out = jnp.zeros((b.n_tile_rows, b.tile_dim, f_packed.shape[2]),
                    jnp.uint32)
    for col, tiles, rows in zip(b.col_idx, b.bit_tiles, b.rows):
        out = out.at[rows].set(_spmm_bbb_block(col, tiles, f_packed,
                                               b.tile_dim))
    return out


def spmm_bin_bin_bin_bucketed_masked(b: B2SRBucketedEll, f_packed: jax.Array,
                                     mask_packed: jax.Array,
                                     complement: bool = True) -> jax.Array:
    """Masked bucketed multi-frontier traversal (mask ANDed post-merge, §V)."""
    y = spmm_bin_bin_bin_bucketed(b, f_packed)
    return apply_frontier_mask(y, mask_packed, complement)


def spmm_bin_bin_bin_pull(ell: B2SREll, f_packed: jax.Array,
                          mask_packed: jax.Array, complement: bool = True,
                          row_chunk: Optional[int] = None) -> jax.Array:
    """Pull-direction multi-frontier traversal, jnp twin.

    Same ``_spmm_bbb_block`` math as masked push — see
    :func:`bmv_bin_bin_bin_pull` for why the jnp pull twins share the
    push block (bit-exactness by construction; the early exit lives in
    the Pallas kernel only)."""
    return spmm_bin_bin_bin_masked(ell, f_packed, mask_packed, complement,
                                   row_chunk)


def spmm_bin_bin_bin_pull_bucketed(b: B2SRBucketedEll, f_packed: jax.Array,
                                   mask_packed: jax.Array,
                                   complement: bool = True) -> jax.Array:
    """Bucketed jnp pull twin of the multi-frontier traversal."""
    return spmm_bin_bin_bin_bucketed_masked(b, f_packed, mask_packed,
                                            complement)


# ---------------------------------------------------------------------------
# BMM: bin × bin -> masked scalar sum (the TC kernel, paper Listing 2)
# ---------------------------------------------------------------------------

def bmm_bin_bin_sum_masked(a: B2SREll, b: B2SREll, mask: B2SREll,
                           row_chunk: Optional[int] = None) -> jax.Array:
    """Fused masked BMM (Table III + §V, paper Listing 2): Σ mask ⊙ (A·B).

    For TC: A = L, B = Lᵀ (both in B2SR), mask = L; returns exactly
    Σ_{(r,c): L_rc=1} (L·Lᵀ)_rc, the paper's fused reduction — the scalar
    twin of ``mxm_bin_bin_full_masked`` (sum instead of materialise).

    Per output tile-row i: for each A tile (i, ka) with col a_c, walk B's
    tile-row a_c; each B tile (a_c, j) contributes to C tile (i, j); the mask
    tile (i, j) is found by matching j against mask's row-i col list.
    """
    t = a.tile_dim

    def chunk(a_col, a_tiles, m_col, m_tiles):
        # a_col [R, Ka]; a_tiles [R, Ka, t]; m_col [R, Km]; m_tiles [R, Km, t]
        Ka = a_col.shape[1]

        def step_ka(total, ka):
            ac = a_col[:, ka]                                    # [R]
            a_bits = unpack_tiles(a_tiles[:, ka], t, jnp.float32)  # [R, t, t]
            safe = jnp.clip(ac, 0, b.n_tile_rows - 1)
            b_cols = b.tile_col_idx[safe]                        # [R, Kb]
            b_tls = b.bit_tiles[safe]                            # [R, Kb, t]
            valid_a = (ac >= 0)[:, None]                         # [R, 1]

            def step_kb(tot, kb):
                bc = b_cols[:, kb]                               # [R]
                b_bits = unpack_tiles(b_tls[:, kb], t, jnp.float32)  # [R, t, t]
                # C tile (i, bc) partial product:
                prod = jnp.einsum("rab,rbc->rac", a_bits, b_bits)    # [R, t, t]
                # match bc against mask cols of row i -> mask bits (0 if absent)
                match = (m_col == bc[:, None]) & (m_col >= 0)        # [R, Km]
                m_words = jnp.sum(
                    jnp.where(match[:, :, None], m_tiles, jnp.uint32(0)),
                    axis=1, dtype=jnp.uint32)                        # [R, t]
                m_bits = unpack_tiles(m_words, t, jnp.float32)       # [R, t, t]
                ok = valid_a & (bc >= 0)[:, None]                    # [R, 1]
                contrib = jnp.sum(prod * m_bits, axis=(1, 2))
                return tot + jnp.sum(jnp.where(ok[:, 0], contrib, 0.0)), None

            tot, _ = jax.lax.scan(step_kb, total, jnp.arange(b_cols.shape[1]))
            return tot, None

        tot, _ = jax.lax.scan(step_ka, jnp.float32(0.0), jnp.arange(Ka))
        return tot

    c = _row_chunks(a.n_tile_rows, row_chunk)
    if c == a.n_tile_rows:
        return chunk(a.tile_col_idx, a.bit_tiles, mask.tile_col_idx, mask.bit_tiles)
    nb = a.n_tile_rows // c
    arrays = (a.tile_col_idx, a.bit_tiles, mask.tile_col_idx, mask.bit_tiles)
    reshaped = tuple(x.reshape((nb, c) + x.shape[1:]) for x in arrays)
    partials = jax.lax.map(lambda xs: chunk(*xs), reshaped)
    return jnp.sum(partials)


def bmm_bin_bin_sum(a: B2SREll, b: B2SREll,
                    row_chunk: Optional[int] = None) -> jax.Array:
    """Unmasked Σ (A·B) (Table III reduction): same walk, all-ones mask."""
    t = a.tile_dim

    def chunk(a_col, a_tiles):
        Ka = a_col.shape[1]

        def step_ka(total, ka):
            ac = a_col[:, ka]
            a_counts = _popcount(a_tiles[:, ka])                 # [R, t] row popcounts
            safe = jnp.clip(ac, 0, b.n_tile_rows - 1)
            b_tls = b.bit_tiles[safe]                            # [R, Kb, t]
            b_valid = (b.tile_col_idx[safe] >= 0)                # [R, Kb]
            # Σ_{r,c} (A·B)[r,c] = Σ_r Σ_m A[r,m] * (Σ_c B[m,c])
            b_row_pop = jnp.sum(
                jnp.where(b_valid[:, :, None], _popcount(b_tls), 0),
                axis=1)                                          # [R, t] per m
            a_bits = unpack_tiles(a_tiles[:, ka], t, jnp.float32)  # [R, t, t]
            contrib = jnp.einsum("ram,rm->r", a_bits,
                                 b_row_pop.astype(jnp.float32))
            ok = ac >= 0
            return total + jnp.sum(jnp.where(ok, contrib, 0.0)), None

        tot, _ = jax.lax.scan(step_ka, jnp.float32(0.0), jnp.arange(Ka))
        return tot

    c = _row_chunks(a.n_tile_rows, row_chunk)
    if c == a.n_tile_rows:
        return chunk(a.tile_col_idx, a.bit_tiles)
    nb = a.n_tile_rows // c
    arrays = (a.tile_col_idx, a.bit_tiles)
    reshaped = tuple(x.reshape((nb, c) + x.shape[1:]) for x in arrays)
    partials = jax.lax.map(lambda xs: chunk(*xs), reshaped)
    return jnp.sum(partials)


# ---------------------------------------------------------------------------
# MXM: bin × bin -> bin / full SpGEMM (paper Table III, the headline result)
# ---------------------------------------------------------------------------

def _check_mxm_dims(a: B2SREll, b: B2SREll):
    if a.tile_dim != b.tile_dim:
        raise ValueError(f"tile_dim mismatch: {a.tile_dim} vs {b.tile_dim}")
    if a.n_cols != b.n_rows:
        raise ValueError(f"inner-dim mismatch: A is {a.n_rows}x{a.n_cols}, "
                         f"B is {b.n_rows}x{b.n_cols}")


def mxm_bin_bin_bin(a: B2SREll, b: B2SREll, mask: Optional[B2SREll] = None,
                    complement: bool = False,
                    row_chunk: Optional[int] = None) -> jax.Array:
    """Boolean SpGEMM (Table III row bin·bin→bin): C = A ∨.∧ B, packed output.

    The tile-level AND/shift word algorithm: for output tile (i, j), each
    A tile (i, m) selects B's tile-row m; C's bit-row r ORs in B's word-row
    k for every set bit k of A's word-row r —
    ``c_word[r] = OR_k (A[r, k] ? b_word[k] : 0)`` — the word formulation of
    the paper's shared-memory AND/shift inner loop.

    Returns the packed output tile grid ``uint32[a.n_tile_rows,
    b.n_tile_cols, t]`` (static shape under jit); compress to B2SR with
    ``b2sr.packed_grid_to_b2sr``. With ``mask``, computes C⟨M⟩ (or C⟨¬M⟩
    when ``complement``): the mask is expanded to grid words and ANDed
    before the return — applied right before the store, paper §V.
    """
    _check_mxm_dims(a, b)

    def chunk(a_col, a_tiles):
        return _mxm_bbb_block(a_col, a_tiles, b, a.tile_dim)

    out = _mapped_over_rows(chunk, (a.tile_col_idx, a.bit_tiles),
                            a.n_tile_rows, row_chunk)
    return apply_grid_mask(out, mask, complement)


def apply_grid_mask(grid: jax.Array, mask: Optional[B2SREll],
                    complement: bool) -> jax.Array:
    """AND a structural mask into a packed output grid (§V, before store).

    Shared by the jnp and Pallas-bucketed mxm paths so the mask semantics
    live in exactly one place.
    """
    if mask is None:
        return grid
    mg = ell_to_packed_grid(mask)
    return grid & (~mg if complement else mg)


def _mxm_bbb_block(a_col: jax.Array, a_tiles: jax.Array, b: B2SREll,
                   t: int) -> jax.Array:
    """Boolean SpGEMM for one A-side ELL slab: packed grid [R, n_tc_b, t]."""
    n_tc_b = b.n_tile_cols
    rb = b.tile_col_idx.shape[0]
    R = a_col.shape[0]
    Ka = a_col.shape[1]

    def step(acc, k):
        ac = a_col[:, k]                                     # [R]
        safe = jnp.clip(ac, 0, rb - 1)
        b_cols = b.tile_col_idx[safe]                        # [R, Kb]
        b_tls = b.bit_tiles[safe]                            # [R, Kb, t]
        a_bits = unpack_tiles(a_tiles[:, k], t, jnp.uint32)  # [R, t(r), t(k)]
        # AND/shift: broadcast B's word k where A bit (r, k) is set
        contrib = jnp.where(a_bits[:, None, :, :] != 0,
                            b_tls[:, :, None, :], jnp.uint32(0))
        c_words = or_reduce_words(contrib, (3,))             # [R, Kb, t(r)]
        ok = (ac >= 0)[:, None] & (b_cols >= 0)              # [R, Kb]
        c_words = jnp.where(ok[:, :, None], c_words, jnp.uint32(0))
        cols = jnp.clip(b_cols, 0, n_tc_b - 1)
        # tile-row merge: distinct cols per legal ELL row -> max == OR
        step_grid = jnp.zeros((R, n_tc_b, t), jnp.uint32).at[
            jnp.arange(R)[:, None], cols].max(c_words)
        return acc | step_grid, None

    acc0 = jnp.zeros((R, n_tc_b, t), jnp.uint32)
    acc, _ = jax.lax.scan(step, acc0, jnp.arange(Ka))
    return acc


def mxm_bin_bin_bin_bucketed(a: B2SRBucketedEll, b: B2SREll,
                             mask: Optional[B2SREll] = None,
                             complement: bool = False) -> jax.Array:
    """Bucketed boolean SpGEMM: A's tile-rows per-bucket, B stays one ELL.

    Same packed-grid contract as ``mxm_bin_bin_bin``; empty A tile-rows
    produce all-zero grid rows. The mask is ANDed after the scatter-merge —
    still right before the caller's store (§V).
    """
    t = a.tile_dim
    if t != b.tile_dim:
        raise ValueError(f"tile_dim mismatch: {t} vs {b.tile_dim}")
    if a.n_cols != b.n_rows:
        raise ValueError(f"inner-dim mismatch: A is {a.n_rows}x{a.n_cols}, "
                         f"B is {b.n_rows}x{b.n_cols}")
    out = jnp.zeros((a.n_tile_rows, b.n_tile_cols, t), jnp.uint32)
    for col, tiles, rows in zip(a.col_idx, a.bit_tiles, a.rows):
        out = out.at[rows].set(_mxm_bbb_block(col, tiles, b, t))
    return apply_grid_mask(out, mask, complement)


def mxm_bin_bin_full(a: B2SREll, b: B2SREll, out_dtype=jnp.int32,
                     row_chunk: Optional[int] = None) -> jax.Array:
    """Count SpGEMM (Table III row bin·bin→full): C = A +.× B, dense output.

    C[i, j] = |N(i) ∩ N⁻(j)| — the common-neighbour count matrix that
    triangle counting and k-truss consume. Output tiles are accumulated
    densely (scatter-add over tile columns) and returned as the dense
    ``[n_rows, n_cols]`` count matrix.
    """
    _check_mxm_dims(a, b)
    t = a.tile_dim

    def chunk(a_col, a_tiles):
        return _mxm_bbf_block(a_col, a_tiles, b, t)

    grid = _mapped_over_rows(chunk, (a.tile_col_idx, a.bit_tiles),
                             a.n_tile_rows, row_chunk)
    dense = grid.transpose(0, 2, 1, 3).reshape(
        a.n_tile_rows * t, b.n_tile_cols * t)
    return dense[: a.n_rows, : b.n_cols].astype(out_dtype)


def _mxm_bbf_block(a_col: jax.Array, a_tiles: jax.Array, b: B2SREll,
                   t: int) -> jax.Array:
    """Count SpGEMM for one A-side ELL slab: count tiles [R, n_tc_b, t, t]."""
    n_tc_b = b.n_tile_cols
    rb = b.tile_col_idx.shape[0]
    R = a_col.shape[0]
    Ka = a_col.shape[1]

    def step(acc, k):
        ac = a_col[:, k]
        safe = jnp.clip(ac, 0, rb - 1)
        b_cols = b.tile_col_idx[safe]                        # [R, Kb]
        b_tls = b.bit_tiles[safe]                            # [R, Kb, t]
        a_bits = unpack_tiles(a_tiles[:, k], t, jnp.int32)   # [R, t(r), t(m)]
        b_bits = unpack_tiles(b_tls, t, jnp.int32)           # [R, Kb, t(m), t(c)]
        prod = jnp.einsum("ram,rnmc->rnac", a_bits, b_bits,
                          preferred_element_type=jnp.int32)  # [R, Kb, t, t]
        ok = (ac >= 0)[:, None] & (b_cols >= 0)
        prod = jnp.where(ok[:, :, None, None], prod, 0)
        cols = jnp.clip(b_cols, 0, n_tc_b - 1)
        return acc.at[jnp.arange(R)[:, None], cols].add(prod), None

    acc0 = jnp.zeros((R, n_tc_b, t, t), jnp.int32)
    acc, _ = jax.lax.scan(step, acc0, jnp.arange(Ka))
    return acc


def mxm_bin_bin_full_bucketed(a: B2SRBucketedEll, b: B2SREll,
                              out_dtype=jnp.int32) -> jax.Array:
    """Bucketed count SpGEMM: dense [n_rows, n_cols] counts, per-bucket k_b."""
    t = a.tile_dim
    if t != b.tile_dim:
        raise ValueError(f"tile_dim mismatch: {t} vs {b.tile_dim}")
    if a.n_cols != b.n_rows:
        raise ValueError(f"inner-dim mismatch: A is {a.n_rows}x{a.n_cols}, "
                         f"B is {b.n_rows}x{b.n_cols}")
    n_tc_b = b.n_tile_cols
    grid = jnp.zeros((a.n_tile_rows, n_tc_b, t, t), jnp.int32)
    for col, tiles, rows in zip(a.col_idx, a.bit_tiles, a.rows):
        grid = grid.at[rows].set(_mxm_bbf_block(col, tiles, b, t))
    dense = grid.transpose(0, 2, 1, 3).reshape(
        a.n_tile_rows * t, n_tc_b * t)
    return dense[: a.n_rows, : b.n_cols].astype(out_dtype)


def mxm_bin_bin_full_masked(a: B2SREll, b: B2SREll, mask: B2SREll,
                            complement: bool = False, out_dtype=jnp.int32,
                            row_chunk: Optional[int] = None) -> jax.Array:
    """Masked count SpGEMM: C⟨M⟩ = A +.× B with a *structural* B2SR mask.

    The fused form ``sum(mxm_bin_bin_full_masked(L, Lᵀ, L))`` is the paper's
    triangle-count reduction (§V, Listing 2); ``bmm_bin_bin_sum_masked``
    is its fully-fused scalar twin.
    """
    counts = mxm_bin_bin_full(a, b, out_dtype, row_chunk)
    return _apply_dense_mask(counts, mask, complement, out_dtype)


def _apply_dense_mask(counts: jax.Array, mask: B2SREll, complement: bool,
                      out_dtype) -> jax.Array:
    t = mask.tile_dim
    mg = ell_to_packed_grid(mask)                               # [R, C, t]
    m_bits = unpack_tiles(mg, t, out_dtype)                     # [R, C, t, t]
    m_dense = m_bits.transpose(0, 2, 1, 3).reshape(
        mg.shape[0] * t, mg.shape[1] * t)[: mask.n_rows, : mask.n_cols]
    keep = (m_dense == 0) if complement else (m_dense != 0)
    return jnp.where(keep, counts, 0)


def mxm_bin_bin_full_masked_bucketed(a: B2SRBucketedEll, b: B2SREll,
                                     mask: B2SREll, complement: bool = False,
                                     out_dtype=jnp.int32) -> jax.Array:
    """Bucketed masked count SpGEMM (tri_count's workhorse on skewed graphs)."""
    counts = mxm_bin_bin_full_bucketed(a, b, out_dtype)
    return _apply_dense_mask(counts, mask, complement, out_dtype)


# ---------------------------------------------------------------------------
# Dispatch-registry entries for the "b2sr" backend (DESIGN.md §10).
#
# Each adapter binds one (op, rhs, out, bucketed, masked) Table II/III row to
# the scheme above. Adapters receive the GraphMatrix (duck-typed: only
# ``.ell`` / ``.buckets()`` are touched — no graphblas import, no cycle), the
# raw right-hand operand, and the normalized :class:`~repro.core.dispatch
# .OpCall`.
# ---------------------------------------------------------------------------

from repro.core.dispatch import apply_output_mask, register  # noqa: E402

# -- mxv: Table II ----------------------------------------------------------

@register("mxv", "dense", "full", "b2sr", bucketed=False, masked=False)
def _mxv_dense(g, x, call):
    return bmv_bin_full_full(g.ell, x, call.semiring, call.a_value,
                             call.row_chunk)


@register("mxv", "dense", "full", "b2sr", bucketed=False, masked=True)
def _mxv_dense_masked(g, x, call):
    return bmv_bin_full_full_masked(g.ell, x, call.mask, call.semiring,
                                    call.a_value, call.complement,
                                    call.row_chunk)


@register("mxv", "dense", "full", "b2sr", bucketed=True, masked=False)
def _mxv_dense_bucketed(g, x, call):
    return bmv_bin_full_full_bucketed(g.buckets(), x, call.semiring,
                                      call.a_value)


@register("mxv", "dense", "full", "b2sr", bucketed=True, masked=True)
def _mxv_dense_bucketed_masked(g, x, call):
    y = bmv_bin_full_full_bucketed(g.buckets(), x, call.semiring,
                                   call.a_value)
    return apply_output_mask(y, call.mask, call.complement,
                             call.semiring.identity_for(y.dtype))


@register("mxv", "bitvec", "bin", "b2sr", bucketed=False, masked=False)
def _mxv_bitvec(g, xw, call):
    return bmv_bin_bin_bin(g.ell, xw, call.row_chunk)


@register("mxv", "bitvec", "bin", "b2sr", bucketed=False, masked=True)
def _mxv_bitvec_masked(g, xw, call):
    return bmv_bin_bin_bin_masked(g.ell, xw, call.mask, call.complement,
                                  call.row_chunk)


@register("mxv", "bitvec", "bin", "b2sr", bucketed=True, masked=False)
def _mxv_bitvec_bucketed(g, xw, call):
    return bmv_bin_bin_bin_bucketed(g.buckets(), xw)


@register("mxv", "bitvec", "bin", "b2sr", bucketed=True, masked=True)
def _mxv_bitvec_bucketed_masked(g, xw, call):
    return bmv_bin_bin_bin_bucketed_masked(g.buckets(), xw, call.mask,
                                           call.complement)


@register("mxv_pull", "bitvec", "bin", "b2sr", bucketed=False, masked=True)
def _mxv_pull(g, xw, call):
    return bmv_bin_bin_bin_pull(g.ell, xw, call.mask, call.complement,
                                call.row_chunk)


@register("mxv_pull", "bitvec", "bin", "b2sr", bucketed=True, masked=True)
def _mxv_pull_bucketed(g, xw, call):
    return bmv_bin_bin_bin_pull_bucketed(g.buckets(), xw, call.mask,
                                         call.complement)


@register("mxv", "bitvec", "full", "b2sr", bucketed=False, masked=False)
def _mxv_count(g, xw, call):
    return bmv_bin_bin_full(g.ell, xw, call.out_dtype, call.row_chunk)


@register("mxv", "bitvec", "full", "b2sr", bucketed=False, masked=True)
def _mxv_count_masked(g, xw, call):
    return bmv_bin_bin_full_masked(g.ell, xw, call.mask, call.complement,
                                   call.out_dtype, call.row_chunk)


@register("mxv", "bitvec", "full", "b2sr", bucketed=True, masked=False)
def _mxv_count_bucketed(g, xw, call):
    return bmv_bin_bin_full_bucketed(g.buckets(), xw, call.out_dtype)


@register("mxv", "bitvec", "full", "b2sr", bucketed=True, masked=True)
def _mxv_count_bucketed_masked(g, xw, call):
    y = bmv_bin_bin_full_bucketed(g.buckets(), xw, call.out_dtype)
    return apply_output_mask(y, call.mask, call.complement,
                             jnp.zeros((), call.out_dtype))


# -- mxm: Table III + widened-RHS rows --------------------------------------

@register("mxm", "dense", "full", "b2sr", bucketed=False, masked=False)
def _mxm_dense(g, x, call):
    return spmm_b2sr(g.ell, x, row_chunk=call.row_chunk)


@register("mxm", "dense", "full", "b2sr", bucketed=False, masked=True)
def _mxm_dense_masked(g, x, call):
    y = spmm_b2sr(g.ell, x, row_chunk=call.row_chunk)
    return apply_output_mask(y, call.mask, call.complement,
                             call.semiring.identity_for(y.dtype))


@register("mxm", "dense", "full", "b2sr", bucketed=True, masked=False)
def _mxm_dense_bucketed(g, x, call):
    return spmm_b2sr_bucketed(g.buckets(), x)


@register("mxm", "dense", "full", "b2sr", bucketed=True, masked=True)
def _mxm_dense_bucketed_masked(g, x, call):
    y = spmm_b2sr_bucketed(g.buckets(), x)
    return apply_output_mask(y, call.mask, call.complement,
                             call.semiring.identity_for(y.dtype))


@register("mxm", "frontier", "bin", "b2sr", bucketed=False, masked=False)
def _mxm_frontier(g, fw, call):
    return spmm_bin_bin_bin(g.ell, fw, call.row_chunk)


@register("mxm", "frontier", "bin", "b2sr", bucketed=False, masked=True)
def _mxm_frontier_masked(g, fw, call):
    return spmm_bin_bin_bin_masked(g.ell, fw, call.mask, call.complement,
                                   call.row_chunk)


@register("mxm", "frontier", "bin", "b2sr", bucketed=True, masked=False)
def _mxm_frontier_bucketed(g, fw, call):
    return spmm_bin_bin_bin_bucketed(g.buckets(), fw)


@register("mxm", "frontier", "bin", "b2sr", bucketed=True, masked=True)
def _mxm_frontier_bucketed_masked(g, fw, call):
    return spmm_bin_bin_bin_bucketed_masked(g.buckets(), fw, call.mask,
                                            call.complement)


def _bitmat_dtype(call):
    return call.out_dtype if call.out_dtype is not None else jnp.float32


@register("mxm", "bitmat", "full", "b2sr", bucketed=False, masked=False)
def _mxm_bitmat(g, xw, call):
    return spmm_bin_bin_full(g.ell, xw, _bitmat_dtype(call), call.row_chunk)


@register("mxm", "bitmat", "full", "b2sr", bucketed=False, masked=True)
def _mxm_bitmat_masked(g, xw, call):
    y = spmm_bin_bin_full(g.ell, xw, _bitmat_dtype(call), call.row_chunk)
    return apply_output_mask(y, call.mask, call.complement,
                             call.semiring.identity_for(y.dtype))


@register("mxm", "bitmat", "full", "b2sr", bucketed=True, masked=False)
def _mxm_bitmat_bucketed(g, xw, call):
    return spmm_bin_bin_full_bucketed(g.buckets(), xw, _bitmat_dtype(call))


@register("mxm", "bitmat", "full", "b2sr", bucketed=True, masked=True)
def _mxm_bitmat_bucketed_masked(g, xw, call):
    y = spmm_bin_bin_full_bucketed(g.buckets(), xw, _bitmat_dtype(call))
    return apply_output_mask(y, call.mask, call.complement,
                             call.semiring.identity_for(y.dtype))


@register("mxm_pull", "frontier", "bin", "b2sr", bucketed=False, masked=True)
def _mxm_pull(g, fw, call):
    return spmm_bin_bin_bin_pull(g.ell, fw, call.mask, call.complement,
                                 call.row_chunk)


@register("mxm_pull", "frontier", "bin", "b2sr", bucketed=True, masked=True)
def _mxm_pull_bucketed(g, fw, call):
    return spmm_bin_bin_bin_pull_bucketed(g.buckets(), fw, call.mask,
                                          call.complement)


@register("mxm", "graph", "bin", "b2sr", bucketed=False)
def _mxm_graph(g, other, call):
    m_ell = call.mask.ell if call.mask is not None else None
    return mxm_bin_bin_bin(g.ell, other.ell, m_ell, call.complement,
                           call.row_chunk)


@register("mxm", "graph", "bin", "b2sr", bucketed=True)
def _mxm_graph_bucketed(g, other, call):
    m_ell = call.mask.ell if call.mask is not None else None
    return mxm_bin_bin_bin_bucketed(g.buckets(), other.ell, m_ell,
                                    call.complement)


@register("mxm", "graph", "full", "b2sr", bucketed=False, masked=False)
def _mxm_graph_count(g, other, call):
    return mxm_bin_bin_full(g.ell, other.ell, row_chunk=call.row_chunk)


@register("mxm", "graph", "full", "b2sr", bucketed=False, masked=True)
def _mxm_graph_count_masked(g, other, call):
    return mxm_bin_bin_full_masked(g.ell, other.ell, call.mask.ell,
                                   call.complement, row_chunk=call.row_chunk)


@register("mxm", "graph", "full", "b2sr", bucketed=True, masked=False)
def _mxm_graph_count_bucketed(g, other, call):
    return mxm_bin_bin_full_bucketed(g.buckets(), other.ell)


@register("mxm", "graph", "full", "b2sr", bucketed=True, masked=True)
def _mxm_graph_count_bucketed_masked(g, other, call):
    return mxm_bin_bin_full_masked_bucketed(g.buckets(), other.ell,
                                            call.mask.ell, call.complement)


# -- mxm_sum: the fused Σ mask ⊙ (A·B) reduction (tri_count, Listing 2) -----

@register("mxm_sum", "tri", "full", "b2sr", bucketed=False, masked=True)
def _tri_sum(g, tri, call):
    counts = mxm_bin_bin_full_masked(tri.ell, tri.ell_t, tri.ell,
                                     row_chunk=call.row_chunk)
    return jnp.sum(counts).astype(jnp.float32)


@register("mxm_sum", "tri", "full", "b2sr", bucketed=True, masked=True)
def _tri_sum_bucketed(g, tri, call):
    counts = mxm_bin_bin_full_masked_bucketed(tri.buckets(), tri.ell_t,
                                              tri.ell)
    return jnp.sum(counts).astype(jnp.float32)


# spmm_b2sr_shardmap moved next to the other shard_map code; re-exported
# here so callers keep one import point for the B2SR SpMM family
from repro.core.ops_sharded import spmm_b2sr_shardmap  # noqa: E402,F401
