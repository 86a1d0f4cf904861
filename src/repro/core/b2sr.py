"""B2SR: Bit-Block Compressed Sparse Row format (the paper's core contribution).

Two-level representation of a *binary* sparse matrix:
  - upper level: CSR over fixed-size square tiles (tile_row_ptr / tile_col_idx)
  - lower level: each non-empty tile is a dense bit matrix; bit-row ``r`` of a
    tile is packed LSB-first into one machine word (bit ``j`` of word ``r`` is
    element ``[r, j]`` of the tile).

Tile sizes 4/8/16/32 are supported (B2SR-4 .. B2SR-32, Table I of the paper).
Storage accounting uses the paper's packing dtypes (uint8/uint8/uint16/uint32);
the *compute* representation is always uint32 words (TPU lanes are 32-bit).

TPU adaptation (see DESIGN.md §2): kernels consume a padded ELL view
(``B2SREll``) with a static ``max_tiles_per_row`` so Pallas BlockSpecs are
static; CSR top level remains the storage/interchange format.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

TILE_DIMS = (4, 8, 16, 32)

# Paper Table I packing dtypes (for storage accounting + host storage).
_STORE_DTYPE = {4: np.uint8, 8: np.uint8, 16: np.uint16, 32: np.uint32}
_STORE_BYTES = {4: 1, 8: 1, 16: 2, 32: 4}
_INDEX_BYTES = 4  # int32 indices, as in the paper


def _pytree(cls):
    """Register a dataclass as a pytree: array fields are leaves, the rest aux."""
    meta = tuple(f.name for f in dataclasses.fields(cls) if f.metadata.get("static"))
    data = tuple(f.name for f in dataclasses.fields(cls) if not f.metadata.get("static"))

    def flatten(obj):
        return tuple(getattr(obj, n) for n in data), tuple(getattr(obj, n) for n in meta)

    def unflatten(aux, children):
        return cls(**dict(zip(data, children)), **dict(zip(meta, aux)))

    jax.tree_util.register_pytree_node(cls, flatten, unflatten)
    return cls


def static_field(**kw):
    return dataclasses.field(metadata={"static": True}, **kw)


@_pytree
@dataclasses.dataclass(frozen=True)
class B2SR:
    """CSR-over-tiles with bit-packed tiles (compute words are uint32).

    The host-side storage and interchange format: its arrays are numpy,
    and the device only ever holds the padded views built from it.
    """

    tile_row_ptr: np.ndarray  # int32[n_tile_rows + 1]
    tile_col_idx: np.ndarray  # int32[n_tiles]
    bit_tiles: np.ndarray     # uint32[n_tiles, tile_dim]; low tile_dim bits used
    tile_dim: int = static_field()
    n_rows: int = static_field()
    n_cols: int = static_field()
    nnz: int = static_field()

    @property
    def n_tile_rows(self) -> int:
        return ceil_div(self.n_rows, self.tile_dim)

    @property
    def n_tile_cols(self) -> int:
        return ceil_div(self.n_cols, self.tile_dim)

    @property
    def n_tiles(self) -> int:
        return int(self.tile_col_idx.shape[0])

    def storage_bytes(self) -> int:
        """Byte size in the paper's on-disk packing (Table I dtypes)."""
        idx = _INDEX_BYTES * (self.n_tile_rows + 1) + _INDEX_BYTES * self.n_tiles
        tiles = self.n_tiles * self.tile_dim * _STORE_BYTES[self.tile_dim]
        return idx + tiles


@_pytree
@dataclasses.dataclass(frozen=True)
class B2SREll:
    """Padded (ELL-style) view of B2SR: static tiles-per-row for TPU kernels.

    ``tile_col_idx`` uses ``-1`` as the padding sentinel; gathers clip to 0 and
    a validity mask kills the padded lanes.
    """

    tile_col_idx: jax.Array  # int32[n_tile_rows, max_tiles_per_row]
    bit_tiles: jax.Array     # uint32[n_tile_rows, max_tiles_per_row, tile_dim]
    row_n_tiles: jax.Array   # int32[n_tile_rows]
    tile_dim: int = static_field()
    n_rows: int = static_field()
    n_cols: int = static_field()

    @property
    def n_tile_rows(self) -> int:
        return int(self.tile_col_idx.shape[0])

    @property
    def n_tile_cols(self) -> int:
        return ceil_div(self.n_cols, self.tile_dim)

    @property
    def max_tiles_per_row(self) -> int:
        return int(self.tile_col_idx.shape[1])

    def valid_mask(self) -> jax.Array:
        return self.tile_col_idx >= 0


@_pytree
@dataclasses.dataclass(frozen=True)
class B2SRBucketedEll:
    """Row-bucketed (SELL-style) ELL view: per-bucket static tiles-per-row.

    The single-``max_tiles_per_row`` ``B2SREll`` makes every tile-row pay
    hub-row cost on skewed (power-law) graphs. Here tile-rows are sorted by
    tile count into length-buckets (power-of-two boundaries, slab width =
    the bucket's own max count); each bucket is a dense ``[rows_b, k_b]``
    ELL slab plus ``rows`` — the original tile-row ids, i.e. the
    row-permutation that restores output order. Empty tile-rows belong to
    no bucket (consumers initialise outputs to the ⊕-identity). See
    DESIGN.md §2 for the bucketing decision.

    Per-bucket arrays (parallel tuples, one entry per bucket):
      col_idx[b]   int32[rows_b, k_b]   (-1 = padding sentinel, as in ELL)
      bit_tiles[b] uint32[rows_b, k_b, tile_dim]
      rows[b]      int32[rows_b]        original tile-row index per slab row
    """

    col_idx: Tuple[jax.Array, ...]
    bit_tiles: Tuple[jax.Array, ...]
    rows: Tuple[jax.Array, ...]
    tile_dim: int = static_field()
    n_rows: int = static_field()
    n_cols: int = static_field()
    n_tile_rows: int = static_field()

    @property
    def n_buckets(self) -> int:
        return len(self.col_idx)

    @property
    def n_tile_cols(self) -> int:
        return ceil_div(self.n_cols, self.tile_dim)

    @property
    def bucket_widths(self) -> Tuple[int, ...]:
        return tuple(int(c.shape[1]) for c in self.col_idx)

    @property
    def bucket_sizes(self) -> Tuple[int, ...]:
        return tuple(int(c.shape[0]) for c in self.col_idx)

    def padded_words(self) -> int:
        """Tile slots held (incl. padding) across all bucket slabs."""
        return sum(int(c.shape[0] * c.shape[1]) for c in self.col_idx)

    def real_words(self) -> int:
        """Non-padding tile slots (equals the B2SR tile count)."""
        return sum(int((np.asarray(c) >= 0).sum()) for c in self.col_idx)

    def fill_ratio(self) -> float:
        """real/padded tile slots; 1.0 == no padded work at all."""
        p = self.padded_words()
        return 1.0 if p == 0 else self.real_words() / p


def ell_fill_ratio(ell: "B2SREll") -> float:
    """real/padded tile slots of the single-max ELL view (for comparison)."""
    padded = int(ell.tile_col_idx.shape[0] * ell.tile_col_idx.shape[1])
    if padded == 0:
        return 1.0
    return int((np.asarray(ell.tile_col_idx) >= 0).sum()) / padded


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


# ---------------------------------------------------------------------------
# Host-side conversion (the cusparseXcsr2bsrNnz / csr2bsr analogue)
# ---------------------------------------------------------------------------

def coo_to_b2sr(
    rows: np.ndarray,
    cols: np.ndarray,
    n_rows: int,
    n_cols: int,
    tile_dim: int = 32,
) -> B2SR:
    """Convert a binary COO matrix to B2SR. Duplicate entries are OR-ed."""
    if tile_dim not in TILE_DIMS:
        raise ValueError(f"tile_dim must be one of {TILE_DIMS}, got {tile_dim}")
    rows = np.asarray(rows, dtype=np.int64)
    cols = np.asarray(cols, dtype=np.int64)
    if rows.size and (rows.min() < 0 or rows.max() >= n_rows):
        raise ValueError("row index out of range")
    if cols.size and (cols.min() < 0 or cols.max() >= n_cols):
        raise ValueError("col index out of range")
    t = tile_dim
    n_tile_rows = ceil_div(n_rows, t)
    n_tile_cols = ceil_div(n_cols, t)

    tr = rows // t
    tc = cols // t
    key = tr * n_tile_cols + tc
    order = np.argsort(key, kind="stable")
    key_sorted = key[order]
    uniq_keys, inverse_sorted = np.unique(key_sorted, return_inverse=True)
    n_tiles = int(uniq_keys.shape[0])

    # inverse map for original nnz order
    inverse = np.empty_like(inverse_sorted)
    inverse[order] = inverse_sorted

    tile_tr = (uniq_keys // n_tile_cols).astype(np.int64)
    tile_tc = (uniq_keys % n_tile_cols).astype(np.int64)

    tile_row_ptr = _row_ptr(tile_tr, n_tile_rows)

    bit_tiles = np.zeros((n_tiles, t), dtype=np.uint32)
    word_idx = (rows % t).astype(np.int64)
    bit = (np.uint32(1) << (cols % t).astype(np.uint32)).astype(np.uint32)
    np.bitwise_or.at(bit_tiles, (inverse, word_idx), bit)

    return B2SR(
        tile_row_ptr=tile_row_ptr,
        tile_col_idx=tile_tc.astype(np.int32),
        bit_tiles=bit_tiles,
        tile_dim=t,
        n_rows=n_rows,
        n_cols=n_cols,
        nnz=int(rows.shape[0]),
    )


def _row_ptr(row_of: np.ndarray, n_rows: int) -> np.ndarray:
    """CSR row pointer (int32) from each entry's (sorted) row index."""
    counts = np.bincount(row_of, minlength=n_rows)
    return np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)


def csr_to_b2sr(row_ptr: np.ndarray, col_idx: np.ndarray, n_cols: int,
                tile_dim: int = 32) -> B2SR:
    row_ptr = np.asarray(row_ptr)
    n_rows = row_ptr.shape[0] - 1
    rows = np.repeat(np.arange(n_rows, dtype=np.int64), np.diff(row_ptr))
    return coo_to_b2sr(rows, np.asarray(col_idx), n_rows, n_cols, tile_dim)


def dense_to_b2sr(mat: np.ndarray, tile_dim: int = 32) -> B2SR:
    mat = np.asarray(mat)
    rows, cols = np.nonzero(mat)
    return coo_to_b2sr(rows, cols, mat.shape[0], mat.shape[1], tile_dim)


def b2sr_to_dense(m: B2SR) -> np.ndarray:
    """Densify (oracle / tests only)."""
    t = m.tile_dim
    out = np.zeros((m.n_tile_rows * t, m.n_tile_cols * t), dtype=np.uint8)
    ptr = np.asarray(m.tile_row_ptr)
    tci = np.asarray(m.tile_col_idx)
    tiles = np.asarray(m.bit_tiles)
    for i in range(m.n_tile_rows):
        for p in range(int(ptr[i]), int(ptr[i + 1])):
            j = int(tci[p])
            block = (tiles[p][:, None] >> np.arange(t, dtype=np.uint32)[None, :]) & 1
            out[i * t:(i + 1) * t, j * t:(j + 1) * t] |= block.astype(np.uint8)
    return out[: m.n_rows, : m.n_cols]


def _csr_arrays(m) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host (tiles-per-row, tile cols, tiles) of a B2SR or its ELL view,
    tiles in row order — the one input every padded view is built from."""
    if isinstance(m, B2SREll):
        col = np.asarray(m.tile_col_idx)
        valid = col >= 0
        return (valid.sum(axis=1).astype(np.int64), col[valid],
                np.asarray(m.bit_tiles)[valid])
    return (np.diff(np.asarray(m.tile_row_ptr)).astype(np.int64),
            np.asarray(m.tile_col_idx), np.asarray(m.bit_tiles))


def _slab(counts: np.ndarray, tci: np.ndarray, bt: np.ndarray,
          rows: np.ndarray, k: int, t: int):
    """Left-justified ``[len(rows), k]`` ELL slab of the given tile-rows."""
    n = counts[rows]
    pos = np.arange(int(n.sum())) - np.repeat(np.cumsum(n) - n, n)
    src = np.repeat((np.cumsum(counts) - counts)[rows], n) + pos
    local = np.repeat(np.arange(rows.size), n)
    col = np.full((rows.size, k), -1, dtype=np.int32)
    tiles = np.zeros((rows.size, k, t), dtype=np.uint32)
    col[local, pos] = tci[src]
    tiles[local, pos] = bt[src]
    return col, tiles


def ell_arrays(m, max_tiles_per_row: Optional[int] = None,
               pad_tile_rows_to: int = 1):
    """Host ``(col, tiles, counts)`` of the padded ELL view of ``m``."""
    counts, tci, bt = _csr_arrays(m)
    k = int(counts.max()) if counts.size else 1
    if max_tiles_per_row is not None:
        if max_tiles_per_row < k:
            raise ValueError(f"max_tiles_per_row={max_tiles_per_row} < required {k}")
        k = max_tiles_per_row
    k = max(k, 1)
    n_tr = m.n_tile_rows
    counts = np.pad(counts, (0, ceil_div(n_tr, pad_tile_rows_to)
                             * pad_tile_rows_to - n_tr))
    col, tiles = _slab(counts, tci, bt, np.arange(counts.size), k,
                       m.tile_dim)
    return col, tiles, counts.astype(np.int32)


def to_ell(m: B2SR, max_tiles_per_row: Optional[int] = None,
           pad_tile_rows_to: int = 1) -> B2SREll:
    """CSR-over-tiles -> padded ELL view (static shapes for TPU kernels).

    Every tile-row is padded to the widest one, so on a skewed graph this
    view can be many times the tile bytes; the default compute path uses
    :func:`to_bucketed` instead and builds this only on request.
    """
    col, tiles, counts = ell_arrays(m, max_tiles_per_row, pad_tile_rows_to)
    # ensure_compile_time_eval: like the bucketed view, this one is built
    # lazily and memoized — possibly first inside a jit trace
    with jax.ensure_compile_time_eval():
        return B2SREll(
            tile_col_idx=jnp.asarray(col),
            bit_tiles=jnp.asarray(tiles),
            row_n_tiles=jnp.asarray(counts),
            tile_dim=m.tile_dim,
            n_rows=m.n_rows,
            n_cols=m.n_cols,
        )


def to_bucketed(m, max_buckets: int = 8) -> B2SRBucketedEll:
    """Tile CSR (or an ELL view of it) -> row-bucketed (SELL-style) view.

    Tile-rows are grouped by tile count into power-of-two ranges
    ``(2^(b-1), 2^b]``; each group's slab width is its own max count (so
    per-row padding is < 2x even inside a bucket). If the count histogram
    spans more than ``max_buckets`` ranges, the *narrowest* ranges are
    merged into one slab: their rows are short, so widening them costs
    little, while a merged hub slab would pad thousands of rows to the
    widest row. Empty tile-rows are dropped. The slabs are built straight
    from the tile CSR, never through the single-max ELL.
    """
    counts, tci, bt = _csr_arrays(m)
    n_tr = m.n_tile_rows
    nonempty = np.flatnonzero(counts > 0)
    cols_out, tiles_out, rows_out = [], [], []
    if nonempty.size:
        # power-of-two bucket index per row: 1 -> 0, 2 -> 1, 3..4 -> 2, ...
        bidx = np.ceil(np.log2(counts[nonempty])).astype(np.int64)
        uniq = np.unique(bidx)
        if uniq.size > max_buckets:
            cap = uniq[uniq.size - max_buckets]
            bidx = np.maximum(bidx, cap)
            uniq = np.unique(bidx)
        # ensure_compile_time_eval: the bucketed view is built lazily and
        # memoized on the GraphMatrix — when the first use happens inside a
        # jit trace, plain jnp.asarray would mint tracers and poison the
        # cache for every later (outside-trace) call
        with jax.ensure_compile_time_eval():
            for b in uniq:
                rows_b = nonempty[bidx == b]
                k_b = int(counts[rows_b].max())
                col, tiles = _slab(counts, tci, bt, rows_b, k_b, m.tile_dim)
                cols_out.append(jnp.asarray(col))
                tiles_out.append(jnp.asarray(tiles))
                rows_out.append(jnp.asarray(rows_b.astype(np.int32)))
    return B2SRBucketedEll(
        col_idx=tuple(cols_out),
        bit_tiles=tuple(tiles_out),
        rows=tuple(rows_out),
        tile_dim=m.tile_dim,
        n_rows=m.n_rows,
        n_cols=m.n_cols,
        n_tile_rows=n_tr,
    )


def transpose(m: B2SR) -> B2SR:
    """B2SR transpose: swap tile coords (CSR->CSC relabel) + bit-transpose tiles.

    The paper uses cusparseScsr2csc for the top level; tiles are transposed
    word-level (:func:`bit_transpose_np`), never unpacked to bits.
    """
    t = m.tile_dim
    ptr = np.asarray(m.tile_row_ptr)
    tile_tr = np.repeat(np.arange(m.n_tile_rows, dtype=np.int64), np.diff(ptr))
    tile_tc = np.asarray(m.tile_col_idx, dtype=np.int64)

    # stable by tile column: within a column the tile rows stay ascending
    order = np.argsort(tile_tc, kind="stable")
    return B2SR(
        tile_row_ptr=_row_ptr(tile_tc[order], m.n_tile_cols),
        tile_col_idx=tile_tr[order].astype(np.int32),
        bit_tiles=bit_transpose_np(np.asarray(m.bit_tiles)[order], t),
        tile_dim=t,
        n_rows=m.n_cols,
        n_cols=m.n_rows,
        nnz=m.nnz,
    )


def bit_transpose_np(tiles: np.ndarray, t: int) -> np.ndarray:
    """Transpose each t-row bit tile with word-level block swaps (numpy).

    ``log2(t)`` rounds; round ``j`` swaps the off-diagonal ``j × j`` blocks
    of every ``2j × 2j`` block with one masked shift-XOR per word pair
    (Hacker's Delight ``transpose32``, LSB-first), in place on strided
    views. Works on uint32 words only: it never materialises the bits.
    """
    out = np.array(tiles, dtype=np.uint32, copy=True)
    words = out.reshape(-1, t)
    j = t // 2
    while j:
        mask = np.uint32(sum(1 << p for p in range(t) if not p & j))
        pairs = words.reshape(-1, t // (2 * j), 2, j)
        lo, hi = pairs[:, :, 0, :], pairs[:, :, 1, :]
        x = ((lo >> np.uint32(j)) ^ hi) & mask
        hi ^= x
        x <<= np.uint32(j)
        lo ^= x
        j //= 2
    return out


# ---------------------------------------------------------------------------
# Device-side bit packing/unpacking (jnp; kernels/bitpack has the Pallas twin)
# ---------------------------------------------------------------------------

def pack_bitvector(x: jax.Array, tile_dim: int, n_cols: Optional[int] = None) -> jax.Array:
    """Pack a dense 0/1 vector into per-tile words (uint32, low tile_dim bits).

    ``x``: bool/int/float vector of length n; returns uint32[ceil(n/t)].
    The paper's column-major vector binarization (Sec. IV, Listing 1 setup).
    """
    t = tile_dim
    n = x.shape[0] if n_cols is None else n_cols
    n_pad = ceil_div(n, t) * t
    xb = (x != 0).astype(jnp.uint32)
    xb = jnp.pad(xb, (0, n_pad - x.shape[0]))
    xb = xb.reshape(-1, t)
    shifts = jnp.arange(t, dtype=jnp.uint32)
    return jnp.sum(xb << shifts[None, :], axis=1, dtype=jnp.uint32)


def unpack_bitvector(words: jax.Array, tile_dim: int, n: int,
                     dtype=jnp.float32) -> jax.Array:
    t = tile_dim
    shifts = jnp.arange(t, dtype=jnp.uint32)
    bits = (words[:, None] >> shifts[None, :]) & jnp.uint32(1)
    return bits.reshape(-1)[:n].astype(dtype)


# Frontier *matrices* (engine/): the source axis packs into full uint32
# words regardless of tile_dim — tile_dim tiles the node axis, the batch
# axis is lane-packed at machine width (DESIGN.md §9).
SOURCE_WORD_BITS = 32


def pack_frontier_matrix(x: jax.Array, tile_dim: int,
                         n_rows: Optional[int] = None) -> jax.Array:
    """Binarize + bit-pack a batch of frontiers ``[n, S]`` along the S axis.

    Returns ``uint32[ceil(n/t), t, W]`` with ``W = ceil(S/32)``: entry
    ``[T, r, w]`` packs sources ``32w..32w+31`` of node ``T*t + r``,
    LSB-first. Node rows are tile-grouped so B2SR schemes gather one
    ``[t, W]`` panel per tile-column index (the multi-frontier twin of
    ``pack_bitvector``); the trailing node pad and source pad are zero bits.
    """
    t = tile_dim
    n = x.shape[0] if n_rows is None else n_rows
    s = x.shape[1]
    n_tiles = ceil_div(n, t)
    w = ceil_div(max(s, 1), SOURCE_WORD_BITS)
    xb = (x != 0).astype(jnp.uint32)
    xb = jnp.pad(xb, ((0, n_tiles * t - x.shape[0]),
                      (0, w * SOURCE_WORD_BITS - s)))
    xb = xb.reshape(n_tiles, t, w, SOURCE_WORD_BITS)
    shifts = jnp.arange(SOURCE_WORD_BITS, dtype=jnp.uint32)
    return jnp.sum(xb << shifts, axis=-1, dtype=jnp.uint32)


def unpack_frontier_matrix(words: jax.Array, n: int, n_sources: int,
                           dtype=jnp.float32) -> jax.Array:
    """Inverse of ``pack_frontier_matrix``: ``uint32[T, t, W]`` -> ``[n, S]``."""
    tiles, t, w = words.shape
    shifts = jnp.arange(SOURCE_WORD_BITS, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)     # [T, t, W, 32]
    return bits.reshape(tiles * t,
                        w * SOURCE_WORD_BITS)[:n, :n_sources].astype(dtype)


def unpack_tiles(tiles: jax.Array, tile_dim: int, dtype=jnp.float32) -> jax.Array:
    """uint32[..., t] words -> dense 0/1 [..., t, t] (row, col)."""
    t = tile_dim
    shifts = jnp.arange(t, dtype=jnp.uint32)
    bits = (tiles[..., :, None] >> shifts) & jnp.uint32(1)
    return bits.astype(dtype)


def or_reduce_words(words: jax.Array, axes) -> jax.Array:
    """Bitwise-OR reduction of uint32 words over ``axes``.

    The ∨-monoid over packed words (kernel-body safe) — shared by the jnp
    mxm/spmm schemes and the Pallas kernels.
    """
    return jax.lax.reduce(words, np.uint32(0), jax.lax.bitwise_or,
                          tuple(axes))


def bit_transpose_words(tiles: jax.Array, tile_dim: int) -> jax.Array:
    """In-device bit transpose of packed tiles (jnp path).

    Unpack/swap/repack; the Pallas kernel uses the same formulation — on TPU
    the unpack is VPU shift/AND work over VREGs, the paper's
    ``__ballot_sync``+``__brev`` analogue.
    """
    t = tile_dim
    bits = unpack_tiles(tiles, t, dtype=jnp.uint32)
    bits_t = jnp.swapaxes(bits, -1, -2)
    shifts = jnp.arange(t, dtype=jnp.uint32)
    return jnp.sum(bits_t << shifts[None, :], axis=-1, dtype=jnp.uint32)


def pack_dense_tiles(dense: jax.Array, tile_dim: int) -> jax.Array:
    """Dense [R*t, C*t] 0/1 matrix -> packed tiles uint32[R, C, t] (jnp path)."""
    t = tile_dim
    r_pad = ceil_div(dense.shape[0], t) * t
    c_pad = ceil_div(dense.shape[1], t) * t
    d = jnp.pad((dense != 0).astype(jnp.uint32),
                ((0, r_pad - dense.shape[0]), (0, c_pad - dense.shape[1])))
    d = d.reshape(r_pad // t, t, c_pad // t, t).transpose(0, 2, 1, 3)
    shifts = jnp.arange(t, dtype=jnp.uint32)
    return jnp.sum(d << shifts, axis=-1, dtype=jnp.uint32)


# ---------------------------------------------------------------------------
# Output-tile packing / accumulation (the SpGEMM C-side, paper Table III)
# ---------------------------------------------------------------------------

def pack_tile_bits(bits: jax.Array, tile_dim: int) -> jax.Array:
    """Dense 0/1 tiles [..., t(row), t(col)] -> packed words uint32[..., t].

    Inverse of ``unpack_tiles``: bit ``j`` of word ``r`` is element
    ``[r, j]``. This is the dense-tile -> bit-tile repack used when an mxm
    accumulates output tiles densely before re-emitting B2SR.
    """
    shifts = jnp.arange(tile_dim, dtype=jnp.uint32)
    return jnp.sum((bits != 0).astype(jnp.uint32) << shifts, axis=-1,
                   dtype=jnp.uint32)


def ell_to_packed_grid(ell: B2SREll) -> jax.Array:
    """ELL view -> dense tile grid uint32[n_tile_rows, n_tile_cols, t].

    The tile-row merge: all slots of a tile row land at their tile-column
    position; padding slots (col ``-1``) clip to column 0 with an all-zero
    word, so the elementwise-max scatter is an OR-merge (a legal ELL row has
    distinct tile columns, hence each grid cell sees one real word + zeros).
    """
    R, _ = ell.tile_col_idx.shape
    C = ell.n_tile_cols
    cols = jnp.clip(ell.tile_col_idx, 0, C - 1)
    tiles = jnp.where((ell.tile_col_idx >= 0)[:, :, None], ell.bit_tiles,
                      jnp.uint32(0))
    grid = jnp.zeros((R, C, ell.tile_dim), jnp.uint32)
    return grid.at[jnp.arange(R)[:, None], cols].max(tiles)


def packed_grid_to_b2sr(grid: np.ndarray, n_rows: int, n_cols: int) -> B2SR:
    """Dense tile grid uint32[R, C, t] -> B2SR (drop all-zero tiles).

    Host-side compression step after an mxm: the output grid has static
    shape under jit; the sparse top level (which tiles survived) is data-
    dependent and is rebuilt here, mirroring ``coo_to_b2sr``.
    """
    grid = np.asarray(grid)
    R, C, t = grid.shape
    if t not in TILE_DIMS:
        raise ValueError(f"tile_dim must be one of {TILE_DIMS}, got {t}")
    if R != ceil_div(n_rows, t) or C < ceil_div(n_cols, t):
        raise ValueError(f"grid {grid.shape} inconsistent with "
                         f"({n_rows}, {n_cols}) at tile_dim {t}")
    tr, tc = np.nonzero(grid.any(axis=-1))
    tiles = grid[tr, tc].astype(np.uint32)
    if not tiles.size:
        nnz = 0
    elif hasattr(np, "bitwise_count"):        # numpy >= 2.0
        nnz = int(np.bitwise_count(tiles).sum())
    else:
        nnz = int(np.unpackbits(tiles.view(np.uint8)).sum())
    return B2SR(
        tile_row_ptr=_row_ptr(tr, R),
        tile_col_idx=tc.astype(np.int32),
        bit_tiles=tiles,
        tile_dim=t,
        n_rows=n_rows,
        n_cols=n_cols,
        nnz=nnz,
    )


def b2sr_to_coo(m: B2SR) -> Tuple[np.ndarray, np.ndarray]:
    """B2SR -> (rows, cols) COO arrays (host-side, for re-ingestion)."""
    t = m.tile_dim
    ptr = np.asarray(m.tile_row_ptr)
    tile_tr = np.repeat(np.arange(m.n_tile_rows, dtype=np.int64), np.diff(ptr))
    tile_tc = np.asarray(m.tile_col_idx, dtype=np.int64)
    tiles = np.asarray(m.bit_tiles)
    bits = (tiles[:, :, None] >> np.arange(t, dtype=np.uint32)) & 1  # [n, t, t]
    p, r, c = np.nonzero(bits)
    return tile_tr[p] * t + r, tile_tc[p] * t + c


# ---------------------------------------------------------------------------
# Storage accounting (paper §VI.B) for format comparisons
# ---------------------------------------------------------------------------

def csr_storage_bytes(n_rows: int, nnz: int, value_bytes: int = 4) -> int:
    """CSR with fp32 values (the GraphBLAST/cuSPARSE baseline layout)."""
    return _INDEX_BYTES * (n_rows + 1) + _INDEX_BYTES * nnz + value_bytes * nnz


def compression_ratio(m: B2SR, value_bytes: int = 4) -> float:
    """B2SR_size / CSR_size (paper's metric; < 1.0 means B2SR is smaller)."""
    return m.storage_bytes() / max(csr_storage_bytes(m.n_rows, m.nnz, value_bytes), 1)


def occupancy(m: B2SR) -> float:
    """Average fraction of set bits inside non-empty tiles (paper Fig. 3b)."""
    if m.n_tiles == 0:
        return 0.0
    return float(m.nnz) / (m.n_tiles * m.tile_dim * m.tile_dim)


def best_tile_dim(rows: np.ndarray, cols: np.ndarray, n_rows: int, n_cols: int,
                  value_bytes: int = 4) -> Tuple[int, dict]:
    """Exact (non-sampled) optimal tile size by total storage (paper Fig. 5b)."""
    sizes = {}
    for t in TILE_DIMS:
        m = coo_to_b2sr(rows, cols, n_rows, n_cols, t)
        sizes[t] = m.storage_bytes()
    best = min(sizes, key=sizes.get)
    return best, sizes
