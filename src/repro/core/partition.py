"""Row-partitioned B2SR: per-device shards for multi-device execution.

The scale-out layer (DESIGN.md §11, §16): a graph's tile-row axis is split
into ``n_shards`` **contiguous ragged blocks** chosen by a greedy
prefix-sum split over per-tile-row tile counts — the bucketed SELL slabs
make per-row cost known in advance, so shard boundaries land where the
cumulative work crosses ``p/P`` of the total and ``balance()`` sits near
1.0 even on heavy-hub graphs (the v1 equal-block split reached 2.1+).
Every shard's ELL slab is padded to one **common padded row count**
(``rows_per_shard`` = the largest block) and one common slab width, so the
per-shard arrays still stack into single leading-axis-``P`` arrays that
``jax.shard_map`` splits across a mesh with one ``in_specs`` entry.

Because blocks are ragged, the concatenation of padded shard outputs is a
*permutation with padding holes* of the global packed layout; the static
``gather_idx`` map (global tile-row → stacked position) undoes it with one
replicated gather inside the shard_map body — no extra collective, and
``unpartition`` remains array-identical to the source B2SR.

Load skew *inside* a shard is what the SELL-style buckets handle — the
partition carries stacked per-bucket slabs with a bucket structure
harmonised across shards (same bucket count, same per-bucket width
everywhere) so the bucketed path also runs under one ``shard_map``.
Padding slab rows scatter to the **garbage row** ``rows_per_shard``
(consumers allocate ``rows_per_shard + 1`` output rows and drop the last).

:func:`build_exchange_plan` derives the communication-avoiding execution
schedule from a partition (DESIGN.md §16): per-shard column-word bitmaps
(which RHS words a shard's column space actually touches), the static
per-ring-offset ``ppermute`` send/recv index sets that move only those
words, and the output redistribution schedule that returns results as
equal-block device-sharded global arrays. Construction is host-side
numpy, like ``to_ell``/``to_bucketed``; :func:`place` is the one step that
touches a mesh (``GraphMatrix.shard`` calls it).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.b2sr import (B2SR, B2SREll, TILE_DIMS, _pytree, ceil_div,
                             ell_arrays, static_field)


@_pytree
@dataclasses.dataclass(frozen=True)
class PartitionedB2SR:
    """Stacked per-shard ELL (+ bucketed) slabs over ragged tile-row blocks.

    Shard ``p`` owns the contiguous global tile rows
    ``[row_starts[p], row_starts[p+1])``; every shard's slab is padded to
    the common ``rows_per_shard`` (the largest block). Padding rows have
    ``row_n_tiles == 0`` and all-``-1`` columns, so every scheme's
    ⊕-identity fills them; ``gather_idx`` maps each real global tile row
    to its stacked position ``p * rows_per_shard + local``.

    Bucketed slabs (built when ``with_buckets``) share one global bucket
    structure: bucket ``b`` has the same slab width ``k_b`` on every shard
    and every shard's slab is padded to the same row count; padding slab
    rows scatter to the **garbage row** ``rows_per_shard`` (consumers
    allocate ``rows_per_shard + 1`` output rows and drop the last).
    """

    tile_col_idx: jax.Array    # int32[P, R, K]; -1 = padding
    bit_tiles: jax.Array       # uint32[P, R, K, t]
    row_n_tiles: jax.Array     # int32[P, R]
    gather_idx: jax.Array      # int32[n_tile_rows] -> stacked position
    # harmonised bucket slabs (parallel tuples, empty when buckets off)
    bucket_col_idx: Tuple[jax.Array, ...]    # int32[P, rb, kb]
    bucket_bit_tiles: Tuple[jax.Array, ...]  # uint32[P, rb, kb, t]
    bucket_rows: Tuple[jax.Array, ...]       # int32[P, rb]; pad rows -> R
    tile_dim: int = static_field()
    n_rows: int = static_field()
    n_cols: int = static_field()
    n_tile_rows: int = static_field()        # real (unpadded) global count
    row_starts: Tuple[int, ...] = static_field()   # len P+1, ragged blocks
    shard_tiles: Tuple[int, ...] = static_field()  # real tiles per shard
    cut_tiles: int = static_field()          # tiles outside own row block

    @property
    def n_shards(self) -> int:
        return int(self.tile_col_idx.shape[0])

    @property
    def rows_per_shard(self) -> int:
        return int(self.tile_col_idx.shape[1])

    @property
    def slab_width(self) -> int:
        return int(self.tile_col_idx.shape[2])

    @property
    def n_buckets(self) -> int:
        return len(self.bucket_col_idx)

    @property
    def n_tile_cols(self) -> int:
        return ceil_div(self.n_cols, self.tile_dim)

    def n_tiles(self) -> int:
        return sum(self.shard_tiles)

    def block_rows(self, p: int) -> int:
        """Real (unpadded) tile rows owned by shard ``p``."""
        return self.row_starts[p + 1] - self.row_starts[p]

    def balance(self) -> float:
        """max/mean tiles per shard; 1.0 == perfectly even load."""
        total = self.n_tiles()
        if total == 0:
            return 1.0
        return max(self.shard_tiles) / (total / self.n_shards)

    def edge_cut(self) -> float:
        """Fraction of tiles whose tile-column lies outside the owning
        shard's own row block — the traffic a 2D (row×col) tiling would
        localise and the row partition pays via the operand broadcast."""
        total = self.n_tiles()
        return 0.0 if total == 0 else self.cut_tiles / total


def _split_starts(counts: np.ndarray, n_shards: int,
                  balanced: bool) -> Tuple[int, ...]:
    """Block boundaries: greedy prefix-sum split over per-row tile counts.

    Boundary ``p`` lands where the cumulative tile count crosses ``p/P``
    of the total, so each shard's work is within one row's cost of even
    (the cost-model split of DESIGN.md §16). Degenerate inputs (no tiles,
    one shard, ``balanced=False``) fall back to the v1 equal-row blocks.
    """
    n_tr = int(counts.shape[0])
    total = int(counts.sum())
    if not balanced or n_shards == 1 or total == 0 or n_tr == 0:
        r_eq = max(ceil_div(n_tr, n_shards), 1)
        return tuple(min(p * r_eq, n_tr) for p in range(n_shards)) + (n_tr,)
    cum = np.cumsum(counts.astype(np.int64))
    targets = total * np.arange(1, n_shards, dtype=np.float64) / n_shards
    # the row whose cumulative cost first reaches the target ends the
    # block — then round each boundary to whichever side of the target is
    # closer, so no shard systematically absorbs the overshoot
    bounds = np.searchsorted(cum, targets, side="left") + 1
    bounds = np.minimum(np.maximum.accumulate(bounds), n_tr)
    for i, b in enumerate(bounds):
        if b >= 2 and abs(cum[b - 2] - targets[i]) < abs(cum[b - 1]
                                                         - targets[i]):
            bounds[i] = b - 1
    bounds = np.minimum(np.maximum.accumulate(bounds), n_tr)
    return (0, *(int(b) for b in bounds), n_tr)


def partition_rows(mat: Union[B2SR, B2SREll], n_shards: int,
                   with_buckets: bool = True, max_buckets: int = 8,
                   balanced: bool = True) -> PartitionedB2SR:
    """Split a B2SR (or its ELL view) into ``n_shards`` row-block shards.

    Tile rows are split into contiguous **nnz-balanced** ragged blocks
    (``balanced=False`` restores the v1 equal blocks); every shard's slab
    is padded to the largest block's row count and the global max slab
    width. Works for any ``n_shards >= 1`` including counts larger than
    the tile-row axis (trailing shards own empty blocks).
    """
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    t = mat.tile_dim
    if t not in TILE_DIMS:
        raise ValueError(f"tile_dim must be one of {TILE_DIMS}, got {t}")
    n_tr = mat.n_tile_rows
    if isinstance(mat, B2SREll):
        col_g = np.asarray(mat.tile_col_idx)
        tiles_g = np.asarray(mat.bit_tiles)
        counts_g = np.asarray(mat.row_n_tiles)
    else:               # the padded slab is built on the host, never placed
        col_g, tiles_g, counts_g = ell_arrays(mat)
    starts = _split_starts(counts_g, n_shards, balanced)
    r_max = max(1, max(starts[p + 1] - starts[p] for p in range(n_shards)))

    k = col_g.shape[1]
    col = np.full((n_shards, r_max, k), -1, np.int32)
    tiles = np.zeros((n_shards, r_max, k, t), np.uint32)
    counts = np.zeros((n_shards, r_max), np.int32)
    gidx = np.zeros(n_tr, np.int32)

    shard_tiles = []
    cut = 0
    for p in range(n_shards):
        lo, hi = starts[p], starts[p + 1]
        m = hi - lo
        col[p, :m] = col_g[lo:hi]
        tiles[p, :m] = tiles_g[lo:hi]
        counts[p, :m] = counts_g[lo:hi]
        gidx[lo:hi] = p * r_max + np.arange(m, dtype=np.int32)
        c = col_g[lo:hi]
        valid = c >= 0
        shard_tiles.append(int(valid.sum()))
        # a tile is "local" to shard p if its tile-col falls inside the
        # shard's own row block (square-matrix notion; rectangular graphs
        # count every tile as cut beyond the row range)
        local = (c >= lo) & (c < hi)
        cut += int((valid & ~local).sum())

    buckets = _harmonised_buckets(col, tiles, counts, t, max_buckets) \
        if with_buckets else ((), (), ())

    return PartitionedB2SR(
        tile_col_idx=col,
        bit_tiles=tiles,
        row_n_tiles=counts,
        gather_idx=gidx,
        bucket_col_idx=buckets[0],
        bucket_bit_tiles=buckets[1],
        bucket_rows=buckets[2],
        tile_dim=t,
        n_rows=mat.n_rows,
        n_cols=mat.n_cols,
        n_tile_rows=n_tr,
        row_starts=tuple(int(s) for s in starts),
        shard_tiles=tuple(shard_tiles),
        cut_tiles=cut,
    )


def place(part: PartitionedB2SR, mesh, axes: Tuple[str, ...]
          ) -> PartitionedB2SR:
    """Put shard ``p``'s host slabs on the devices of mesh block ``p``.

    ``partition_rows`` builds the stacked ``[P, ...]`` slabs on the host;
    here each is split along its leading axis over ``axes`` (the layout
    the sharded rows' ``shard_map`` reads) and sent straight to its
    devices, so no device ever holds the whole partition and no op
    reshards it per call. The row remap is replicated.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    def put(a):
        spec = PartitionSpec(axes, *([None] * (a.ndim - 1)))
        return jax.device_put(a, NamedSharding(mesh, spec))

    return dataclasses.replace(
        part, gather_idx=jax.device_put(
            part.gather_idx, NamedSharding(mesh, PartitionSpec())),
        tile_col_idx=put(part.tile_col_idx),
        bit_tiles=put(part.bit_tiles), row_n_tiles=put(part.row_n_tiles),
        bucket_col_idx=tuple(put(a) for a in part.bucket_col_idx),
        bucket_bit_tiles=tuple(put(a) for a in part.bucket_bit_tiles),
        bucket_rows=tuple(put(a) for a in part.bucket_rows))


def _harmonised_buckets(col: np.ndarray, tiles: np.ndarray,
                        counts: np.ndarray, t: int, max_buckets: int):
    """Per-shard SELL buckets with one global bucket structure.

    Bucket boundaries (power-of-two count ranges, merged to ``max_buckets``)
    and slab widths come from the *global* count histogram, so bucket ``b``
    means the same range and width on every shard; each bucket's slab is
    padded to the max per-shard row count, padding rows pointing at the
    garbage row ``rows_per_shard``. Operates on the already-stacked
    ``[P, R, ...]`` arrays, so slab rows index shard-locally.
    """
    n_shards, r_max = counts.shape
    nonempty = counts > 0
    if not nonempty.any():
        return (), (), ()
    bidx = np.full(counts.shape, -1, np.int64)
    bidx[nonempty] = np.ceil(np.log2(counts[nonempty])).astype(np.int64)
    uniq = np.sort(np.unique(bidx[nonempty]))
    if uniq.size > max_buckets:
        keep = uniq[: max_buckets - 1]
        hub = uniq[max_buckets - 1]
        sel = nonempty & ~np.isin(bidx, keep)
        bidx[sel] = hub
        uniq = np.sort(np.unique(bidx[nonempty]))

    cols_out, tiles_out, rows_out = [], [], []
    for b in uniq:
        per_shard = []
        k_b = 1
        for p in range(n_shards):
            local = np.flatnonzero(bidx[p] == b)
            per_shard.append(local)
            if local.size:
                k_b = max(k_b, int(counts[p, local].max()))
        rb = max(max(len(ix) for ix in per_shard), 1)
        c_slab = np.full((n_shards, rb, k_b), -1, np.int32)
        t_slab = np.zeros((n_shards, rb, k_b, t), np.uint32)
        r_slab = np.full((n_shards, rb), r_max, np.int32)
        for p, local in enumerate(per_shard):
            if not local.size:
                continue
            c_slab[p, : local.size] = col[p, local, :k_b]
            t_slab[p, : local.size] = tiles[p, local, :k_b]
            r_slab[p, : local.size] = local
        cols_out.append(c_slab)
        tiles_out.append(t_slab)
        rows_out.append(r_slab)
    return tuple(cols_out), tuple(tiles_out), tuple(rows_out)


def unpartition(part: PartitionedB2SR) -> B2SR:
    """Reassemble the global B2SR from the stacked shard slabs.

    The exact inverse of ``partition_rows`` for any shard count and any
    (ragged or equal) block layout: each shard's real rows are read back
    through ``row_starts``, tile order within each row is preserved, so
    the result is array-identical to the source B2SR.
    """
    t = part.tile_dim
    col_s = np.asarray(part.tile_col_idx)
    tiles_s = np.asarray(part.bit_tiles)
    col = np.empty((part.n_tile_rows, part.slab_width), np.int32)
    tiles = np.empty((part.n_tile_rows, part.slab_width, t), np.uint32)
    for p in range(part.n_shards):
        lo, hi = part.row_starts[p], part.row_starts[p + 1]
        col[lo:hi] = col_s[p, : hi - lo]
        tiles[lo:hi] = tiles_s[p, : hi - lo]

    valid = col >= 0
    row_counts = valid.sum(axis=1)
    ptr = np.zeros(part.n_tile_rows + 1, np.int64)
    np.cumsum(row_counts, out=ptr[1:])
    tci = col[valid].astype(np.int32)
    bt = tiles[valid].astype(np.uint32)
    if bt.size == 0:
        nnz = 0
    elif hasattr(np, "bitwise_count"):
        nnz = int(np.bitwise_count(bt).sum())
    else:
        nnz = int(np.unpackbits(bt.view(np.uint8)).sum())
    return B2SR(
        tile_row_ptr=jnp.asarray(ptr.astype(np.int32)),
        tile_col_idx=jnp.asarray(tci),
        bit_tiles=jnp.asarray(bt.reshape(-1, t)),
        tile_dim=t,
        n_rows=part.n_rows,
        n_cols=part.n_cols,
        nnz=nnz,
    )


# ---------------------------------------------------------------------------
# Exchange plans: static communication schedules for combine="exchange"
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ExchangePlan:
    """Static ``ppermute`` schedule for the frontier-word exchange path.

    Built host-side from a partition's column-word bitmaps (DESIGN.md §16).
    The exchange-mode contract in ``ops_sharded``: the RHS arrives
    device-sharded in equal leading-axis blocks of ``c_eq`` tile-columns;
    each device assembles only the words its slab touches (own block +
    per-ring-offset pairwise sends), computes its ragged row block, and
    redistributes the output rows to their equal-block owners, returning a
    device-sharded global array (``r_eq`` tile rows per device).

    All index arrays are ``[P, W]`` — device ``p`` reads row ``p`` via
    ``axis_index`` — with padding lanes pointing at a garbage slot (source
    garbage: the appended zero row; destination garbage: the appended
    drop row), so every hop has one static width per offset.
    """

    n_shards: int
    c_eq: int                  # RHS tile-columns per equal device block
    r_eq: int                  # output tile-rows per equal device block
    n_tc_pad: int              # n_shards * c_eq
    # RHS word exchange: one ppermute hop per (nonempty) ring offset
    rhs_offsets: Tuple[int, ...]
    rhs_send_idx: Tuple[jax.Array, ...]   # int32[P, W_o] into own block
    rhs_recv_pos: Tuple[jax.Array, ...]   # int32[P, W_o] into the buffer
    # output redistribution: ragged compute blocks -> equal owner blocks
    out_offsets: Tuple[int, ...]
    out_send_idx: Tuple[jax.Array, ...]   # int32[P, W_o] into local rows
    out_recv_pos: Tuple[jax.Array, ...]   # int32[P, W_o] into owner block
    self_src: jax.Array                   # int32[P, W_s] local overlap copy
    self_dst: jax.Array
    # static comm accounting (lanes = leading-axis rows moved on the wire)
    rhs_lanes: int
    out_lanes: int
    gather_lanes: int          # what the all-gather path would move

    def exchanged_lanes(self) -> int:
        return self.rhs_lanes + self.out_lanes


def build_exchange_plan(part: PartitionedB2SR) -> Optional[ExchangePlan]:
    """Derive the static exchange schedule from a partition's bitmaps.

    Returns None for a single shard (nothing to exchange — the gather path
    is already collective-free there).
    """
    P = part.n_shards
    if P == 1:
        return None
    n_tc = part.n_tile_cols
    n_tr = part.n_tile_rows
    r_max = part.rows_per_shard
    c_eq = max(ceil_div(n_tc, P), 1)
    r_eq = max(ceil_div(n_tr, P), 1)
    n_tc_pad = P * c_eq

    # per-shard column-word bitmap: the RHS words shard p's slab touches
    # (bucket slabs reference the same tiles, so the ELL slab covers them)
    col = np.asarray(part.tile_col_idx)
    need = [np.unique(col[p][col[p] >= 0]).astype(np.int64)
            for p in range(P)]

    # need[p] split by owner q = word // c_eq; ring offset o sends q -> q+o
    need_from = [[n_p[(n_p // c_eq) == q] for q in range(P)]
                 for n_p in need]
    rhs_offsets, rhs_send, rhs_recv = [], [], []
    rhs_lanes = 0
    for o in range(1, P):
        w_o = max(len(need_from[(q + o) % P][q]) for q in range(P))
        if w_o == 0:
            continue
        send = np.full((P, w_o), c_eq, np.int32)        # garbage: pad row
        recv = np.full((P, w_o), n_tc_pad, np.int32)    # garbage: drop row
        for q in range(P):
            dst = (q + o) % P
            words = need_from[dst][q]
            send[q, : len(words)] = words - q * c_eq
            recv[dst, : len(words)] = words
        rhs_offsets.append(o)
        rhs_send.append(jnp.asarray(send))
        rhs_recv.append(jnp.asarray(recv))
        rhs_lanes += P * w_o

    # output redistribution: shard q computed global rows
    # [row_starts[q], row_starts[q+1]); owner p holds [p*r_eq, (p+1)*r_eq)
    overlaps = {}
    for q in range(P):
        lo_q, hi_q = part.row_starts[q], part.row_starts[q + 1]
        for p in range(P):
            lo = max(lo_q, p * r_eq)
            hi = min(hi_q, (p + 1) * r_eq)
            if hi > lo:
                overlaps[(q, p)] = (lo, hi)
    w_s = max((hi - lo for (q, p), (lo, hi) in overlaps.items() if q == p),
              default=0)
    self_src = np.full((P, max(w_s, 1)), r_max, np.int32)
    self_dst = np.full((P, max(w_s, 1)), r_eq, np.int32)
    for p in range(P):
        lo, hi = overlaps.get((p, p), (0, 0))
        m = hi - lo
        if m:
            self_src[p, :m] = np.arange(lo, hi) - part.row_starts[p]
            self_dst[p, :m] = np.arange(lo, hi) - p * r_eq

    out_offsets, out_send, out_recv = [], [], []
    out_lanes = 0
    for o in range(1, P):
        pairs = [(q, (q + o) % P) for q in range(P)]
        w_o = max((overlaps[(q, p)][1] - overlaps[(q, p)][0]
                   for (q, p) in pairs if (q, p) in overlaps), default=0)
        if w_o == 0:
            continue
        send = np.full((P, w_o), r_max, np.int32)
        recv = np.full((P, w_o), r_eq, np.int32)
        for q, p in pairs:
            if (q, p) not in overlaps:
                continue
            lo, hi = overlaps[(q, p)]
            m = hi - lo
            send[q, :m] = np.arange(lo, hi) - part.row_starts[q]
            recv[p, :m] = np.arange(lo, hi) - p * r_eq
        out_offsets.append(o)
        out_send.append(jnp.asarray(send))
        out_recv.append(jnp.asarray(recv))
        out_lanes += P * w_o

    return ExchangePlan(
        n_shards=P, c_eq=c_eq, r_eq=r_eq, n_tc_pad=n_tc_pad,
        rhs_offsets=tuple(rhs_offsets), rhs_send_idx=tuple(rhs_send),
        rhs_recv_pos=tuple(rhs_recv),
        out_offsets=tuple(out_offsets), out_send_idx=tuple(out_send),
        out_recv_pos=tuple(out_recv),
        self_src=jnp.asarray(self_src), self_dst=jnp.asarray(self_dst),
        rhs_lanes=rhs_lanes, out_lanes=out_lanes,
        gather_lanes=P * (P - 1) * r_max,
    )


def mesh_fingerprint(mesh, axes: Tuple[str, ...]) -> Tuple:
    """Hashable identity of (mesh, shard axes) for plan-cache keys.

    Two meshes that differ in axis names, shape, or member devices must
    never share a compiled plan — the shard_map trace bakes all three in.
    """
    return (
        tuple(mesh.axis_names),
        tuple(int(s) for s in mesh.devices.shape),
        tuple(axes),
        tuple(int(d.id) for d in mesh.devices.flat),
    )


def shard_count(mesh, axes: Tuple[str, ...]) -> int:
    """Product of the mesh-axis sizes the partition shards over."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    missing = [a for a in axes if a not in sizes]
    if missing:
        raise ValueError(f"mesh has no axis {missing}; axes are "
                         f"{tuple(mesh.axis_names)}")
    p = 1
    for a in axes:
        p *= int(sizes[a])
    return p
