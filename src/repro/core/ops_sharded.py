"""Multi-device shard_map twins of the Table II/III rows (DESIGN.md §11, §16).

Every row here runs the *same per-shard math* as its single-device twin —
the jnp ``_*_block`` helpers from ``repro.core.ops`` or the real Pallas
wrappers from ``repro.kernels`` (selected by ``g.backend``), so
bit-exactness is by construction — wrapped in one ``jax.shard_map`` over
the stacked per-shard slabs of a
:class:`~repro.core.partition.PartitionedB2SR`. Two combine layouts exist,
selected at ``GraphMatrix.shard(combine=...)`` time and isolated per plan
(the mesh fingerprint in ``PlanKey`` carries the comm mode):

``combine="gather"`` (the PR 5 layout, default)
  - slab arrays shard their leading (shard) axis; the right-hand operand
    is replicated (``P()``),
  - each device computes its own contiguous row block locally,
  - one ``jax.lax.all_gather(..., tiled=True)`` concatenates the padded
    blocks on every device. Blocks are **ragged** since the nnz-balanced
    v2 partitioner, so the stacked layout is a permutation-with-holes of
    the global one; the partition's static ``gather_idx`` map undoes it
    with one local gather on the replicated result — no extra collective.

``combine="exchange"`` (communication-avoiding, DESIGN.md §16)
  - the operand arrives **device-sharded** in equal contiguous blocks of
    ``c_eq`` tile-columns — nothing is replicated, ever;
  - each device assembles only the column words its slab actually touches:
    its own block plus one statically-scheduled ``ppermute`` per nonempty
    ring offset (send/recv index sets precomputed host-side from the
    partition's column-word bitmap, padding lanes aimed at garbage slots);
  - after the local block compute, the ragged output rows are
    redistributed to their equal-block owners the same way (self-copy +
    per-offset ``ppermute``), so the op returns a **global but
    device-sharded** array in the single-device layout — iterative
    algorithms feed it straight back in with zero per-iteration
    replication. All P-1 hops of a phase are issued before any consumer,
    so XLA's latency-hiding scheduler runs the ring transfers
    concurrently with the scatter/compute between them.
  Exchange is bit-exact against gather by construction: both run the same
  block math over the same slab; only who holds which words differs.

Masks are applied *after* the combine through the same shared §V helpers
(``apply_frontier_mask`` / ``apply_grid_mask`` / ``apply_output_mask``) the
non-fused single-device paths use: mask-at-store semantics, one code path.

The rows register for both b2sr backends; since v2 the ``b2sr_pallas``
rows dispatch the real ``kernels/`` entry points *inside* the shard_map
body (interpret mode on CPU), building per-shard ELL views from the raw
slab arrays — the jnp word schemes remain the ``b2sr`` bodies. The graph
SpGEMM rows (B replicated, streamed tile-row-wise) and the fused
``mxm_sum`` reduction stay on the jnp blocks and the gather/psum combine:
their B-side slabs are three ragged arrays with no column-word layout to
exchange (decision record in DESIGN.md §16). The CSR baseline registers no
sharded rows — ``GraphMatrix.shard`` rejects it up front.

``row_chunk`` is rejected on every sharded row: the shards themselves are
the memory bound. The generic layer raises before any operand staging
(``dispatch.reject_sharded_row_chunk``); the checks here are backstops.

Comm accounting: every sharded call increments
``gather_words_total`` / ``exchange_words_total{op,backend,shards}`` with
the statically-known element counts its collectives move, and annotates
the ambient launch trace span. The increments run at trace time — once
per compiled plan, per call in eager execution — so eager benchmark
sweeps read exact per-call volumes while jitted serving loops see one
increment per (re)trace.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


from repro.core import ops as core_ops
from repro.core.b2sr import (B2SREll, ceil_div, ell_to_packed_grid,
                             unpack_tiles)
from repro.core.dispatch import BOTH, apply_output_mask, register
from repro.core.operands import pad_leading
from repro.core.ops import (_bff_setup, _bmv_bbb_block, _bmv_bbf_block,
                            _bmv_bff_block, _mxm_bbb_block, _mxm_bbf_block,
                            _spmm_bbb_block, _spmm_bbf_block, _spmm_block,
                            apply_frontier_mask, apply_grid_mask)
from repro.core.partition import (ExchangePlan, PartitionedB2SR, shard_count)


class _LocalShard:
    """One device's view of the partition inside a shard_map body."""

    __slots__ = ("col", "tiles", "cnt", "bcol", "btiles", "brows", "part")

    def __init__(self, col, tiles, cnt, bcol, btiles, brows,
                 part: PartitionedB2SR):
        self.col = col          # int32[R, K]
        self.tiles = tiles      # uint32[R, K, t]
        self.cnt = cnt          # int32[R]
        self.bcol = bcol        # tuple of int32[rb, kb]
        self.btiles = btiles    # tuple of uint32[rb, kb, t]
        self.brows = brows      # tuple of int32[rb]; pad rows -> R (garbage)
        self.part = part

    @property
    def rows(self) -> int:
        return self.part.rows_per_shard

    def ell(self, n_cols: int) -> B2SREll:
        """This shard's slab as a B2SREll — the Pallas wrappers' operand."""
        return B2SREll(tile_col_idx=self.col, bit_tiles=self.tiles,
                       row_n_tiles=self.cnt, tile_dim=self.part.tile_dim,
                       n_rows=self.rows * self.part.tile_dim, n_cols=n_cols)

    def scatter_buckets(self, out, block_fn):
        """Per-bucket compute + scatter through the local row permutation.

        ``out`` must have ``rows_per_shard + 1`` leading rows — padding
        slab rows target the final garbage row, which is dropped here.
        """
        for cb, tb, rb in zip(self.bcol, self.btiles, self.brows):
            out = out.at[rb].set(block_fn(cb, tb))
        return out[: self.rows]


def _bucket_ell(cb, tb, tile_dim: int, n_cols: int) -> B2SREll:
    """One bucket slab as a B2SREll (per-bucket Pallas operand)."""
    return B2SREll(tile_col_idx=cb, bit_tiles=tb,
                   row_n_tiles=jnp.sum(cb >= 0, axis=1).astype(jnp.int32),
                   tile_dim=tile_dim, n_rows=cb.shape[0] * tile_dim,
                   n_cols=n_cols)


def _pallas(g) -> bool:
    return g.backend == "b2sr_pallas"


def _no_row_chunk(call):
    if call.row_chunk is not None:
        raise ValueError(
            "row_chunk is not supported on the sharded path — the row "
            "partition already bounds per-device memory (unshard() first "
            "if chunked evaluation is required)")


def _combine_for(g, part: Optional[PartitionedB2SR] = None) -> str:
    """Per-call combine mode: exchange only on the graph's own forward
    partition (the transposed view carries its own plan), gather for any
    side partition (tri_count's L) and for single-shard meshes, where
    gather is already collective-free."""
    if (getattr(g, "comm", "gather") == "exchange"
            and (part is None or part is g.partitioned)
            and getattr(g, "xplan", None) is not None):
        return "exchange"
    return "gather"


_COMM_LABELS = ("op", "backend", "shards")


def _record_comm(g, part: PartitionedB2SR, combine: str, op: str,
                 n: int) -> None:
    """Static comm-volume accounting for one sharded call (see module doc).

    ``n`` counts *elements* moved by the call's collectives — literal
    uint32 words on the packed rows, values on the dense ones. Gather
    charges the operand replication plus the ring all-gather of the
    padded blocks; exchange charges exactly its scheduled lanes.
    """
    if part.n_shards <= 1:
        return
    from repro.obs import metrics as obs_metrics
    from repro.obs import trace as obs_trace
    if not obs_metrics.enabled():
        return
    reg = obs_metrics.get_registry()
    labels = {"op": op, "backend": g.backend, "shards": part.n_shards}
    if combine == "exchange":
        reg.counter("exchange_words_total",
                    "elements moved by exchange-mode collectives",
                    _COMM_LABELS).inc(n, **labels)
        obs_trace.annotate(comm="exchange", exchanged_words=n)
    else:
        reg.counter("gather_words_total",
                    "elements moved by gather/psum-mode collectives",
                    _COMM_LABELS).inc(n, **labels)
        obs_trace.annotate(comm=combine, gathered_words=n)


def _sharded_call(g, local_fn, rhs_arrays: Tuple, combine: str = "gather",
                  part: PartitionedB2SR = None, op: str = "mxv",
                  out_ndim: int = 1):
    """Run ``local_fn(view, *rhs)`` under shard_map over ``g``'s mesh.

    ``local_fn`` returns this device's output block (leading axis = the
    partition's padded local rows).

    ``combine="gather"``: rhs replicated, padded blocks all-gathered, the
    static ``gather_idx`` permutation restores the global row order; the
    result is replicated — drop-in for every caller. ``combine="psum"``
    sum-reduces scalars/partials. ``combine="exchange"`` takes exactly one
    rhs array whose leading axis is the tile-column/word axis, runs the
    statically-scheduled ppermute exchange from ``g.xplan``, and returns
    the global result **device-sharded** in equal row blocks (still the
    single-device layout — callers slice and mask it unchanged).
    ``out_ndim`` is the rank of ``local_fn``'s output (exchange needs it
    for the output partition spec).
    """
    from jax.sharding import PartitionSpec as P

    part = g.partitioned if part is None else part
    mesh, axes = g.mesh, g.shard_axes
    nb = part.n_buckets
    slabs = (part.tile_col_idx, part.bit_tiles, part.row_n_tiles,
             *part.bucket_col_idx, *part.bucket_bit_tiles,
             *part.bucket_rows)
    slab_specs = tuple(P(axes, *([None] * (a.ndim - 1))) for a in slabs)
    n_slab = len(slabs)

    def view_of(s):
        return _LocalShard(
            s[0][0], s[1][0], s[2][0],
            tuple(x[0] for x in s[3: 3 + nb]),
            tuple(x[0] for x in s[3 + nb: 3 + 2 * nb]),
            tuple(x[0] for x in s[3 + 2 * nb: 3 + 3 * nb]),
            part)

    if combine in ("gather", "psum"):
        in_specs = slab_specs + tuple(P() for _ in rhs_arrays)

        def body(*args):
            view = view_of(args[:n_slab])
            y = local_fn(view, *args[n_slab:])
            if combine == "psum":
                return jax.lax.psum(y, axes)
            return jax.lax.all_gather(y, axes, axis=0, tiled=True)

        y = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                          out_specs=P(), check_vma=False)(*slabs, *rhs_arrays)
        rhs_words = sum(int(jnp.size(a)) for a in rhs_arrays)
        P_n = part.n_shards
        if combine == "gather":
            # ragged blocks: the stacked concatenation is a permutation
            # (with padding holes) of the global layout — one local gather
            # on the replicated result restores it
            lane = 1
            for s in y.shape[1:]:
                lane *= int(s)
            moved = (P_n - 1) * (rhs_words
                                 + P_n * part.rows_per_shard * lane)
            y = jnp.take(y, part.gather_idx, axis=0)
        else:
            moved = (P_n - 1) * (rhs_words + int(jnp.size(y)))
        _record_comm(g, part, combine, op, moved)
        return y

    if combine != "exchange":
        raise ValueError(f"unknown combine mode {combine!r}")
    xp: ExchangePlan = g.xplan
    if xp is None or len(rhs_arrays) != 1:
        raise ValueError("combine='exchange' needs a built ExchangePlan "
                         "and exactly one column-word operand")
    if len(axes) != 1:
        raise ValueError("combine='exchange' runs a single-axis ppermute "
                         "ring; shard over one mesh axis (got "
                         f"{axes})")
    axis = axes[0]
    Pn = xp.n_shards
    nr, no = len(xp.rhs_offsets), len(xp.out_offsets)
    idx = (*xp.rhs_send_idx, *xp.rhs_recv_pos, *xp.out_send_idx,
           *xp.out_recv_pos, xp.self_src, xp.self_dst)
    rhs = pad_leading(rhs_arrays[0], xp.n_tc_pad)
    in_specs = slab_specs
    in_specs += tuple(P(axes, None) for _ in idx)
    in_specs += (P(axes, *([None] * (rhs.ndim - 1))),)

    def ring(payload, offset):
        return jax.lax.ppermute(
            payload, axis, perm=[(i, (i + offset) % Pn) for i in range(Pn)])

    def body(*args):
        view = view_of(args[:n_slab])
        ix = [a[0] for a in args[n_slab: n_slab + len(idx)]]
        x_blk = args[n_slab + len(idx)]
        r_send, r_recv = ix[:nr], ix[nr: 2 * nr]
        o_send, o_recv = ix[2 * nr: 2 * nr + no], ix[2 * nr + no:
                                                     2 * nr + 2 * no]
        self_src, self_dst = ix[-2], ix[-1]

        # --- inbound word exchange: all P-1 ring hops issued up front, so
        # the transfers overlap each other and the own-block scatter
        tail = jnp.zeros((1,) + x_blk.shape[1:], x_blk.dtype)
        x_g = jnp.concatenate([x_blk, tail], axis=0)   # garbage src @ c_eq
        recvs = [ring(x_g[si], o)
                 for o, si in zip(xp.rhs_offsets, r_send)]
        buf = jnp.zeros((xp.n_tc_pad + 1,) + x_blk.shape[1:], x_blk.dtype)
        q = jax.lax.axis_index(axis)
        buf = jax.lax.dynamic_update_slice(
            buf, x_blk, (q * xp.c_eq,) + (0,) * (x_blk.ndim - 1))
        for rp, rv in zip(r_recv, recvs):
            buf = buf.at[rp].set(rv)   # pad lanes land on the drop row

        y = local_fn(view, buf[:-1])

        # --- outbound redistribution: ragged compute blocks -> the equal
        # owner blocks (self-copy + one ppermute per nonempty offset)
        y_g = jnp.concatenate(
            [y, jnp.zeros((1,) + y.shape[1:], y.dtype)], axis=0)
        o_recvs = [ring(y_g[si], o)
                   for o, si in zip(xp.out_offsets, o_send)]
        out = jnp.zeros((xp.r_eq + 1,) + y.shape[1:], y.dtype)
        out = out.at[self_dst].set(y_g[self_src])
        for rp, rv in zip(o_recv, o_recvs):
            out = out.at[rp].set(rv)
        return out[:-1]

    out_specs = P(axes, *([None] * (out_ndim - 1)))
    y = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False)(*slabs, *idx, rhs)
    rhs_lane = 1
    for s in rhs.shape[1:]:
        rhs_lane *= int(s)
    out_lane = 1
    for s in y.shape[1:]:
        out_lane *= int(s)
    _record_comm(g, part, "exchange", op,
                 xp.rhs_lanes * rhs_lane + xp.out_lanes * out_lane)
    return y


def _b2sr_ell(col, tiles, cnt, tile_dim: int, n_rows: int,
              n_cols: int) -> B2SREll:
    """Wrap raw replicated ELL arrays back into the view the blocks take."""
    return B2SREll(tile_col_idx=col, bit_tiles=tiles, row_n_tiles=cnt,
                   tile_dim=tile_dim, n_rows=n_rows, n_cols=n_cols)


# ---------------------------------------------------------------------------
# mxv rows (Table II)
# ---------------------------------------------------------------------------

def _mxv_bin_words(g, xw, bucketed: bool) -> jax.Array:
    part = g.partitioned
    t = part.tile_dim

    # a partition without bucket slabs (built while use_buckets was off, or
    # an empty graph) runs the ELL slab — identical results, no SELL split
    bucketed = bucketed and part.n_buckets
    if _pallas(g):
        from repro.kernels.bmv import ops as bmv_ops
        if bucketed:
            def local(view, x):
                out = jnp.zeros((view.rows + 1,), jnp.uint32)
                return view.scatter_buckets(
                    out, lambda cb, tb: bmv_ops.bmv_bin_bin_bin(
                        _bucket_ell(cb, tb, t, part.n_cols), x))
        else:
            def local(view, x):
                return bmv_ops.bmv_bin_bin_bin(view.ell(part.n_cols), x)
    elif bucketed:
        def local(view, x):
            out = jnp.zeros((view.rows + 1,), jnp.uint32)
            return view.scatter_buckets(
                out, lambda cb, tb: _bmv_bbb_block(cb, tb, x, t))
    else:
        def local(view, x):
            return _bmv_bbb_block(view.col, view.tiles, x, t)

    y = _sharded_call(g, local, (xw,), combine=_combine_for(g), op="mxv",
                      out_ndim=1)
    return y[: ceil_div(part.n_rows, t)]


@register("mxv", "bitvec", "bin", "b2sr", bucketed=False, masked=False,
          sharded=True)
@register("mxv", "bitvec", "bin", "b2sr_pallas", bucketed=False,
          masked=False, sharded=True)
def _mxv_bitvec_sharded(g, xw, call):
    _no_row_chunk(call)
    return _mxv_bin_words(g, xw, bucketed=False)


@register("mxv", "bitvec", "bin", "b2sr", bucketed=True, masked=False,
          sharded=True)
@register("mxv", "bitvec", "bin", "b2sr_pallas", bucketed=True,
          masked=False, sharded=True)
def _mxv_bitvec_bucketed_sharded(g, xw, call):
    _no_row_chunk(call)
    return _mxv_bin_words(g, xw, bucketed=True)


@register("mxv", "bitvec", "bin", "b2sr", bucketed=False, masked=True,
          sharded=True)
@register("mxv", "bitvec", "bin", "b2sr_pallas", bucketed=False,
          masked=True, sharded=True)
def _mxv_bitvec_masked_sharded(g, xw, call):
    _no_row_chunk(call)
    y = _mxv_bin_words(g, xw, bucketed=False)
    return y & (~call.mask if call.complement else call.mask)


@register("mxv", "bitvec", "bin", "b2sr", bucketed=True, masked=True,
          sharded=True)
@register("mxv", "bitvec", "bin", "b2sr_pallas", bucketed=True,
          masked=True, sharded=True)
def _mxv_bitvec_bucketed_masked_sharded(g, xw, call):
    _no_row_chunk(call)
    y = _mxv_bin_words(g, xw, bucketed=True)
    return y & (~call.mask if call.complement else call.mask)


# Sharded pull rows (DESIGN.md §12): the pull *schedule* is a per-shard
# kernel concern, but under shard_map every shard runs the same block math
# over its row slab, so the sharded pull twin is the masked sharded sweep.
# What direction-optimization changes on a mesh is the *decision*: the
# traversal loops popcount the frontier/visited words, so every shard
# derives the same global density and switches in lockstep — no collective
# needed for the heuristic itself.

@register("mxv_pull", "bitvec", "bin", "b2sr", bucketed=False, masked=True,
          sharded=True)
@register("mxv_pull", "bitvec", "bin", "b2sr_pallas", bucketed=False,
          masked=True, sharded=True)
def _mxv_pull_sharded(g, xw, call):
    _no_row_chunk(call)
    y = _mxv_bin_words(g, xw, bucketed=False)
    return y & (~call.mask if call.complement else call.mask)


@register("mxv_pull", "bitvec", "bin", "b2sr", bucketed=True, masked=True,
          sharded=True)
@register("mxv_pull", "bitvec", "bin", "b2sr_pallas", bucketed=True,
          masked=True, sharded=True)
def _mxv_pull_bucketed_sharded(g, xw, call):
    _no_row_chunk(call)
    y = _mxv_bin_words(g, xw, bucketed=True)
    return y & (~call.mask if call.complement else call.mask)


def _mxv_count_vals(g, xw, call, bucketed: bool) -> jax.Array:
    part = g.partitioned
    t = part.tile_dim
    dt = call.out_dtype

    bucketed = bucketed and part.n_buckets
    if _pallas(g):
        from repro.kernels.bmv import ops as bmv_ops
        if bucketed:
            def local(view, x):
                out = jnp.zeros((view.rows + 1, t), dt)
                return view.scatter_buckets(
                    out, lambda cb, tb: bmv_ops.bmv_bin_bin_full(
                        _bucket_ell(cb, tb, t, part.n_cols), x, dt).reshape(-1, t))
        else:
            def local(view, x):
                return bmv_ops.bmv_bin_bin_full(
                    view.ell(part.n_cols), x, dt).reshape(-1, t)
    elif bucketed:
        def local(view, x):
            out = jnp.zeros((view.rows + 1, t), dt)
            return view.scatter_buckets(
                out, lambda cb, tb: _bmv_bbf_block(cb, tb, x, dt))
    else:
        def local(view, x):
            return _bmv_bbf_block(view.col, view.tiles, x, dt)

    y = _sharded_call(g, local, (xw,), combine=_combine_for(g), op="mxv",
                      out_ndim=2)
    return y.reshape(-1)[: part.n_rows]


@register("mxv", "bitvec", "full", "b2sr", bucketed=False, masked=False,
          sharded=True)
@register("mxv", "bitvec", "full", "b2sr_pallas", bucketed=False,
          masked=False, sharded=True)
def _mxv_count_sharded(g, xw, call):
    _no_row_chunk(call)
    return _mxv_count_vals(g, xw, call, bucketed=False)


@register("mxv", "bitvec", "full", "b2sr", bucketed=True, masked=False,
          sharded=True)
@register("mxv", "bitvec", "full", "b2sr_pallas", bucketed=True,
          masked=False, sharded=True)
def _mxv_count_bucketed_sharded(g, xw, call):
    _no_row_chunk(call)
    return _mxv_count_vals(g, xw, call, bucketed=True)


@register("mxv", "bitvec", "full", "b2sr", bucketed=False, masked=True,
          sharded=True)
@register("mxv", "bitvec", "full", "b2sr_pallas", bucketed=False,
          masked=True, sharded=True)
def _mxv_count_masked_sharded(g, xw, call):
    _no_row_chunk(call)
    y = _mxv_count_vals(g, xw, call, bucketed=False)
    return apply_output_mask(y, call.mask, call.complement,
                             jnp.zeros((), call.out_dtype))


@register("mxv", "bitvec", "full", "b2sr", bucketed=True, masked=True,
          sharded=True)
@register("mxv", "bitvec", "full", "b2sr_pallas", bucketed=True,
          masked=True, sharded=True)
def _mxv_count_bucketed_masked_sharded(g, xw, call):
    _no_row_chunk(call)
    y = _mxv_count_vals(g, xw, call, bucketed=True)
    return apply_output_mask(y, call.mask, call.complement,
                             jnp.zeros((), call.out_dtype))


def _mxv_dense_vals(g, x, call, bucketed: bool) -> jax.Array:
    part = g.partitioned
    t = part.tile_dim
    sr = call.semiring
    av = call.a_value
    x3, ident, _ = _bff_setup(part.n_tile_cols, t, x, sr, call.a_value)

    bucketed = bucketed and part.n_buckets
    if _pallas(g):
        from repro.kernels.bmv import ops as bmv_ops
        # the wrapper pads/stages the flat vector itself, so the local body
        # recovers it from the (possibly exchange-widened) tile-word layout
        if bucketed:
            def local(view, xr):
                xf = xr.reshape(-1)[: part.n_cols]
                out = jnp.full((view.rows + 1, t), ident, dtype=xr.dtype)
                return view.scatter_buckets(
                    out, lambda cb, tb: bmv_ops.bmv_bin_full_full(
                        _bucket_ell(cb, tb, t, part.n_cols), xf, sr, av).reshape(-1, t))
        else:
            def local(view, xr):
                xf = xr.reshape(-1)[: part.n_cols]
                return bmv_ops.bmv_bin_full_full(
                    view.ell(part.n_cols), xf, sr, av).reshape(-1, t)
    elif bucketed:
        def local(view, xr):
            out = jnp.full((view.rows + 1, t), ident, dtype=xr.dtype)
            return view.scatter_buckets(
                out,
                lambda cb, tb: _bmv_bff_block(cb, tb, xr, sr, av, ident, t))
    else:
        def local(view, xr):
            return _bmv_bff_block(view.col, view.tiles, xr, sr, av, ident, t)

    y = _sharded_call(g, local, (x3,), combine=_combine_for(g), op="mxv",
                      out_ndim=2)
    return y.reshape(-1)[: part.n_rows]


@register("mxv", "dense", "full", "b2sr", bucketed=False, masked=False,
          sharded=True)
@register("mxv", "dense", "full", "b2sr_pallas", bucketed=False,
          masked=False, sharded=True)
def _mxv_dense_sharded(g, x, call):
    _no_row_chunk(call)
    return _mxv_dense_vals(g, x, call, bucketed=False)


@register("mxv", "dense", "full", "b2sr", bucketed=True, masked=False,
          sharded=True)
@register("mxv", "dense", "full", "b2sr_pallas", bucketed=True,
          masked=False, sharded=True)
def _mxv_dense_bucketed_sharded(g, x, call):
    _no_row_chunk(call)
    return _mxv_dense_vals(g, x, call, bucketed=True)


@register("mxv", "dense", "full", "b2sr", bucketed=False, masked=True,
          sharded=True)
@register("mxv", "dense", "full", "b2sr_pallas", bucketed=False,
          masked=True, sharded=True)
def _mxv_dense_masked_sharded(g, x, call):
    _no_row_chunk(call)
    y = _mxv_dense_vals(g, x, call, bucketed=False)
    return apply_output_mask(y, call.mask, call.complement,
                             call.semiring.identity_for(y.dtype))


@register("mxv", "dense", "full", "b2sr", bucketed=True, masked=True,
          sharded=True)
@register("mxv", "dense", "full", "b2sr_pallas", bucketed=True,
          masked=True, sharded=True)
def _mxv_dense_bucketed_masked_sharded(g, x, call):
    _no_row_chunk(call)
    y = _mxv_dense_vals(g, x, call, bucketed=True)
    return apply_output_mask(y, call.mask, call.complement,
                             call.semiring.identity_for(y.dtype))


# ---------------------------------------------------------------------------
# mxm rows: dense features (SpMM) / frontier batches / graph SpGEMM
# ---------------------------------------------------------------------------

def _mxm_dense_vals(g, x, call, bucketed: bool) -> jax.Array:
    part = g.partitioned
    t = part.tile_dim
    n_tc = part.n_tile_cols
    d = x.shape[1]
    dt = call.out_dtype or x.dtype
    x_pad = jnp.pad(x, ((0, n_tc * t - x.shape[0]), (0, 0)))
    x3 = x_pad.reshape(n_tc, t, d)

    bucketed = bucketed and part.n_buckets
    if _pallas(g):
        from repro.kernels.spmm import ops as spmm_ops
        if bucketed:
            def local(view, xr):
                x2 = xr.reshape(-1, d)[: part.n_cols]
                out = jnp.zeros((view.rows + 1, t, d), dtype=x.dtype)
                return view.scatter_buckets(
                    out, lambda cb, tb: spmm_ops.spmm(
                        _bucket_ell(cb, tb, t, part.n_cols), x2).reshape(-1, t, d))
        else:
            def local(view, xr):
                x2 = xr.reshape(-1, d)[: part.n_cols]
                return spmm_ops.spmm(view.ell(part.n_cols),
                                     x2).reshape(-1, t, d)
    elif bucketed:
        def local(view, xr):
            out = jnp.zeros((view.rows + 1, t, d), dtype=dt)
            return view.scatter_buckets(
                out, lambda cb, tb: _spmm_block(cb, tb, xr, t, dt))
    else:
        def local(view, xr):
            return _spmm_block(view.col, view.tiles, xr, t, dt)

    y = _sharded_call(g, local, (x3,), combine=_combine_for(g), op="mxm",
                      out_ndim=3)
    return y.reshape(-1, d)[: part.n_rows]


@register("mxm", "dense", "full", "b2sr", bucketed=False, masked=False,
          sharded=True)
@register("mxm", "dense", "full", "b2sr_pallas", bucketed=False,
          masked=False, sharded=True)
def _mxm_dense_sharded(g, x, call):
    _no_row_chunk(call)
    return _mxm_dense_vals(g, x, call, bucketed=False)


@register("mxm", "dense", "full", "b2sr", bucketed=True, masked=False,
          sharded=True)
@register("mxm", "dense", "full", "b2sr_pallas", bucketed=True,
          masked=False, sharded=True)
def _mxm_dense_bucketed_sharded(g, x, call):
    _no_row_chunk(call)
    return _mxm_dense_vals(g, x, call, bucketed=True)


@register("mxm", "dense", "full", "b2sr", bucketed=False, masked=True,
          sharded=True)
@register("mxm", "dense", "full", "b2sr_pallas", bucketed=False,
          masked=True, sharded=True)
def _mxm_dense_masked_sharded(g, x, call):
    _no_row_chunk(call)
    y = _mxm_dense_vals(g, x, call, bucketed=False)
    return apply_output_mask(y, call.mask, call.complement,
                             call.semiring.identity_for(y.dtype))


@register("mxm", "dense", "full", "b2sr", bucketed=True, masked=True,
          sharded=True)
@register("mxm", "dense", "full", "b2sr_pallas", bucketed=True,
          masked=True, sharded=True)
def _mxm_dense_bucketed_masked_sharded(g, x, call):
    _no_row_chunk(call)
    y = _mxm_dense_vals(g, x, call, bucketed=True)
    return apply_output_mask(y, call.mask, call.complement,
                             call.semiring.identity_for(y.dtype))


def _mxm_bitmat_vals(g, xw, call, bucketed: bool) -> jax.Array:
    part = g.partitioned
    t = part.tile_dim
    d = xw.shape[1]
    dt = call.out_dtype if call.out_dtype is not None else jnp.float32

    bucketed = bucketed and part.n_buckets
    if _pallas(g):
        from repro.kernels.spmm import ops as spmm_ops
        if bucketed:
            def local(view, xr):
                out = jnp.zeros((view.rows + 1, t, d), dtype=dt)
                return view.scatter_buckets(
                    out, lambda cb, tb: spmm_ops.spmm_bin_bin_full(
                        _bucket_ell(cb, tb, t, part.n_cols), xr, dt).reshape(-1, t, d))
        else:
            def local(view, xr):
                return spmm_ops.spmm_bin_bin_full(
                    view.ell(part.n_cols), xr, dt).reshape(-1, t, d)
    elif bucketed:
        def local(view, xr):
            out = jnp.zeros((view.rows + 1, t, d), dtype=dt)
            return view.scatter_buckets(
                out, lambda cb, tb: _spmm_bbf_block(cb, tb, xr, dt))
    else:
        def local(view, xr):
            return _spmm_bbf_block(view.col, view.tiles, xr, dt)

    y = _sharded_call(g, local, (xw,), combine=_combine_for(g), op="mxm",
                      out_ndim=3)
    return y.reshape(-1, d)[: part.n_rows]


@register("mxm", "bitmat", "full", "b2sr", bucketed=False, masked=False,
          sharded=True)
@register("mxm", "bitmat", "full", "b2sr_pallas", bucketed=False,
          masked=False, sharded=True)
def _mxm_bitmat_sharded(g, xw, call):
    _no_row_chunk(call)
    return _mxm_bitmat_vals(g, xw, call, bucketed=False)


@register("mxm", "bitmat", "full", "b2sr", bucketed=True, masked=False,
          sharded=True)
@register("mxm", "bitmat", "full", "b2sr_pallas", bucketed=True,
          masked=False, sharded=True)
def _mxm_bitmat_bucketed_sharded(g, xw, call):
    _no_row_chunk(call)
    return _mxm_bitmat_vals(g, xw, call, bucketed=True)


@register("mxm", "bitmat", "full", "b2sr", bucketed=False, masked=True,
          sharded=True)
@register("mxm", "bitmat", "full", "b2sr_pallas", bucketed=False,
          masked=True, sharded=True)
def _mxm_bitmat_masked_sharded(g, xw, call):
    _no_row_chunk(call)
    y = _mxm_bitmat_vals(g, xw, call, bucketed=False)
    return apply_output_mask(y, call.mask, call.complement,
                             call.semiring.identity_for(y.dtype))


@register("mxm", "bitmat", "full", "b2sr", bucketed=True, masked=True,
          sharded=True)
@register("mxm", "bitmat", "full", "b2sr_pallas", bucketed=True,
          masked=True, sharded=True)
def _mxm_bitmat_bucketed_masked_sharded(g, xw, call):
    _no_row_chunk(call)
    y = _mxm_bitmat_vals(g, xw, call, bucketed=True)
    return apply_output_mask(y, call.mask, call.complement,
                             call.semiring.identity_for(y.dtype))


def _mxm_frontier_words(g, fw, bucketed: bool) -> jax.Array:
    part = g.partitioned
    t = part.tile_dim
    W = fw.shape[2]

    bucketed = bucketed and part.n_buckets
    if _pallas(g):
        from repro.kernels.spmm import ops as spmm_ops
        if bucketed:
            def local(view, f3):
                out = jnp.zeros((view.rows + 1, t, W), jnp.uint32)
                return view.scatter_buckets(
                    out, lambda cb, tb: spmm_ops.spmm_bin_bin_bin(
                        _bucket_ell(cb, tb, t, part.n_cols), f3))
        else:
            def local(view, f3):
                return spmm_ops.spmm_bin_bin_bin(view.ell(part.n_cols), f3)
    elif bucketed:
        def local(view, f3):
            out = jnp.zeros((view.rows + 1, t, W), jnp.uint32)
            return view.scatter_buckets(
                out, lambda cb, tb: _spmm_bbb_block(cb, tb, f3, t))
    else:
        def local(view, f3):
            return _spmm_bbb_block(view.col, view.tiles, f3, t)

    y = _sharded_call(g, local, (fw,), combine=_combine_for(g), op="mxm",
                      out_ndim=3)
    return y[: ceil_div(part.n_rows, t)]


@register("mxm", "frontier", "bin", "b2sr", bucketed=False, masked=False,
          sharded=True)
@register("mxm", "frontier", "bin", "b2sr_pallas", bucketed=False,
          masked=False, sharded=True)
def _mxm_frontier_sharded(g, fw, call):
    _no_row_chunk(call)
    return _mxm_frontier_words(g, fw, bucketed=False)


@register("mxm", "frontier", "bin", "b2sr", bucketed=True, masked=False,
          sharded=True)
@register("mxm", "frontier", "bin", "b2sr_pallas", bucketed=True,
          masked=False, sharded=True)
def _mxm_frontier_bucketed_sharded(g, fw, call):
    _no_row_chunk(call)
    return _mxm_frontier_words(g, fw, bucketed=True)


@register("mxm", "frontier", "bin", "b2sr", bucketed=False, masked=True,
          sharded=True)
@register("mxm", "frontier", "bin", "b2sr_pallas", bucketed=False,
          masked=True, sharded=True)
def _mxm_frontier_masked_sharded(g, fw, call):
    _no_row_chunk(call)
    y = _mxm_frontier_words(g, fw, bucketed=False)
    return apply_frontier_mask(y, call.mask, call.complement)


@register("mxm", "frontier", "bin", "b2sr", bucketed=True, masked=True,
          sharded=True)
@register("mxm", "frontier", "bin", "b2sr_pallas", bucketed=True,
          masked=True, sharded=True)
def _mxm_frontier_bucketed_masked_sharded(g, fw, call):
    _no_row_chunk(call)
    y = _mxm_frontier_words(g, fw, bucketed=True)
    return apply_frontier_mask(y, call.mask, call.complement)


@register("mxm_pull", "frontier", "bin", "b2sr", bucketed=False, masked=True,
          sharded=True)
@register("mxm_pull", "frontier", "bin", "b2sr_pallas", bucketed=False,
          masked=True, sharded=True)
def _mxm_pull_sharded(g, fw, call):
    _no_row_chunk(call)
    y = _mxm_frontier_words(g, fw, bucketed=False)
    return apply_frontier_mask(y, call.mask, call.complement)


@register("mxm_pull", "frontier", "bin", "b2sr", bucketed=True, masked=True,
          sharded=True)
@register("mxm_pull", "frontier", "bin", "b2sr_pallas", bucketed=True,
          masked=True, sharded=True)
def _mxm_pull_bucketed_sharded(g, fw, call):
    _no_row_chunk(call)
    y = _mxm_frontier_words(g, fw, bucketed=True)
    return apply_frontier_mask(y, call.mask, call.complement)


def _mxm_graph_grid(g, other_ell: B2SREll) -> jax.Array:
    """A (sharded) ∨.∧ B (replicated): per-shard SpGEMM row blocks.

    B streams tile-row-wise against every shard's A tiles — one pass of
    B's slabs per iteration for the whole mesh; the output grid blocks
    reassemble into the single-device ``mxm_bin_bin_bin`` grid through the
    gather_idx permutation. The slab (not the SELL buckets) carries A
    here, matching the single-device SpGEMM whose B side is always one
    ELL; B's three ragged slab arrays have no column-word layout, so the
    graph rows stay on the gather combine (DESIGN.md §16).
    """
    part = g.partitioned
    t = part.tile_dim
    if t != other_ell.tile_dim:
        raise ValueError(f"tile_dim mismatch: {t} vs {other_ell.tile_dim}")
    if part.n_cols != other_ell.n_rows:
        raise ValueError(f"inner-dim mismatch: A is {part.n_rows}x"
                         f"{part.n_cols}, B is {other_ell.n_rows}x"
                         f"{other_ell.n_cols}")

    def local(view, b_col, b_tiles, b_cnt):
        b = _b2sr_ell(b_col, b_tiles, b_cnt, t, other_ell.n_rows,
                      other_ell.n_cols)
        return _mxm_bbb_block(view.col, view.tiles, b, t)

    grid = _sharded_call(g, local, (other_ell.tile_col_idx,
                                    other_ell.bit_tiles,
                                    other_ell.row_n_tiles), op="mxm")
    return grid[: part.n_tile_rows]


@register("mxm", "graph", "bin", "b2sr", bucketed=BOTH, sharded=True)
@register("mxm", "graph", "bin", "b2sr_pallas", bucketed=BOTH, sharded=True)
def _mxm_graph_sharded(g, other, call):
    _no_row_chunk(call)
    grid = _mxm_graph_grid(g, other.ell)
    m_ell = call.mask.ell if call.mask is not None else None
    return apply_grid_mask(grid, m_ell, call.complement)


def _mxm_graph_counts(g, other_ell: B2SREll, out_dtype) -> jax.Array:
    part = g.partitioned
    t = part.tile_dim
    if t != other_ell.tile_dim:
        raise ValueError(f"tile_dim mismatch: {t} vs {other_ell.tile_dim}")
    if part.n_cols != other_ell.n_rows:
        raise ValueError(f"inner-dim mismatch: A is {part.n_rows}x"
                         f"{part.n_cols}, B is {other_ell.n_rows}x"
                         f"{other_ell.n_cols}")

    def local(view, b_col, b_tiles, b_cnt):
        b = _b2sr_ell(b_col, b_tiles, b_cnt, t, other_ell.n_rows,
                      other_ell.n_cols)
        return _mxm_bbf_block(view.col, view.tiles, b, t)

    grid = _sharded_call(g, local, (other_ell.tile_col_idx,
                                    other_ell.bit_tiles,
                                    other_ell.row_n_tiles), op="mxm")
    grid = grid[: part.n_tile_rows]
    dense = grid.transpose(0, 2, 1, 3).reshape(
        part.n_tile_rows * t, other_ell.n_tile_cols * t)
    return dense[: part.n_rows, : other_ell.n_cols].astype(out_dtype)


@register("mxm", "graph", "full", "b2sr", bucketed=BOTH, masked=False,
          sharded=True)
@register("mxm", "graph", "full", "b2sr_pallas", bucketed=BOTH,
          masked=False, sharded=True)
def _mxm_graph_count_sharded(g, other, call):
    _no_row_chunk(call)
    return _mxm_graph_counts(g, other.ell, jnp.int32)


@register("mxm", "graph", "full", "b2sr", bucketed=BOTH, masked=True,
          sharded=True)
@register("mxm", "graph", "full", "b2sr_pallas", bucketed=BOTH,
          masked=True, sharded=True)
def _mxm_graph_count_masked_sharded(g, other, call):
    _no_row_chunk(call)
    counts = _mxm_graph_counts(g, other.ell, jnp.int32)
    return core_ops._apply_dense_mask(counts, call.mask.ell,
                                      call.complement, jnp.int32)


# ---------------------------------------------------------------------------
# mxm_sum: the fused Σ L ⊙ (L·Lᵀ) reduction (tri_count)
# ---------------------------------------------------------------------------

@register("mxm_sum", "tri", "full", "b2sr", bucketed=BOTH, masked=True,
          sharded=True)
@register("mxm_sum", "tri", "full", "b2sr_pallas", bucketed=BOTH,
          masked=True, sharded=True)
def _tri_sum_sharded(g, tri, call):
    """Per-shard masked count SpGEMM partials + one psum.

    L is row-partitioned with the graph's shard count (memoized on the
    :class:`LowerTriangle` operand); Lᵀ is replicated; the mask tile for an
    output block is the shard's own L slab, so each device's partial is
    Σ over its row block and the psum is exact (integer adds).
    """
    _no_row_chunk(call)
    part = tri.partitioned(shard_count(g.mesh, g.shard_axes))
    ell_t = tri.ell_t
    t = part.tile_dim

    def local(view, b_col, b_tiles, b_cnt):
        b = _b2sr_ell(b_col, b_tiles, b_cnt, t, ell_t.n_rows, ell_t.n_cols)
        counts = _mxm_bbf_block(view.col, view.tiles, b, t)  # [R, C, t, t]
        # the mask tiles for this output block are the shard's own L slab
        mg = ell_to_packed_grid(
            _b2sr_ell(view.col, view.tiles, view.cnt, t,
                      view.rows * t, part.n_cols))           # [R, C, t]
        m_bits = unpack_tiles(mg, t, jnp.int32)              # [R, C, t, t]
        return jnp.sum(counts * m_bits)

    total = _sharded_call(g, local, (ell_t.tile_col_idx, ell_t.bit_tiles,
                                     ell_t.row_n_tiles),
                          combine="psum", part=part, op="mxm_sum")
    return total.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Mesh-context shardmap SpMM (the pre-registry scale-out entry point)
# ---------------------------------------------------------------------------

def spmm_b2sr_shardmap(ell: B2SREll, x, axes, row_chunk=None):
    """Tile-row-partitioned B2SR SpMM (§Perf, EXPERIMENTS.md).

    The ambient-mesh twin of the registered sharded rows above: instead of
    a pre-partitioned graph it shards a single ELL view over the *current*
    mesh context at call time (each device owns a block of tile-rows, the
    feature matrix is all-gathered once — reduce-scatter in the backward).
    Kept for callers that manage their own mesh scope
    (``tests/test_shardmap_agg.py`` pins it); model code routes through
    ``repro.gnn_bit.layers.aggregate`` and the registry instead.
    Requires ell.n_rows == n_tile_rows × tile_dim (padded) and both the
    tile-row dim and x's node dim to shard evenly over ``axes``.
    """
    from jax._src.mesh import thread_resources
    from jax.sharding import PartitionSpec as P

    mesh = thread_resources.env.physical_mesh
    axes = tuple(a for a in axes if a in mesh.axis_names)
    if not axes or mesh.empty:
        return core_ops.spmm_b2sr(ell, x, row_chunk=row_chunk)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    p_total = 1
    for a in axes:
        p_total *= sizes[a]
    R = int(ell.tile_col_idx.shape[0])
    if (R % p_total != 0 or x.shape[0] % p_total != 0
            or ell.n_rows != R * ell.tile_dim):
        # small graphs (fewer tile-rows than shards) fall back to the
        # GSPMD path — the shard_map contract needs even blocks
        return core_ops.spmm_b2sr(ell, x, row_chunk=row_chunk)
    t = ell.tile_dim

    def block(col_blk, tiles_blk, cnt_blk, x_blk):
        x_full = jax.lax.all_gather(x_blk, axes, axis=0, tiled=True)
        ell_blk = B2SREll(
            tile_col_idx=col_blk, bit_tiles=tiles_blk, row_n_tiles=cnt_blk,
            tile_dim=t, n_rows=col_blk.shape[0] * t, n_cols=ell.n_cols)
        return core_ops.spmm_b2sr(ell_blk, x_full, row_chunk=row_chunk,
                                  vma_axes=axes)

    return jax.shard_map(
        block, mesh=mesh,
        in_specs=(P(axes, None), P(axes, None, None), P(axes), P(axes, None)),
        out_specs=P(axes, None),
    )(ell.tile_col_idx, ell.bit_tiles, ell.row_n_tiles, x)
