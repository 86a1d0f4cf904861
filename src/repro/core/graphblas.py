"""High-level GraphBLAS matrix object: one generic operation API.

``GraphMatrix`` is what algorithms and models consume. It bundles:
  - the B2SR tile CSR on the host (+ optional transposed B2SR for vxm),
  - the float CSR baseline representation (the GraphBLAST stand-in),
  - padded views for the static-shape TPU kernel path, built on first use.

The operation surface is two generic ops (DESIGN.md §10):

  ``mxv(x, semiring, desc)``   x: dense vector | BitVector
  ``mxm(B, semiring, desc)``   B: GraphMatrix | dense matrix | FrontierBatch

The paper's Table II/III row is resolved from the operand *types* and the
semiring — a packed ``BitVector`` on the boolean semiring is the BFS
kernel, a dense vector on min-plus is SSSP, a ``FrontierBatch`` is the
multi-source engine row — and the implementation is looked up in the
central dispatch registry (``repro.core.dispatch``) keyed by
``(op, rhs, out, backend, bucketed, masked)``. Masks, complement,
input-transpose, replace semantics, and row chunking travel in one
:class:`~repro.core.descriptor.Descriptor`.

``backend`` selects the compute path:
  "b2sr"      jnp word-level bit ops (repro.core.ops)
  "b2sr_pallas"  Pallas kernels (repro.kernels, interpret on CPU)
  "csr"       float CSR baseline (repro.core.csr)

Load balancing: both b2sr backends transparently run the row-bucketed
(SELL-style) path when ``use_buckets`` is on (the default) — ``ell_buckets``
is built lazily from the tile CSR on first use (DESIGN.md §2).
``row_chunk`` callers keep the single-ELL path (chunking needs one uniform
row axis); that padded view is built only when such a path first asks.

The pre-registry per-row method names (``mxv_bool``, ``mxv_count``,
``spmm``, ``spmm_bool``, ``mxm_count``) survive as deprecation shims:
external callers get a warning and the old behavior; ``repro``-internal
call sites raise, so algorithms/ and engine/ can never quietly regress
onto them.
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import b2sr as b2sr_mod
from repro.core import csr as csr_mod
from repro.core import descriptor as descriptor_mod
from repro.core import dispatch
from repro.core import partition as partition_mod
from repro.core.b2sr import (B2SR, B2SRBucketedEll, B2SREll,
                             ell_to_packed_grid, pack_bitvector)
from repro.core.descriptor import _UNSET, Descriptor
from repro.core.dispatch import OpCall, warn_deprecated
from repro.core.operands import (BitVector, FrontierBatch, check_operand,
                                 operand_kind)
from repro.core.semiring import Semiring, ARITHMETIC, BOOLEAN

BACKENDS = ("b2sr", "b2sr_pallas", "csr")


class LowerTriangle:
    """Memoized strict-lower-triangle operands (tri_count's L / Lᵀ pair).

    The COO split is done eagerly (cheap numpy); the B2SR/ELL builds and
    the bucketed view are lazy, so the CSR backend never pays for packing
    it never reads. Cached on the owning ``GraphMatrix`` (the
    ``degrees_cache`` pattern) — repeated ``tri_count`` calls stop
    rebuilding L host-side on every call.
    """

    def __init__(self, csr: csr_mod.CSRMatrix, tile_dim: int, n: int):
        rows = np.asarray(csr.row_idx)
        cols = np.asarray(csr.col_idx)
        keep = rows > cols
        self.rows, self.cols = rows[keep], cols[keep]
        self._tile_dim = tile_dim
        self._n = n
        self._ell: Optional[B2SREll] = None
        self._ell_t: Optional[B2SREll] = None
        self._buckets: Optional[B2SRBucketedEll] = None
        self._parts: dict = {}          # n_shards -> PartitionedB2SR of L

    @property
    def ell(self) -> B2SREll:
        if self._ell is None:
            m = b2sr_mod.coo_to_b2sr(self.rows, self.cols, self._n, self._n,
                                     self._tile_dim)
            self._ell = b2sr_mod.to_ell(m)
            self._ell_t = b2sr_mod.to_ell(b2sr_mod.transpose(m))
        return self._ell

    @property
    def ell_t(self) -> B2SREll:
        self.ell
        return self._ell_t

    def buckets(self) -> B2SRBucketedEll:
        if self._buckets is None:
            self._buckets = b2sr_mod.to_bucketed(self.ell)
        return self._buckets

    def partitioned(self, n_shards: int) -> "partition_mod.PartitionedB2SR":
        """L row-partitioned for the sharded mxm_sum row (memoized per
        shard count, like the ELL pair)."""
        if n_shards not in self._parts:
            self._parts[n_shards] = partition_mod.partition_rows(
                self.ell, n_shards, with_buckets=False)
        return self._parts[n_shards]


class _LoopPlan:
    """A compiled whole-graph loop and the dispatch keys its trace
    resolved. Each later call replays those keys through the resolve hook
    (``dispatch.replay``), so a fault injector still sees every kernel the
    loop runs, once per call, as it did when each call traced the loop."""

    def __init__(self, fn: Callable):
        self.fn = fn
        self.keys: Optional[Tuple] = None

    def __call__(self, *args):
        if self.keys is not None:
            dispatch.replay(self.keys)
            return self.fn(*args)
        with dispatch.recording() as keys:
            out = self.fn(*args)
        self.keys = tuple(dict.fromkeys(keys))
        return out


@dataclasses.dataclass
class GraphMatrix:
    """An immutable homogeneous-graph adjacency matrix, multi-format."""

    n_rows: int
    n_cols: int
    nnz: int
    tile_dim: int
    # the host tile CSR every padded view is built from, and its transpose
    # (for vxm / pull traversal); None for a graph wrapped around an ELL
    tiles: Optional[B2SR]
    tiles_t: Optional[B2SR]
    csr: csr_mod.CSRMatrix
    csr_t: Optional[csr_mod.CSRMatrix]
    backend: str = "b2sr"
    # row-bucketed (SELL-style) views, built lazily from ell/ell_t; the
    # default compute path on the b2sr backends when ``use_buckets`` is on
    ell_buckets: Optional[B2SRBucketedEll] = None
    ell_buckets_t: Optional[B2SRBucketedEll] = None
    use_buckets: bool = True
    # single-max padded ELL views, built only when a path asks for them
    # (use_buckets off, row_chunk, sharding, k-hop): on a skewed graph
    # they can be many times the tile bytes
    ell_cache: Optional[B2SREll] = None
    ell_t_cache: Optional[B2SREll] = None
    # lazy caches (same pattern as ell_buckets): the out-degree vector, the
    # transposed view, the structure fingerprint used by engine/planner,
    # and tri_count's strict-lower-triangle operand pair
    degrees_cache: Optional[jax.Array] = None
    transposed_cache: Optional["GraphMatrix"] = None
    fingerprint_cache: Optional[str] = None
    tri_cache: Optional[LowerTriangle] = None
    # scale-out state (``shard(mesh)``, DESIGN.md §11): the mesh + axes the
    # graph is row-partitioned over and the stacked per-shard slabs for the
    # forward / transposed orientation; every op dispatches to the
    # shard_map rows while these are set
    mesh: Optional[object] = None
    shard_axes: Optional[tuple] = None
    partitioned: Optional["partition_mod.PartitionedB2SR"] = None
    partitioned_t: Optional["partition_mod.PartitionedB2SR"] = None
    # comm layout for the sharded rows (DESIGN.md §16): "gather" replicates
    # operands + all-gathers outputs; "exchange" moves only the column
    # words each shard's slab touches over a static ppermute ring. The
    # ExchangePlans hold device arrays, so they live here (mutable holder)
    # rather than on the frozen partition pytree.
    comm: str = "gather"
    xplan: Optional["partition_mod.ExchangePlan"] = None
    xplan_t: Optional["partition_mod.ExchangePlan"] = None
    # compiled whole-graph loops (``loop_plan``). Not an __init__ field:
    # every derived graph (``dataclasses.replace`` in with_backend,
    # with_buckets, transposed, shard, unshard) starts with an empty memo,
    # so a loop traced for one backend or layout never runs on another
    loop_plans: dict = dataclasses.field(default_factory=dict, init=False,
                                         repr=False, compare=False)

    # -- constructors -------------------------------------------------------
    @staticmethod
    def from_coo(rows: np.ndarray, cols: np.ndarray, n_rows: int, n_cols: int,
                 tile_dim: int = 32, with_transpose: bool = True,
                 backend: str = "b2sr") -> "GraphMatrix":
        mat = b2sr_mod.coo_to_b2sr(rows, cols, n_rows, n_cols, tile_dim)
        return GraphMatrix._from_tiles(mat, rows, cols, with_transpose,
                                       backend)

    @staticmethod
    def from_dense(mat: np.ndarray, tile_dim: int = 32, **kw) -> "GraphMatrix":
        rows, cols = np.nonzero(np.asarray(mat))
        return GraphMatrix.from_coo(rows, cols, mat.shape[0], mat.shape[1],
                                    tile_dim, **kw)

    @staticmethod
    def from_b2sr(mat: B2SR, with_transpose: bool = True,
                  backend: str = "b2sr") -> "GraphMatrix":
        """Wrap an already-built B2SR (e.g. an mxm output) without re-packing.

        The CSR twin is derived from the same tiles (one unpack), not by a
        second COO -> B2SR conversion.
        """
        rows, cols = b2sr_mod.b2sr_to_coo(mat)
        return GraphMatrix._from_tiles(mat, rows, cols, with_transpose,
                                       backend)

    @staticmethod
    def _from_tiles(mat: B2SR, rows, cols, with_transpose: bool,
                    backend: str) -> "GraphMatrix":
        if backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}")
        return GraphMatrix(
            n_rows=mat.n_rows, n_cols=mat.n_cols, nnz=mat.nnz,
            tile_dim=mat.tile_dim, tiles=mat,
            tiles_t=b2sr_mod.transpose(mat) if with_transpose else None,
            csr=csr_mod.from_coo(rows, cols, mat.n_rows, mat.n_cols),
            csr_t=(csr_mod.from_coo(cols, rows, mat.n_cols, mat.n_rows)
                   if with_transpose else None),
            backend=backend,
        )

    # -- padded views (built on first use, then memoized) --------------------
    @property
    def ell(self) -> B2SREll:
        if self.ell_cache is None:
            self.ell_cache = b2sr_mod.to_ell(self.tiles)
        return self.ell_cache

    @property
    def ell_t(self) -> Optional[B2SREll]:
        if self.ell_t_cache is None and self.tiles_t is not None:
            self.ell_t_cache = b2sr_mod.to_ell(self.tiles_t)
        return self.ell_t_cache

    @property
    def has_transpose(self) -> bool:
        return self.tiles_t is not None or self.ell_t_cache is not None

    def _tiles_or_ell(self):
        """The host tile CSR, or the ELL of a graph wrapped around one."""
        return self.tiles if self.tiles is not None else self.ell

    def with_backend(self, backend: str) -> "GraphMatrix":
        if backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}, "
                             f"got {backend!r}")
        if backend == "csr" and self.sharded:
            raise ValueError("the csr baseline has no sharded rows; call "
                             "unshard() before with_backend('csr')")
        # the cached transpose carries the old backend; drop it (degrees,
        # the structure fingerprint, and the lower-triangle operands are
        # backend-independent and survive)
        return dataclasses.replace(self, backend=backend,
                                   transposed_cache=None)

    def with_buckets(self, use_buckets: bool) -> "GraphMatrix":
        """Toggle the bucketed (SELL-style) compute path on the b2sr backends."""
        return dataclasses.replace(self, use_buckets=use_buckets,
                                   transposed_cache=None)

    def transposed(self) -> "GraphMatrix":
        """Aᵀ as a view: swap the stored forward/transposed representations.

        Memoized (like ``ell_buckets``): repeated PageRank/PPR/vxm calls on
        the same graph reuse one transposed view instead of rebuilding it —
        and the view's back-reference makes ``transposed()`` an involution.
        """
        if self.transposed_cache is not None:
            return self.transposed_cache
        if not self.has_transpose:
            raise ValueError("GraphMatrix built without transpose "
                             "(with_transpose=True)")
        # build (and cache on *self*) the transpose's bucketed view before
        # swapping, so the cached view shares it with this instance
        if (self.use_buckets and self.backend != "csr"
                and self.ell_buckets_t is None):
            self.ell_buckets_t = b2sr_mod.to_bucketed(
                self.tiles_t if self.tiles_t is not None else self.ell_t)
        gt = dataclasses.replace(
            self, tiles=self.tiles_t, tiles_t=self.tiles,
            ell_cache=self.ell_t_cache, ell_t_cache=self.ell_cache,
            csr=self.csr_t,
            csr_t=self.csr, ell_buckets=self.ell_buckets_t,
            ell_buckets_t=self.ell_buckets, n_rows=self.n_cols,
            n_cols=self.n_rows, degrees_cache=None, transposed_cache=self,
            fingerprint_cache=None, tri_cache=None,
            partitioned=self.partitioned_t, partitioned_t=self.partitioned,
            xplan=self.xplan_t, xplan_t=self.xplan)
        self.transposed_cache = gt
        return gt

    def buckets(self) -> B2SRBucketedEll:
        """The bucketed view, built lazily from the tile CSR and cached."""
        if self.ell_buckets is None:
            self.ell_buckets = b2sr_mod.to_bucketed(self._tiles_or_ell())
        return self.ell_buckets

    def _bucketed(self, row_chunk: Optional[int] = None) -> bool:
        """Whether this op dispatches to the bucketed path."""
        return self.use_buckets and row_chunk is None

    # -- scale-out: row-partitioned multi-device execution (DESIGN.md §11) --
    @property
    def sharded(self) -> bool:
        return self.partitioned is not None

    def shard(self, mesh, axes: Optional[Sequence[str]] = None,
              max_buckets: int = 8, combine: str = "gather",
              balanced: bool = True) -> "GraphMatrix":
        """Row-partition this graph across ``mesh`` (scale-out entry point).

        Returns a new ``GraphMatrix`` whose every operation — and hence
        every algorithm and engine query built on it — executes under
        ``jax.shard_map``: shard ``p`` owns a contiguous block of tile
        rows, split nnz-balanced over the per-tile-row word counts
        (``balanced=False`` restores the v1 equal blocks). Results are
        bit-exact against the unsharded twin; no call site changes.

        ``combine`` picks the collective layout (DESIGN.md §16):
        ``"gather"`` replicates operands and all-gathers the padded row
        blocks every op; ``"exchange"`` precomputes which column words
        each shard's slab touches and moves only those (plus the owned
        output words) over a static ``ppermute`` ring — the
        communication-avoiding mode for iterative mxv/spmm. Exchange
        needs a single shard axis (``ppermute`` rings are 1-D); rows
        without an exchange layout (graph SpGEMM, tri_count) stay on
        gather/psum transparently.

        ``axes`` selects the mesh axes to shard over (default: all of
        them); their size product is the shard count. Both orientations
        are partitioned so ``transposed()`` (BFS/PR pull direction) stays
        sharded too.
        """
        if self.backend == "csr":
            raise ValueError("the csr baseline has no sharded rows; shard "
                             "the b2sr or b2sr_pallas backend")
        if combine not in ("gather", "exchange"):
            raise ValueError(f"combine must be 'gather' or 'exchange', "
                             f"got {combine!r}")
        axes = tuple(axes) if axes is not None else tuple(mesh.axis_names)
        if combine == "exchange" and len(axes) != 1:
            raise ValueError("combine='exchange' runs a single-axis "
                             "ppermute ring; shard over exactly one mesh "
                             f"axis (got {axes})")
        n_shards = partition_mod.shard_count(mesh, axes)
        # bucket slabs only when the bucketed path is on: the sharded rows
        # fall back to the ELL slab if a later with_buckets(True) finds a
        # partition without them (correct, just not SELL-balanced — reshard
        # to get harmonised buckets back)
        part = partition_mod.partition_rows(self._tiles_or_ell(),
                                            n_shards,
                                            with_buckets=self.use_buckets,
                                            max_buckets=max_buckets,
                                            balanced=balanced)
        part_t = None
        if self.has_transpose:
            part_t = partition_mod.partition_rows(
                self.tiles_t if self.tiles_t is not None else self.ell_t,
                n_shards,
                with_buckets=self.use_buckets, max_buckets=max_buckets,
                balanced=balanced)
        xplan = xplan_t = None
        if combine == "exchange":
            xplan = partition_mod.build_exchange_plan(part)
            if part_t is not None:
                xplan_t = partition_mod.build_exchange_plan(part_t)
        part = partition_mod.place(part, mesh, axes)
        if part_t is not None:
            part_t = partition_mod.place(part_t, mesh, axes)
        self._publish_partition_quality(part, part_t, n_shards)
        return dataclasses.replace(
            self, mesh=mesh, shard_axes=axes, partitioned=part,
            partitioned_t=part_t, comm=combine, xplan=xplan,
            xplan_t=xplan_t, transposed_cache=None)

    @staticmethod
    def _publish_partition_quality(part, part_t, n_shards: int) -> None:
        """Partition-quality gauges (ISSUE 10 satellite): one point per
        ``shard()`` call, labelled by orientation and shard count."""
        from repro.obs import metrics as obs_metrics
        if not obs_metrics.enabled():
            return
        reg = obs_metrics.get_registry()
        labels = ("orientation", "shards")
        bal = reg.gauge("partition_balance",
                        "max/mean per-shard tile load of the row partition",
                        labels)
        cut = reg.gauge("partition_edge_cut",
                        "fraction of tiles whose column block lives on "
                        "another shard", labels)
        for orient, p in (("forward", part), ("transpose", part_t)):
            if p is None:
                continue
            bal.set(p.balance(), orientation=orient, shards=n_shards)
            cut.set(p.edge_cut(), orientation=orient, shards=n_shards)

    def unshard(self) -> "GraphMatrix":
        """Back to single-device execution (drops the partition, keeps all
        single-device representations — they were never removed)."""
        return dataclasses.replace(
            self, mesh=None, shard_axes=None, partitioned=None,
            partitioned_t=None, comm="gather", xplan=None, xplan_t=None,
            transposed_cache=None)

    # -- packed-vector helpers ---------------------------------------------
    def pack(self, x: jax.Array) -> jax.Array:
        """Binarize + bit-pack a column-space vector (paper §IV, Listing 1).

        Returns raw uint32 words; ``BitVector.pack`` wraps the same layout
        in the typed operand the generic API consumes.
        """
        return pack_bitvector(x, self.tile_dim, self.n_cols)

    def pack_rows(self, x: jax.Array) -> jax.Array:
        """Binarize + bit-pack a row-space vector (output/frontier side)."""
        return pack_bitvector(x, self.tile_dim, self.n_rows)

    # -- the generic operations (DESIGN.md §10) -----------------------------
    def mxv(self, x, semiring: Optional[Semiring] = None,
            desc: Optional[Descriptor] = None, *, a_value: float = 1.0,
            out_dtype=None, out=None, mask=_UNSET, complement=_UNSET,
            row_chunk=_UNSET):
        """y = A ⊕.⊗ x — the generic matrix-vector product (paper Table II).

        The table row is resolved from the operand type and the semiring:

          dense ``x``           bin·full→full (any Table IV semiring;
                                SSSP / PageRank / CC)
          ``BitVector`` x,      bin·bin→bin — packed frontier traversal
          boolean semiring      (the BFS kernel); returns a ``BitVector``
          ``BitVector`` x,      bin·bin→full — neighbour counts
          other semiring        y_i = |N(i) ∩ frontier|

        ``semiring`` defaults to boolean for packed operands and arithmetic
        for dense ones. ``desc`` carries mask / complement / transpose /
        replace / row_chunk (``mask=``/``complement=``/``row_chunk=`` are
        accepted as one-off sugar); with ``desc.replace=False`` the
        masked-out output entries are taken from ``out``.
        """
        desc = descriptor_mod.merge_sugar(desc, mask, complement, row_chunk)
        if self.sharded:
            dispatch.reject_sharded_row_chunk("mxv", desc.row_chunk)
        if desc.transpose_a:
            return self.transposed().mxv(
                x, semiring, desc.replace_with(transpose_a=False),
                a_value=a_value, out_dtype=out_dtype, out=out)
        kind = operand_kind(x)
        if kind not in ("dense", "bitvec"):
            raise TypeError(f"mxv right-hand side must be a dense vector or "
                            f"BitVector, got {type(x).__name__}; use mxm "
                            f"for FrontierBatch/GraphMatrix operands")
        if kind == "bitvec":
            check_operand(x, self.tile_dim, self.n_cols, "x")
        semiring = semiring if semiring is not None else (
            BOOLEAN if kind == "bitvec" else ARITHMETIC)
        dispatch.check_semiring("mxv", kind, semiring)
        out_kind = dispatch.out_kind_for(semiring, kind)
        call = OpCall(
            semiring=semiring,
            mask=self._norm_mask(desc.mask, kind, out_kind),
            complement=desc.complement, row_chunk=desc.row_chunk,
            a_value=a_value,
            out_dtype=out_dtype if out_dtype is not None else jnp.float32)
        op = self._direction_op("mxv", desc, kind, "bitvec", out_kind,
                                call.mask is not None)
        impl = dispatch.resolve(op, kind, out_kind, self.backend,
                                self._bucketed(desc.row_chunk),
                                call.mask is not None, self.sharded)
        y = impl(self, x.words if kind == "bitvec" else x, call)
        if out_kind == "bin":
            y = BitVector.from_words(y, self.n_rows, self.tile_dim)
        return self._merge_unreplaced(y, desc, out, out_kind, call)

    def vxm(self, x, semiring: Optional[Semiring] = None,
            desc: Optional[Descriptor] = None, *, mask=_UNSET,
            complement=_UNSET, row_chunk=_UNSET, **kw):
        """xᵀ·A, pull direction (Table II via Aᵀ): ``mxv`` with the
        descriptor's input transpose — uses the stored transpose. Accepts
        the same ``mask=``/``complement=``/``row_chunk=`` sugar as mxv."""
        desc = descriptor_mod.merge_sugar(desc, mask, complement, row_chunk)
        return self.mxv(x, semiring, desc.replace_with(transpose_a=True),
                        **kw)

    def mxm(self, other=None, semiring: Optional[Semiring] = None,
            desc: Optional[Descriptor] = None, *, out=None,
            with_transpose: bool = True, out_dtype=None, mask=_UNSET,
            complement=_UNSET, row_chunk=_UNSET):
        """C⟨M⟩ = A ⊕.⊗ B — the generic matrix product (paper Table III).

        The table row is resolved from the operand type and the semiring:

          ``GraphMatrix`` B,    bin·bin→bin boolean SpGEMM; the packed
          boolean semiring      output grid is recompressed host-side into
                                a full ``GraphMatrix`` (``other`` defaults
                                to ``self``: A², 2-hop reachability)
          ``GraphMatrix`` B,    bin·bin→full count SpGEMM: dense
          other semiring        common-neighbour counts (TC / k-truss)
          dense matrix B        bin·full→full widened: Y = A @ X over
                                features (the GNN hot path)
          ``FrontierBatch`` B   bin·bin→bin widened: one traversal for S
                                packed frontiers (the engine/ hot path);
                                returns a ``FrontierBatch``
          ``BitMatrix`` B       bin·bin→full: popcount-accumulated dense
                                counts over packed binarized activations
                                (the fully-binarized BitGNN layer;
                                DESIGN.md §15) — arithmetic semiring only

        ``semiring`` defaults to boolean for packed/graph operands and
        arithmetic for dense ones. Masks are structural and applied right
        before the store (paper §V); ``desc.replace=False`` merges
        masked-out entries from ``out``.
        """
        desc = descriptor_mod.merge_sugar(desc, mask, complement, row_chunk)
        if self.sharded:
            dispatch.reject_sharded_row_chunk("mxm", desc.row_chunk)
        if desc.transpose_a:
            return self.transposed().mxm(
                other, semiring, desc.replace_with(transpose_a=False),
                out=out, with_transpose=with_transpose, out_dtype=out_dtype)
        other = self if other is None else other
        kind = operand_kind(other)
        if kind == "bitvec":
            raise TypeError("mxm right-hand side is a BitVector; use mxv "
                            "for packed vector operands")
        semiring = semiring if semiring is not None else (
            ARITHMETIC if kind in ("dense", "bitmat") else BOOLEAN)
        dispatch.check_semiring("mxm", kind, semiring)
        out_kind = dispatch.out_kind_for(semiring, kind)
        if kind == "graph":
            if self.n_cols != other.n_rows:
                raise ValueError(f"inner-dim mismatch: {self.n_cols} vs "
                                 f"{other.n_rows}")
            if self.backend != "csr" and self.tile_dim != other.tile_dim:
                raise ValueError(f"tile_dim mismatch: {self.tile_dim} vs "
                                 f"{other.tile_dim}")
        elif kind in ("frontier", "bitmat"):
            check_operand(other, self.tile_dim, self.n_cols, "B")
        norm_mask = self._norm_mask(desc.mask, kind, out_kind, other=other)
        if (kind in ("dense", "bitmat") and norm_mask is not None
                and norm_mask.ndim == 1):
            # a vector mask over the [n_rows, d] feature output masks rows
            norm_mask = norm_mask[:, None]
        call = OpCall(
            semiring=semiring, mask=norm_mask,
            complement=desc.complement, row_chunk=desc.row_chunk,
            out_dtype=out_dtype)
        op = self._direction_op("mxm", desc, kind, "frontier", out_kind,
                                call.mask is not None)
        impl = dispatch.resolve(op, kind, out_kind, self.backend,
                                self._bucketed(desc.row_chunk),
                                call.mask is not None, self.sharded)
        y = impl(self, other.words if kind in ("frontier", "bitmat")
                 else other, call)
        if kind == "graph" and out_kind == "bin":
            return self._grid_to_graph(y, other, desc, out, with_transpose)
        if kind == "frontier":
            y = FrontierBatch.from_words(y, self.n_rows, other.n_sources,
                                         self.tile_dim)
        return self._merge_unreplaced(y, desc, out, out_kind, call)

    def tri_count(self, row_chunk: Optional[int] = None) -> jax.Array:
        """Σ (L·Lᵀ ⊙ L) where L = strict lower triangle of this matrix.

        The fused masked reduction (paper §V, Listing 2 — Azad-Buluç as in
        GraphBLAST), dispatched as the ``mxm_sum`` registry op: the b2sr
        backend runs the masked count SpGEMM + sum, the Pallas backend the
        fully-fused BMM kernel, the CSR baseline a dense masked matmul.
        The L / Lᵀ operand pair is built once and memoized
        (:class:`LowerTriangle`, the ``degrees_cache`` pattern).
        """
        if self.sharded:
            dispatch.reject_sharded_row_chunk("mxm_sum", row_chunk)
        if self.tri_cache is None:
            self.tri_cache = LowerTriangle(self.csr, self.tile_dim,
                                           self.n_rows)
        call = OpCall(semiring=ARITHMETIC, row_chunk=row_chunk)
        impl = dispatch.resolve("mxm_sum", "tri", "full", self.backend,
                                self._bucketed(row_chunk), True,
                                self.sharded)
        return impl(self, self.tri_cache, call)

    # -- generic-layer helpers ---------------------------------------------
    @staticmethod
    def _direction_op(base: str, desc: Descriptor, kind: str,
                      pull_kind: str, out_kind: str, masked: bool) -> str:
        """Resolve ``desc.direction`` to the registry op name.

        ``direction="pull"`` selects the fused pull row (DESIGN.md §12),
        which exists only for the masked packed traversal — the
        bin·bin→bin ``pull_kind`` operand with a §V visited mask. Any
        other shape has no pull semantics and is rejected here so a typo
        never silently runs push.
        """
        if desc.direction is None:
            return base
        if desc.direction != "pull":
            raise ValueError(f"unknown descriptor direction "
                             f"{desc.direction!r}; expected None or 'pull'")
        if kind != pull_kind or out_kind != "bin" or not masked:
            raise ValueError(
                f"direction='pull' applies only to the masked packed "
                f"traversal row ({base} over a {pull_kind} operand on the "
                f"boolean semiring with a visited mask); got rhs={kind} "
                f"out={out_kind} masked={masked}")
        return base + "_pull"

    def _norm_mask(self, mask, rhs_kind: str, out_kind: str,
                   other: Optional["GraphMatrix"] = None):
        """Validate the descriptor mask and strip it to the row's raw form.

        Packed outputs take packed masks (words), SpGEMM takes a structural
        ``GraphMatrix`` mask, dense outputs take dense masks (a
        ``BitVector`` is unpacked as a convenience).
        """
        if mask is None:
            return None
        if rhs_kind == "graph":
            if operand_kind(mask) != "graph":
                raise TypeError("mxm over GraphMatrix operands takes a "
                                "structural GraphMatrix mask")
            if (mask.n_rows != self.n_rows
                    or mask.n_cols != other.n_cols):
                raise ValueError("mask shape must match the output")
            if (out_kind == "bin" and self.backend != "csr"
                    and mask.tile_dim != self.tile_dim):
                raise ValueError(f"mask tile_dim mismatch: {mask.tile_dim} "
                                 f"vs {self.tile_dim}")
            return mask
        if out_kind == "bin":
            if rhs_kind == "bitvec":
                if not isinstance(mask, BitVector):
                    raise TypeError("packed mxv takes a BitVector mask")
                check_operand(mask, self.tile_dim, self.n_rows, "mask")
            else:  # frontier
                if not isinstance(mask, FrontierBatch):
                    raise TypeError("frontier mxm takes a FrontierBatch mask")
                check_operand(mask, self.tile_dim, self.n_rows, "mask")
            return mask.words
        if isinstance(mask, BitVector):
            check_operand(mask, self.tile_dim, self.n_rows, "mask")
            return mask.unpack(jnp.bool_)
        return mask

    def _merge_unreplaced(self, y, desc: Descriptor, out, out_kind: str,
                          call: OpCall):
        """Apply ``desc.replace=False``: masked-out entries come from ``out``.

        With ``replace=True`` (the default, the paper's mask-at-store) the
        registered impl already stored the ⊕-identity there and ``y`` is
        returned as-is.
        """
        if desc.replace or desc.mask is None:
            return y
        if out is None:
            raise ValueError("desc.replace=False needs the previous output "
                             "(out=) to merge masked-out entries from")
        if out_kind == "bin":
            m = call.mask if not desc.complement else ~call.mask
            merged = (y.words & m) | (out.words & ~m)
            return y._like(merged)
        keep = ((call.mask == 0) if desc.complement else (call.mask != 0))
        return jnp.where(keep, y, out)

    def _grid_to_graph(self, grid, other: "GraphMatrix", desc: Descriptor,
                       out, with_transpose: bool) -> "GraphMatrix":
        """Recompress a packed SpGEMM output grid into a ``GraphMatrix``."""
        if not desc.replace and desc.mask is not None:
            if out is None:
                raise ValueError("desc.replace=False needs the previous "
                                 "output (out=) to merge masked-out entries "
                                 "from")
            mg = ell_to_packed_grid(desc.mask.ell)
            m = ~mg if desc.complement else mg
            grid = (jnp.asarray(grid) & m) | (ell_to_packed_grid(out.ell) & ~m)
        mat = b2sr_mod.packed_grid_to_b2sr(np.asarray(grid), self.n_rows,
                                           other.n_cols)
        return GraphMatrix.from_b2sr(mat, with_transpose=with_transpose,
                                     backend=self.backend)

    # -- legacy per-row method names (deprecation shims) --------------------
    def mxv_bool(self, x_packed: jax.Array,
                 mask_packed: Optional[jax.Array] = None,
                 complement: bool = True,
                 row_chunk: Optional[int] = None) -> jax.Array:
        """Deprecated: ``mxv`` with a ``BitVector`` operand (boolean row)."""
        warn_deprecated("mxv_bool", "mxv(BitVector, desc=Descriptor(...))")
        m = (None if mask_packed is None else
             BitVector.from_words(mask_packed, self.n_rows, self.tile_dim))
        y = self.mxv(BitVector.from_words(x_packed, self.n_cols,
                                          self.tile_dim),
                     BOOLEAN, Descriptor(mask=m, complement=complement,
                                         row_chunk=row_chunk))
        return y.words

    def mxv_count(self, x_packed: jax.Array, out_dtype=jnp.float32,
                  row_chunk: Optional[int] = None) -> jax.Array:
        """Deprecated: ``mxv`` with a ``BitVector`` operand on arithmetic."""
        warn_deprecated("mxv_count",
                        "mxv(BitVector, ARITHMETIC, out_dtype=...)")
        return self.mxv(BitVector.from_words(x_packed, self.n_cols,
                                             self.tile_dim),
                        ARITHMETIC, Descriptor(row_chunk=row_chunk),
                        out_dtype=out_dtype)

    def spmm(self, x: jax.Array,
             row_chunk: Optional[int] = None) -> jax.Array:
        """Deprecated: ``mxm`` with a dense feature-matrix operand."""
        warn_deprecated("spmm", "mxm(X)")
        return self.mxm(x, ARITHMETIC, Descriptor(row_chunk=row_chunk))

    def spmm_bool(self, f_packed: jax.Array,
                  mask_packed: Optional[jax.Array] = None,
                  complement: bool = True,
                  row_chunk: Optional[int] = None) -> jax.Array:
        """Deprecated: ``mxm`` with a ``FrontierBatch`` operand."""
        warn_deprecated("spmm_bool",
                        "mxm(FrontierBatch, desc=Descriptor(...))")
        s_pad = int(f_packed.shape[2]) * b2sr_mod.SOURCE_WORD_BITS
        m = (None if mask_packed is None else
             FrontierBatch.from_words(mask_packed, self.n_rows, s_pad,
                                      self.tile_dim))
        y = self.mxm(FrontierBatch.from_words(f_packed, self.n_cols, s_pad,
                                              self.tile_dim),
                     BOOLEAN, Descriptor(mask=m, complement=complement,
                                         row_chunk=row_chunk))
        return y.words

    def mxm_count(self, other: Optional["GraphMatrix"] = None,
                  mask: Optional["GraphMatrix"] = None,
                  complement: bool = False,
                  row_chunk: Optional[int] = None) -> jax.Array:
        """Deprecated: ``mxm`` with a GraphMatrix operand on arithmetic."""
        warn_deprecated("mxm_count", "mxm(B, ARITHMETIC, desc=...)")
        return self.mxm(other, ARITHMETIC,
                        Descriptor(mask=mask, complement=complement,
                                   row_chunk=row_chunk))

    # -- batched query entry points (dispatch through engine/) ---------------
    def msbfs(self, sources: Sequence[int], max_iters: Optional[int] = None,
              direction=None):
        """Multi-source BFS: per-source hop levels ``int32[n, S]``.

        One wide frontier-matrix traversal for the whole batch (engine/
        queries, plan-cached) — column ``s`` is bit-exact against
        ``algorithms.bfs(g, sources[s])`` for every ``direction`` mode
        (``"push"``/``"pull"``/``"auto"``; default auto).
        """
        from repro.engine import queries
        return queries.msbfs(self, sources, max_iters=max_iters,
                             direction=direction)

    def ppr(self, seeds: Sequence[int], alpha: float = 0.85,
            max_iters: int = 10, eps: float = 1e-9):
        """Batched personalized PageRank: per-seed ranks ``f32[n, S]``."""
        from repro.engine import queries
        return queries.batched_ppr(self, seeds, alpha=alpha,
                                   max_iters=max_iters, eps=eps)

    # -- storage -------------------------------------------------------------
    def degrees(self) -> jax.Array:
        """Out-degree vector from the CSR twin (row_ptr diff); memoized."""
        if self.degrees_cache is None:
            ptr = self.csr.row_ptr
            self.degrees_cache = (ptr[1:] - ptr[:-1]).astype(jnp.float32)
        return self.degrees_cache

    def loop_plan(self, kind: str, opts: Tuple,
                  build: Callable[[], Callable]) -> Callable:
        """This graph's compiled whole-graph loop ``kind`` (``"bfs"``,
        ``"pagerank"``) for the static options ``opts``, made by ``build``
        on the first call and reused by every later one.

        The key also holds the layout the traced loop bakes in (backend,
        bucketing, tile dim, mesh and comm). Lookups count as
        ``plan_cache_{hits,misses}_total{kind,backend}``, the series the
        engine's plan cache emits for its batched kinds.
        """
        mesh = (partition_mod.mesh_fingerprint(self.mesh, self.shard_axes)
                if self.sharded else None)
        key = (kind, opts, self.backend, self.use_buckets, self.tile_dim,
               mesh, self.comm)
        plan = self.loop_plans.get(key)
        what = "misses" if plan is None else "hits"
        if plan is None:
            plan = self.loop_plans[key] = _LoopPlan(build())
        from repro.obs import metrics as obs_metrics
        if obs_metrics.enabled():
            obs_metrics.get_registry().counter(
                f"plan_cache_{what}_total", f"plan cache {what}",
                ("kind", "backend")).inc(kind=kind, backend=self.backend)
        return plan

    def fingerprint(self) -> str:
        """Content hash of the graph structure (the plan-cache key component).

        Hashes the tile CSR (row pointer, tile columns, bit tiles) once per
        instance (memoized; backend/bucket toggles keep it — they are
        separate plan-key fields). A graph wrapped around an ELL view hashes
        that view instead.
        """
        if self.fingerprint_cache is None:
            h = hashlib.blake2b(digest_size=16)
            h.update(f"{self.n_rows}:{self.n_cols}:{self.nnz}:"
                     f"{self.tile_dim}".encode())
            src = self.tiles if self.tiles is not None else self.ell
            arrays = ((src.tile_row_ptr, src.tile_col_idx, src.bit_tiles)
                      if self.tiles is not None
                      else (src.tile_col_idx, src.bit_tiles))
            for a in arrays:
                h.update(np.ascontiguousarray(a).tobytes())
            self.fingerprint_cache = h.hexdigest()
        return self.fingerprint_cache
