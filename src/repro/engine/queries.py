"""Batched multi-source graph queries over packed frontier matrices.

The single-source algorithms in ``repro.algorithms`` pay one full matrix
sweep per query. Here a batch of S queries shares every sweep: frontiers
live in one bit-packed :class:`~repro.core.operands.FrontierBatch`
(``uint32[tiles, t, W]`` with 32 sources per word) and each iteration is
one generic ``GraphMatrix.mxm`` launch — the FrontierBatch operand selects
the multi-frontier Table row, and A's tiles stream once for the whole
batch. Every query loop is compiled once per (graph, kernel, batch width,
descriptor) and cached by ``engine.planner``. A sharded graph
(``GraphMatrix.shard(mesh)``) routes every iteration through the
shard_map rows — one mesh serves the whole batch per sweep — and the plan
key carries the mesh fingerprint, so plans never leak across mesh shapes
(DESIGN.md §11).

Parity contracts (pinned by tests/test_engine.py):
  - ``msbfs`` / ``mskhop`` / ``ms_sssp`` column ``s`` is **bit-exact**
    against the single-source run on ``sources[s]`` (boolean ops are
    order-insensitive).
  - ``batched_ppr`` column ``s`` is **allclose** against
    ``algorithms.pagerank.ppr`` (the batched spmm sums features in a
    different float order than the scanned bmv).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.algorithms import direction as direction_mod
from repro.algorithms.bfs import _check_max_iters
from repro.algorithms.direction import DirectionConfig
from repro.core.b2sr import (SOURCE_WORD_BITS, ceil_div,
                             unpack_frontier_matrix)
from repro.core.descriptor import Descriptor
from repro.core.graphblas import GraphMatrix
from repro.core.operands import FrontierBatch
from repro.engine import planner as planner_mod
from repro.engine.planner import PlanCache, descriptor_key, plan_key

#: The descriptor every masked-traversal loop bakes into its trace: the
#: per-source visited sets as a complement mask (loop-carried, so mask
#: presence is pinned via ``masked=True`` at key time).
_TRAVERSAL_DESC = descriptor_key(Descriptor(complement=True), masked=True)


def _traversal_desc(cfg: DirectionConfig):
    """Plan-key descriptor component for a direction-switching loop.

    The msbfs loop's Descriptor direction is *loop-internal* (each
    iteration picks push or pull), so the baked-in policy — mode and
    thresholds — must reach the key here; two configs are two XLA
    programs and must never share a cached plan.
    """
    return _TRAVERSAL_DESC + (cfg.mode, cfg.alpha, cfg.beta)


@dataclasses.dataclass
class MSBFSResult:
    levels: jax.Array        # int32[n, S]; -1 = unreachable from sources[s]
    n_iterations: int        # max over the batch (columns finish together)
    directions: tuple = ()   # per-iteration direction used (whole batch)


@dataclasses.dataclass
class MSSSSPResult:
    distances: jax.Array     # float32[n, S]; +inf = unreachable
    n_iterations: int


@dataclasses.dataclass
class BatchedPPRResult:
    ranks: jax.Array         # float32[n, S]; column s = PPR from seeds[s]
    n_iterations: int


@dataclasses.dataclass
class GNNInferResult:
    logits: jax.Array        # float32[n_classes, S]; column s = node sources[s]
    n_layers: int


def _check_sources(sources, n: int) -> np.ndarray:
    src = np.asarray(sources, dtype=np.int64).reshape(-1)
    if src.size == 0:
        raise ValueError("need at least one source")
    bad = src[(src < 0) | (src >= n)]
    if bad.size:
        shown = ", ".join(str(b) for b in bad[:5])
        more = ", ..." if bad.size > 5 else ""
        raise ValueError(
            f"source id(s) {shown}{more} out of range for a graph with "
            f"{n} nodes (valid ids are 0..{n - 1})")
    return src


def _padded_width(n_sources: int) -> int:
    return ceil_div(n_sources, SOURCE_WORD_BITS) * SOURCE_WORD_BITS


def _one_hot_frontier(g: GraphMatrix, src: np.ndarray,
                      s_pad: int) -> FrontierBatch:
    """Packed one-hot frontier matrix [tiles, t, W] for a source batch.

    Built directly in the packed layout — S word-writes instead of
    materialising (and shipping) the dense ``[n, s_pad]`` matrix that
    ``FrontierBatch.pack`` would consume (hot on the serving path).
    """
    t = g.tile_dim
    words = np.zeros((ceil_div(g.n_rows, t), t, s_pad // SOURCE_WORD_BITS),
                     np.uint32)
    idx = np.arange(src.size)
    np.bitwise_or.at(
        words, (src // t, src % t, idx // SOURCE_WORD_BITS),
        np.uint32(1) << (idx % SOURCE_WORD_BITS).astype(np.uint32))
    return FrontierBatch.from_words(jnp.asarray(words), g.n_rows, s_pad, t)


def _planner(planner: Optional[PlanCache]) -> PlanCache:
    return planner_mod.DEFAULT_PLANNER if planner is None else planner


# ---------------------------------------------------------------------------
# multi-source BFS: per-source depth via iteration-stamped updates
# ---------------------------------------------------------------------------

def _build_msbfs_plan(g: GraphMatrix, cfg: DirectionConfig):
    gt = g.transposed()
    n = g.n_rows
    avg_degree = g.nnz / max(n, 1)

    def step_push(f, v):
        # FrontierBatch operand -> the multi-frontier bin·bin→bin mxm
        # row, with the per-source visited sets as the §V mask
        return gt.mxm(f, desc=Descriptor(mask=v, complement=True))

    def step_pull(f, v):
        return gt.mxm(f, desc=Descriptor(mask=v, complement=True,
                                         direction="pull"))

    def loop(f0, levels0, max_iters, n_active):
        def cond(state):
            frontier, _, _, it, _, _, _ = state
            return frontier.any() & (it < max_iters)

        def body(state):
            frontier, visited, levels, it, d, locked, trace = state
            if cfg.mode == "auto":
                nxt = jax.lax.cond(d == direction_mod.PULL, step_pull,
                                   step_push, frontier, visited)
            elif cfg.mode == "pull":
                nxt = step_pull(frontier, visited)
            else:
                nxt = step_push(frontier, visited)
            new_bits = unpack_frontier_matrix(nxt.words, n, levels.shape[1],
                                              jnp.bool_)
            levels = jnp.where(new_bits & (levels < 0), it + 1, levels)
            new_visited = visited | nxt
            trace = direction_mod.record(trace, it, d)
            # n_active (not the padded width) scales the summed counts to
            # per-query magnitudes: padded columns are all-zero and would
            # dilute the density estimate; traced so one cached plan
            # serves every batch size sharing this padded width
            d_next, locked = direction_mod.next_direction(
                cfg, d, locked, direction_mod.nnz_words(nxt.words),
                direction_mod.nnz_words(new_visited.words), n, avg_degree,
                batch=n_active)
            return (nxt, new_visited, levels, it + 1, d_next, locked,
                    trace)

        state = (f0, f0, levels0, jnp.int32(0),
                 direction_mod.initial_direction(cfg), jnp.bool_(False),
                 direction_mod.empty_trace(n))
        _, _, levels, it, _, _, trace = jax.lax.while_loop(cond, body,
                                                           state)
        return levels, it, trace

    return planner_mod.HoistedJit(loop)


def msbfs(g: GraphMatrix, sources: Sequence[int],
          max_iters: Optional[int] = None,
          planner: Optional[PlanCache] = None,
          direction=None) -> MSBFSResult:
    """Hop levels from every source in one batched traversal.

    Column ``s`` of ``levels`` is bit-exact against
    ``algorithms.bfs(g, sources[s]).levels`` for every ``direction``
    mode; the whole batch switches direction together (one shared sweep
    per iteration is the point of batching), steered by the summed
    density scaled back to per-query magnitudes. ``direction=None``
    defaults to auto switching to match ``bfs``.
    """
    cfg = (direction_mod.as_config(direction) if direction is not None
           else DirectionConfig(mode="auto"))
    n = g.n_rows
    src = _check_sources(sources, n)
    max_iters = _check_max_iters(max_iters, n)
    s_pad = _padded_width(src.size)
    plan = _planner(planner).get(plan_key(g, "msbfs", s_pad,
                                          desc=_traversal_desc(cfg)),
                                 lambda: _build_msbfs_plan(g, cfg))
    f0 = _one_hot_frontier(g, src, s_pad)
    levels0 = jnp.asarray(_stamp_zero(n, s_pad, src))
    levels, it, trace = plan(f0, levels0, jnp.int32(max_iters),
                             jnp.float32(src.size))
    it = int(it)
    dirs = direction_mod.trace_tuple(trace, it)
    direction_mod.observe_trace(dirs, kernel="msbfs")
    return MSBFSResult(levels=levels[:, : src.size], n_iterations=it,
                       directions=dirs)


def _stamp_zero(n: int, s_pad: int, src: np.ndarray) -> np.ndarray:
    lv = np.full((n, s_pad), -1, np.int32)
    lv[src, np.arange(src.size)] = 0
    return lv


# ---------------------------------------------------------------------------
# multi-source k-hop neighborhoods
# ---------------------------------------------------------------------------

def _build_mskhop_plan(g: GraphMatrix):
    gt = g.transposed()

    def loop(f0, k):
        def body(_, state):
            frontier, visited = state
            nxt = gt.mxm(frontier, desc=Descriptor(mask=visited,
                                                   complement=True))
            return nxt, visited | nxt

        _, visited = jax.lax.fori_loop(0, k, body, (f0, f0))
        return visited & ~f0              # exclude the sources themselves

    return planner_mod.HoistedJit(loop)


def mskhop(g: GraphMatrix, sources: Sequence[int], k: int,
           planner: Optional[PlanCache] = None) -> jax.Array:
    """<=k-hop neighborhoods of every source, as ``bool[n, S]``.

    Column ``s`` is bit-exact against
    ``algorithms.khop_frontier(g, sources[s], k)``.
    """
    if k < 1:
        raise ValueError("k must be >= 1")
    n = g.n_rows
    src = _check_sources(sources, n)
    s_pad = _padded_width(src.size)
    plan = _planner(planner).get(plan_key(g, "mskhop", s_pad,
                                          desc=_TRAVERSAL_DESC),
                                 lambda: _build_mskhop_plan(g))
    reached = plan(_one_hot_frontier(g, src, s_pad), jnp.int32(k))
    return unpack_frontier_matrix(reached.words, n, src.size, jnp.bool_)


# ---------------------------------------------------------------------------
# multi-source SSSP (uniform edge weight — hop distances × weight)
# ---------------------------------------------------------------------------

def ms_sssp(g: GraphMatrix, sources: Sequence[int], edge_weight: float = 1.0,
            max_iters: Optional[int] = None,
            planner: Optional[PlanCache] = None,
            direction=None) -> MSSSSPResult:
    """Batched SSSP on the binary adjacency: ``levels × edge_weight``.

    B2SR edges are unweighted, so min-plus distances are hop counts scaled
    by the uniform weight — one msbfs serves the whole batch. Matches the
    looped ``algorithms.sssp`` exactly for dyadic weights (1.0, 0.5, 2.0,
    ...), where k repeated float adds equal ``k * w``.
    """
    res = msbfs(g, sources, max_iters=max_iters, planner=planner,
                direction=direction)
    dist = jnp.where(res.levels >= 0,
                     res.levels.astype(jnp.float32) * edge_weight, jnp.inf)
    return MSSSSPResult(distances=dist, n_iterations=res.n_iterations)


# ---------------------------------------------------------------------------
# batched personalized PageRank (arithmetic semiring, per-column restarts)
# ---------------------------------------------------------------------------

def _build_ppr_plan(g: GraphMatrix):
    gt = g.transposed()
    out_deg = g.degrees()
    dangling = out_deg == 0
    safe_deg = jnp.where(dangling, 1.0, out_deg)

    def loop(restart, alpha, eps, max_iters):
        def cond(state):
            _, delta, it = state
            return (delta > eps) & (it < max_iters)

        def body(state):
            pr, _, it = state
            scaled = pr / safe_deg[:, None]           # out-degree division
            contrib = gt.mxm(scaled)                  # [n, S] multi-vector
            dangle = jnp.sum(jnp.where(dangling[:, None], pr, 0.0), axis=0)
            new = alpha * contrib + (alpha * dangle[None, :]
                                     + (1.0 - alpha)) * restart
            delta = jnp.max(jnp.sum(jnp.abs(new - pr), axis=0))
            return new, delta, it + 1

        pr, _, it = jax.lax.while_loop(
            cond, body, (restart, jnp.float32(jnp.inf), jnp.int32(0)))
        return pr, it

    return planner_mod.HoistedJit(loop)


# ---------------------------------------------------------------------------
# batched GNN inference (BitGNN forward on the bit path, DESIGN.md §15)
# ---------------------------------------------------------------------------

#: Served models by name: weights + input features + the bit-path flag.
#: Names (not arrays) travel in the query params, so groups coalesce and
#: warmup recipes stay JSON-serialisable; re-register after a restart.
_GNN_MODELS: dict = {}


@dataclasses.dataclass
class GNNModel:
    """A registered inference model: per-layer (W, b) + node features.

    ``binarize=True`` routes every hidden layer's aggregation through the
    packed bin·bin→full row — activations are sign-binarized, packed to
    :class:`~repro.core.operands.BitMatrix` words, and aggregated as
    α·(2·popcount − rowsum) (``repro.gnn_bit``); the input layer always
    aggregates dense (float features). ``version`` feeds the plan key so
    re-registering a name never serves a stale compiled forward.
    """

    name: str
    params: tuple            # ((w, b), ...) per layer
    features: jax.Array      # float[n, d_in]
    binarize: bool = True
    version: int = 0


def register_gnn_model(name: str, params, features,
                       binarize: bool = True) -> GNNModel:
    """Register (or replace) a model for ``gnn_infer`` serving."""
    prev = _GNN_MODELS.get(name)
    model = GNNModel(
        name=name,
        params=tuple((jnp.asarray(w), jnp.asarray(b)) for w, b in params),
        features=jnp.asarray(features), binarize=binarize,
        version=prev.version + 1 if prev is not None else 0)
    _GNN_MODELS[name] = model
    return model


def _gnn_model(name: str) -> GNNModel:
    m = _GNN_MODELS.get(name)
    if m is None:
        raise ValueError(
            f"no GNN model registered under {name!r}; call "
            f"engine.queries.register_gnn_model first "
            f"(registered: {sorted(_GNN_MODELS) or 'none'})")
    return m


def _build_gnn_plan(g: GraphMatrix, model: GNNModel):
    from repro.gnn_bit import binarize as binarize_mod

    rowsum = g.degrees().astype(jnp.float32)      # A's row-sums (neighbors)
    params = model.params
    n_last = len(params) - 1

    def fwd(idx):
        h = model.features
        for li, (w, b) in enumerate(params):
            if model.binarize and li > 0:
                # hidden layers ride the packed path: sign-binarize, pack,
                # one bin·bin→full mxm, α·popcount reconstruction — the
                # adjacency *and* the activations stay bit-packed
                alpha = binarize_mod.alpha_scale(h)
                bm = binarize_mod.pack_activations(h, g.tile_dim)
                counts = g.mxm(bm)
                agg = alpha[None, :] * (2.0 * counts - rowsum[:, None]) + h
            else:
                agg = g.mxm(h) + h                # dense row + self loop
            h = agg @ w + b
            if li < n_last:
                h = jax.nn.relu(h)
        return h[idx].T                           # [n_classes, s_pad]

    return planner_mod.HoistedJit(fwd)


def gnn_infer(g: GraphMatrix, sources: Sequence[int], model: str,
              planner: Optional[PlanCache] = None) -> GNNInferResult:
    """Class scores for a batch of nodes through one full-graph forward.

    One compiled plan per (graph, model version, padded width) serves every
    batch: the forward computes logits for all nodes (the aggregation
    launches are shared — that is the batching win) and gathers the
    requested rows. Column ``s`` of ``logits`` belongs to ``sources[s]``.
    The model's hidden aggregations run on the packed bit path when it was
    registered with ``binarize=True``; every mxm row involved exists on all
    three backends, so the serving fallback chain applies unchanged.
    """
    m = _gnn_model(model)
    n = g.n_rows
    if int(m.features.shape[0]) != n:
        raise ValueError(
            f"model {model!r} features cover {int(m.features.shape[0])} "
            f"nodes but the graph has {n}")
    src = _check_sources(sources, n)
    s_pad = _padded_width(src.size)
    padded = np.concatenate(
        [src, np.full(s_pad - src.size, src[0], np.int64)])
    plan = _planner(planner).get(
        plan_key(g, "gnn_infer", s_pad,
                 desc=("gnn", m.name, m.version, m.binarize)),
        lambda: _build_gnn_plan(g, m))
    logits = plan(jnp.asarray(padded, jnp.int32))
    return GNNInferResult(logits=logits[:, : src.size],
                          n_layers=len(m.params))


def batched_ppr(g: GraphMatrix,
                seeds: Union[Sequence[int], jax.Array, np.ndarray],
                alpha: float = 0.85, max_iters: int = 10, eps: float = 1e-9,
                planner: Optional[PlanCache] = None) -> BatchedPPRResult:
    """Personalized PageRank for S seeds in one multi-vector iteration.

    ``seeds`` is either an int array ``[S]`` (one-hot restarts) or a dense
    restart matrix ``[n, S]`` (per-column restart distributions). Dangling
    mass restarts into each column's own distribution — the same update as
    ``algorithms.pagerank.ppr``, so column ``s`` is allclose against the
    single-seed run. Stops when the worst column's L1 delta is <= ``eps``
    (a batch iterates until its slowest member converges).
    """
    n = g.n_rows
    seeds_arr = np.asarray(seeds)
    if seeds_arr.ndim == 2:
        if seeds_arr.shape[0] != n:
            raise ValueError(f"restart matrix must be [n={n}, S]")
        s = seeds_arr.shape[1]
        s_pad = _padded_width(s)
        restart = np.zeros((n, s_pad), np.float32)
        restart[:, :s] = seeds_arr
    else:
        src = _check_sources(seeds_arr, n)
        s = src.size
        s_pad = _padded_width(s)
        restart = np.zeros((n, s_pad), np.float32)
        restart[src, np.arange(s)] = 1.0
    plan = _planner(planner).get(plan_key(g, "ppr", s_pad),
                                 lambda: _build_ppr_plan(g))
    ranks, it = plan(jnp.asarray(restart), jnp.float32(alpha),
                     jnp.float32(eps), jnp.int32(max_iters))
    return BatchedPPRResult(ranks=ranks[:, :s], n_iterations=int(it))
