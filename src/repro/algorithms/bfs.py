"""BFS on the boolean semiring with bit-packed frontiers (paper §V).

Each iteration performs one-degree edge traversal with the visited mask
applied right before the output store (§V). The traversal is
*direction-optimizing* (DESIGN.md §12): push iterations run the classic
masked bin·bin→bin mxv (mask AND at the end — no divergence-like
predication on TPU); pull iterations dispatch the fused ``mxv_pull`` row,
whose Pallas kernel early-exits each output row on the first set allowed
bit. ``repro.algorithms.direction`` decides per iteration from popcount
density estimates; the choice is loop-carried traced state, so the whole
switching traversal stays one compiled ``while_loop``.

The frontier, visited set, and mask are bit-packed uint32 words end-to-end
on the b2sr backends; levels are materialised incrementally in an int32
vector.

The single-source loop is compiled once per graph and option set
(``direction`` config and ``row_chunk``) and kept on the graph
(``GraphMatrix.loop_plan``): ``source`` and ``max_iters`` are traced
``int32`` arguments, so later calls dispatch the resident executable
without tracing or lowering anything.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.algorithms import direction as direction_mod
from repro.algorithms.direction import DirectionConfig
from repro.core.descriptor import Descriptor
from repro.core.graphblas import GraphMatrix
from repro.core.operands import BitVector


@dataclasses.dataclass
class BFSResult:
    """Result of a single-source traversal.

    ``levels`` is always ``int32[n]`` with ``levels[source] == 0`` and -1
    for unreachable vertices — including the ``max_iters=0`` case, which
    returns the 0-iteration shape: only the source stamped, zero
    iterations, empty ``directions``. ``directions`` records the
    direction *used* by each executed iteration (``"push"``/``"pull"``),
    so callers can observe which path the heuristic picked.
    """

    levels: jax.Array      # int32[n]; -1 = unreachable
    n_iterations: int
    directions: Tuple[str, ...] = ()


def _check_max_iters(max_iters: Optional[int], n: int) -> int:
    """Shared single-source/batched validation (both paths, same rules)."""
    if max_iters is None:
        return n
    max_iters = int(max_iters)
    if max_iters < 0:
        raise ValueError(f"max_iters must be >= 0, got {max_iters}")
    return max_iters


def bfs(g: GraphMatrix, source, max_iters: Optional[int] = None,
        row_chunk: Optional[int] = None,
        direction: Union[str, DirectionConfig, None] = "auto"):
    """Hop levels from ``source`` following out-edges.

    ``direction`` is ``"auto"`` (default: Beamer-style push/pull
    switching), ``"push"``, ``"pull"``, or a
    :class:`~repro.algorithms.direction.DirectionConfig` with explicit
    thresholds. All modes are bit-exact; the chosen schedule is recorded
    on ``BFSResult.directions``.

    ``source`` may also be an *array* of sources: the batch routes through
    the multi-source engine (one wide frontier-matrix traversal, plan-
    cached) and returns its ``MSBFSResult`` with ``levels[n, S]`` — column
    ``s`` bit-exact against the single-source run on ``source[s]``.
    """
    cfg = direction_mod.as_config(direction)
    n = g.n_rows
    max_iters = _check_max_iters(max_iters, n)
    if np.ndim(source) > 0:
        if row_chunk is not None:
            raise ValueError("row_chunk is not supported for batched "
                             "sources (the engine plans its own loop)")
        from repro.engine.queries import msbfs
        return msbfs(g, source, max_iters=max_iters, direction=cfg)
    source = int(source)
    plan = g.loop_plan("bfs", (cfg, row_chunk),
                       lambda: _build_bfs_plan(g, cfg, row_chunk))
    levels, it, trace = plan(np.int32(source), np.int32(max_iters))
    it = int(it)
    dirs = direction_mod.trace_tuple(trace, it)
    direction_mod.observe_trace(dirs, kernel="bfs")
    return BFSResult(levels=levels, n_iterations=it, directions=dirs)


def _build_bfs_plan(g: GraphMatrix, cfg: DirectionConfig,
                    row_chunk: Optional[int]):
    """The single-source traversal as one program of traced
    ``(source, max_iters)``; ``cfg`` and ``row_chunk`` are baked in."""
    from repro.engine.planner import HoistedJit
    n = g.n_rows
    t = g.tile_dim
    # both directions traverse Aᵀ · frontier over the stored transpose;
    # push/pull differ in schedule (and kernel), never in the operand
    gt = g.transposed()
    avg_degree = g.nnz / max(n, 1)

    def step_push(f, v):
        return gt.mxv(f, desc=Descriptor(mask=v, complement=True,
                                         row_chunk=row_chunk))

    def step_pull(f, v):
        return gt.mxv(f, desc=Descriptor(mask=v, complement=True,
                                         row_chunk=row_chunk,
                                         direction="pull"))

    def loop(source, max_iters):
        src = jnp.zeros(n, jnp.float32).at[source].set(1.0)
        frontier = BitVector.pack(src, t, n)
        levels = jnp.full(n, -1, jnp.int32).at[source].set(0)

        def cond(state):
            # NOT jnp.sum(frontier.astype(uint64)): without x64 that
            # silently downcasts to uint32 and the word sum can wrap to
            # exactly zero, terminating BFS with a live frontier. any() is
            # also cheaper.
            frontier, _, _, it, _, _, _ = state
            return frontier.any() & (it < max_iters)

        def body(state):
            frontier, visited, levels, it, d, locked, trace = state
            if cfg.mode == "auto":
                # direction is loop-carried traced state — both branches
                # are compiled once, the switch costs one predicate per
                # iteration
                nxt = jax.lax.cond(d == direction_mod.PULL, step_pull,
                                   step_push, frontier, visited)
            elif cfg.mode == "pull":
                nxt = step_pull(frontier, visited)
            else:
                nxt = step_push(frontier, visited)
            new_visited = visited | nxt
            new_bits = nxt.unpack(jnp.int32)
            levels_new = jnp.where((new_bits > 0) & (levels < 0), it + 1,
                                   levels)
            trace = direction_mod.record(trace, it, d)
            d_next, locked = direction_mod.next_direction(
                cfg, d, locked, direction_mod.nnz_words(nxt.words),
                direction_mod.nnz_words(new_visited.words), n, avg_degree)
            return (nxt, new_visited, levels_new, it + 1, d_next, locked,
                    trace)

        # the trace is sized by n (BFS runs at most n iterations), not by
        # max_iters, so that max_iters stays a traced argument
        state = (frontier, frontier, levels, jnp.int32(0),
                 direction_mod.initial_direction(cfg), jnp.bool_(False),
                 direction_mod.empty_trace(n))
        _, _, levels, it, _, _, trace = jax.lax.while_loop(cond, body, state)
        return levels, it, trace

    return HoistedJit(loop)
