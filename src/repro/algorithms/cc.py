"""Connected components, FastSV-style linear-algebra formulation (paper §V).

min-plus label propagation with pointer jumping (the FastSV "stochastic
hooking + shortcutting" collapsed to its min-label core, as in the
GraphBLAST implementation the paper follows): every vertex repeatedly takes
the minimum label among {itself, its neighbors' labels}, then shortcuts
through its parent. Converges in O(log n) iterations on typical graphs.

Direction optimization (DESIGN.md §12): CC has no visited mask, so the
pull row doesn't apply — here direction is *operand orientation*. A push
iteration hooks over out-edges (``A``), a pull iteration over in-edges
(``Aᵀ``); on the symmetric adjacency CC semantically assumes the two are
the same matrix, and min is order-insensitive, so every mode is bit-exact.
The changed-vertex set plays the frontier role in the density estimate
(packed + popcounted, same estimator as BFS) and the per-iteration choice
is recorded on ``CCResult.directions``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.algorithms import direction as direction_mod
from repro.algorithms.direction import DirectionConfig
from repro.core.descriptor import Descriptor
from repro.core.graphblas import GraphMatrix
from repro.core.operands import BitVector
from repro.core.semiring import MIN_PLUS


@dataclasses.dataclass
class CCResult:
    labels: jax.Array       # int32[n]: representative (min vertex id) per component
    n_iterations: int
    directions: Tuple[str, ...] = ()


def connected_components(g: GraphMatrix, max_iters: Optional[int] = None,
                         row_chunk: Optional[int] = None,
                         direction: Union[str, DirectionConfig, None] = "auto"
                         ) -> CCResult:
    cfg = direction_mod.as_config(direction)
    n = g.n_rows
    max_iters = n if max_iters is None else max_iters
    # orientation switching needs the stored transpose; a graph built
    # with with_transpose=False keeps the historical push-only loop
    if not g.has_transpose and g.backend != "csr" and cfg.mode != "push":
        cfg = DirectionConfig(mode="push")
    gt = g.transposed() if cfg.mode != "push" else g
    avg_degree = g.nnz / max(n, 1)
    t = g.tile_dim
    f0 = jnp.arange(n, dtype=jnp.float32)

    def hook_push(f):
        # hook: min over neighbors' labels (a_value=0 ⇒ pure min of f_j)
        return g.mxv(f, MIN_PLUS, Descriptor(row_chunk=row_chunk),
                     a_value=0.0)

    def hook_pull(f):
        return gt.mxv(f, MIN_PLUS, Descriptor(row_chunk=row_chunk),
                      a_value=0.0)

    def cond(state):
        _, changed, it, _, _, _ = state
        return changed.any() & (it < max_iters)

    def body(state):
        f, _, it, d, locked, trace = state
        if cfg.mode == "auto":
            neigh = jax.lax.cond(d == direction_mod.PULL, hook_pull,
                                 hook_push, f)
        elif cfg.mode == "pull":
            neigh = hook_pull(f)
        else:
            neigh = hook_push(f)
        f_new = jnp.minimum(f, neigh)
        # shortcut: pointer jumping f[i] <- f[f[i]]
        f_new = f_new[f_new.astype(jnp.int32)]
        changed = BitVector.pack((f_new != f).astype(jnp.float32), t, n)
        trace = direction_mod.record(trace, it, d)
        # the changed set is the "frontier"; CC has no visited set, so the
        # unexplored estimate is the whole edge set (pull while a large
        # fraction of labels is still moving, push for the tail)
        d_next, locked = direction_mod.next_direction(
            cfg, d, locked, direction_mod.nnz_words(changed.words),
            jnp.int32(0), n, avg_degree)
        return f_new, changed, it + 1, d_next, locked, trace

    ones = BitVector.pack(jnp.ones(n, jnp.float32), t, n)
    state = (f0, ones, jnp.int32(0), direction_mod.initial_direction(cfg),
             jnp.bool_(False), direction_mod.empty_trace(max_iters))
    f, _, it, _, _, trace = jax.lax.while_loop(cond, body, state)
    it = int(it)
    dirs = direction_mod.trace_tuple(trace, it)
    direction_mod.observe_trace(dirs, kernel="cc")
    return CCResult(labels=f.astype(jnp.int32), n_iterations=it,
                    directions=dirs)
