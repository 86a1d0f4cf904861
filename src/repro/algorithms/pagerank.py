"""PageRank on the arithmetic semiring (paper §V).

The paper multiplies the *column-stochastic* adjacency by the rank vector
using ``bmv_bin_full_full`` with an auxiliary out-degree vector: each rank
entry is divided by its out-degree *before* the binary mxv — exactly the
refactoring that keeps the matrix binary. Dangling mass is redistributed
uniformly; parameters default to the paper's (alpha 0.85, 10 iters, eps 1e-9).

``pagerank`` compiles its loop once per graph and ``row_chunk`` and keeps
it on the graph (``GraphMatrix.loop_plan``): ``alpha`` and its teleport
term, ``max_iters`` and ``eps`` are traced arguments, so later calls
dispatch the resident executable without tracing or lowering anything.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.descriptor import Descriptor
from repro.core.graphblas import GraphMatrix
from repro.core.semiring import ARITHMETIC


@dataclasses.dataclass
class PageRankResult:
    ranks: jax.Array
    n_iterations: int


def pagerank(g: GraphMatrix, alpha: float = 0.85, max_iters: int = 10,
             eps: float = 1e-9, row_chunk: Optional[int] = None) -> PageRankResult:
    plan = g.loop_plan("pagerank", (row_chunk,),
                       lambda: _build_pagerank_plan(g, row_chunk))
    # the teleport term is rounded to float32 from its double value, as
    # the weakly typed Python scalars of an eager loop would be
    pr, it = plan(np.float32(alpha), np.float32((1.0 - alpha) / g.n_rows),
                  np.int32(max_iters), np.float32(eps))
    return PageRankResult(ranks=pr, n_iterations=int(it))


def _build_pagerank_plan(g: GraphMatrix, row_chunk: Optional[int]):
    """The PageRank loop as one program of traced ``(alpha, teleport,
    max_iters, eps)``; ``row_chunk`` is baked in."""
    from repro.engine.planner import HoistedJit
    n = g.n_rows
    gt = g.transposed()  # column-stochastic mxv == Aᵀ · (pr / outdeg)
    out_deg = g.degrees()

    def loop(alpha, teleport, max_iters, eps):
        dangling = out_deg == 0
        safe_deg = jnp.where(dangling, 1.0, out_deg)
        pr0 = jnp.full(n, 1.0 / n, jnp.float32)

        def cond(state):
            _, delta, it = state
            return (delta > eps) & (it < max_iters)

        def body(state):
            pr, _, it = state
            scaled = pr / safe_deg                  # the v_out_degree division
            contrib = gt.mxv(scaled, ARITHMETIC,
                             Descriptor(row_chunk=row_chunk))
            dangle_mass = jnp.sum(jnp.where(dangling, pr, 0.0)) / n
            new = alpha * (contrib + dangle_mass) + teleport
            return new, jnp.sum(jnp.abs(new - pr)), it + 1

        pr, _, it = jax.lax.while_loop(
            cond, body, (pr0, jnp.float32(jnp.inf), jnp.int32(0)))
        return pr, it

    return HoistedJit(loop)


def ppr(g: GraphMatrix, seed: Union[int, jax.Array, np.ndarray],
        alpha: float = 0.85, max_iters: int = 10, eps: float = 1e-9,
        row_chunk: Optional[int] = None) -> PageRankResult:
    """Personalized PageRank: teleport to ``seed`` instead of uniformly.

    ``seed`` is a node id (one-hot restart) or a restart distribution
    ``[n]``. Dangling mass restarts into the same distribution, so rank mass
    stays within the seed's reachable set — the update is

        pr' = α·Aᵀ(pr/outdeg) + (α·dangling_mass + 1 − α)·r .

    The batched engine twin (``engine.queries.batched_ppr``) runs this
    per-column over a rank *matrix*; its columns are allclose to this loop.
    """
    n = g.n_rows
    if np.ndim(seed) == 0:
        if not 0 <= int(seed) < n:
            raise ValueError(f"seed {int(seed)} out of range [0, {n})")
        r = jnp.zeros(n, jnp.float32).at[int(seed)].set(1.0)
    else:
        r = jnp.asarray(seed, jnp.float32)
        if r.shape != (n,):
            raise ValueError(f"restart vector must have shape ({n},)")
    gt = g.transposed()
    out_deg = g.degrees()
    dangling = out_deg == 0
    safe_deg = jnp.where(dangling, 1.0, out_deg)

    def cond(state):
        _, delta, it = state
        return (delta > eps) & (it < max_iters)

    def body(state):
        pr, _, it = state
        scaled = pr / safe_deg
        contrib = gt.mxv(scaled, ARITHMETIC, Descriptor(row_chunk=row_chunk))
        dangle_mass = jnp.sum(jnp.where(dangling, pr, 0.0))
        new = alpha * contrib + (alpha * dangle_mass + (1.0 - alpha)) * r
        return new, jnp.sum(jnp.abs(new - pr)), it + 1

    pr, _, it = jax.lax.while_loop(cond, body, (r, jnp.float32(jnp.inf),
                                                jnp.int32(0)))
    return PageRankResult(ranks=pr, n_iterations=int(it))

