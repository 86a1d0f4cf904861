"""k-hop reachability / neighborhood expansion via repeated masked mxm.

The multi-hop traversal workload the SpGEMM subsystem unlocks (paper §VI's
headline kernel, composed GraphBLAST-style): with A the boolean adjacency,

    R_1 = A,    F_1 = A
    F_{i+1} = (F_i ∨.∧ A)⟨¬R_i⟩        -- frontier: *newly* reached pairs
    R_{i+1} = R_i ∨ F_{i+1}            -- reached within i+1 hops

The complemented structural mask ⟨¬R_i⟩ is the matrix analogue of BFS's
visited-mask (applied right before the store, paper §V): it keeps every
frontier product sparse, which is what makes repeated B2SR×B2SR mxm cheap.
Iteration stops early when a frontier empties (graph diameter reached).

All-pairs state (R_i) is held as a packed tile grid — uint32 words, 1 bit
per pair — so even the dense-ish late iterations stay bit-compressed.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import b2sr as b2sr_mod
from repro.core.b2sr import ell_to_packed_grid
from repro.core.descriptor import Descriptor
from repro.core.graphblas import GraphMatrix
from repro.core.operands import BitVector


@dataclasses.dataclass
class KHopResult:
    reach: GraphMatrix       # R[i, j] = 1 iff j reachable from i in <= k hops
    n_iterations: int        # mxm steps actually run (early exit at diameter)


def _grid_to_graph(grid: np.ndarray, n_rows: int, n_cols: int,
                   backend: str, with_transpose: bool = True) -> GraphMatrix:
    mat = b2sr_mod.packed_grid_to_b2sr(np.asarray(grid), n_rows, n_cols)
    return GraphMatrix.from_b2sr(mat, with_transpose=with_transpose,
                                 backend=backend)


def khop_reachability(g: GraphMatrix, k: int,
                      row_chunk: Optional[int] = None) -> KHopResult:
    """All-pairs <=k-hop reachability matrix via repeated masked mxm."""
    if g.n_rows != g.n_cols:
        raise ValueError("khop needs a square adjacency matrix")
    if k < 1:
        raise ValueError("k must be >= 1")
    if g.backend == "csr":
        return _khop_csr(g, k, row_chunk)
    # bit backends stay at the packed-grid level between hops: the visited
    # mask IS the reach grid (word AND-NOT), and the frontier only needs a
    # fresh ELL view — no COO/CSR/transpose materialisation per hop.
    reach_grid = np.asarray(ell_to_packed_grid(g.ell))
    frontier_ell = g.ell
    it = 1
    for _ in range(k - 1):
        if g.backend == "b2sr_pallas":
            from repro.kernels.spgemm import ops as spgemm_kernel_ops
            prod = np.asarray(spgemm_kernel_ops.mxm(frontier_ell, g.ell))
        else:
            from repro.core import ops
            prod = np.asarray(ops.mxm_bin_bin_bin(frontier_ell, g.ell,
                                                  row_chunk=row_chunk))
        new_grid = prod & ~reach_grid          # ⟨¬R_i⟩ mask-at-store
        if not new_grid.any():
            break
        reach_grid = reach_grid | new_grid
        frontier_ell = b2sr_mod.to_ell(b2sr_mod.packed_grid_to_b2sr(
            new_grid, g.n_rows, g.n_cols))
        it += 1
    reach = _grid_to_graph(reach_grid, g.n_rows, g.n_cols, g.backend)
    return KHopResult(reach=reach, n_iterations=it)


def _khop_csr(g: GraphMatrix, k: int,
              row_chunk: Optional[int] = None) -> KHopResult:
    """Float-baseline k-hop: repeated masked GraphMatrix.mxm."""
    reach = g
    frontier = g
    it = 1
    for _ in range(k - 1):
        new = frontier.mxm(g, mask=reach, complement=True,
                           row_chunk=row_chunk, with_transpose=False)
        if new.nnz == 0:
            break
        reach_grid = (np.asarray(ell_to_packed_grid(reach.ell))
                      | np.asarray(ell_to_packed_grid(new.ell)))
        reach = _grid_to_graph(reach_grid, g.n_rows, g.n_cols, g.backend,
                               with_transpose=False)
        frontier = new
        it += 1
    final = _grid_to_graph(np.asarray(ell_to_packed_grid(reach.ell)),
                           g.n_rows, g.n_cols, g.backend)
    return KHopResult(reach=final, n_iterations=it)


def khop_frontier(g: GraphMatrix, source: int, k: int,
                  row_chunk: Optional[int] = None) -> jax.Array:
    """Single-source <=k-hop neighborhood as a bool[n] vector.

    The vector specialisation of ``khop_reachability``: repeated masked
    ``mxv_bool`` on packed frontiers — the same visited-complement masking,
    one word-AND per tile instead of a tile product. BFS seed semantics:
    the source seeds ``visited`` and is excluded from the result (so a
    cycle back to the source is not reported, unlike the matrix diagonal).
    """
    if not g.has_transpose:
        raise ValueError("khop_frontier needs the transpose "
                         "(with_transpose=True)")
    n = g.n_rows
    gt = g.transposed()
    src = jnp.zeros(n, jnp.float32).at[source].set(1.0)
    frontier = BitVector.pack(src, g.tile_dim, n)
    seed = frontier
    visited = frontier
    for _ in range(k):
        frontier = gt.mxv(frontier,
                          desc=Descriptor(mask=visited, complement=True,
                                          row_chunk=row_chunk))
        visited = visited | frontier
    reached = visited & ~seed                  # exclude the source itself
    return reached.unpack(jnp.bool_)
