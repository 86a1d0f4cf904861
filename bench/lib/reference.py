"""The plain reference: BFS levels, components and PageRank from
scipy.sparse and numpy alone, on the COO the generator made.

Copied from the repository's chip smoke test (``Reference``) so that the
yardstick cannot move with the code under test; it imports nothing of the
program. The answers a cell compares are float64 or exact integers here.

Also kept here: the Graph500 edge count of a BFS (input edges in the
root's component) and the lower-precision controls that the correctness
limits were shown to fail (:func:`pagerank_bf16`,
:func:`levels_truncated`).
"""

from __future__ import annotations

import ml_dtypes
import numpy as np
import scipy.sparse as sp
from scipy.sparse.csgraph import connected_components, shortest_path


class Reference:
    """scipy/numpy answers for one graph given as a symmetric COO."""

    def __init__(self, rows: np.ndarray, cols: np.ndarray, n: int):
        self.n = n
        self.a = sp.csr_matrix((np.ones(rows.size, np.float64),
                                (rows, cols)), shape=(n, n))
        self.a.sum_duplicates()
        self.a.data[:] = 1.0
        self.at = self.a.T.tocsr()
        self.outdeg = np.asarray(self.a.sum(axis=1)).ravel()
        self._comp = None

    # -- traversals ----------------------------------------------------------
    def levels(self, sources) -> np.ndarray:
        """Hop levels ``[len(sources), n]`` along out-edges, -1 unreachable."""
        d = shortest_path(self.a, directed=True, unweighted=True,
                          indices=np.asarray(sources, np.int64))
        return np.where(np.isinf(d), -1, d).astype(np.int64)

    def components(self) -> np.ndarray:
        """Component label of each vertex (weakly connected)."""
        if self._comp is None:
            _, self._comp = connected_components(self.a, directed=False)
        return self._comp

    def graph500_edges(self, roots) -> np.ndarray:
        """Graph500 TEPS numerator per root: the undirected edges with both
        ends in the root's component (each stored pair counted once)."""
        lab = self.components()
        per_comp = np.bincount(lab, weights=self.outdeg)
        return per_comp[lab[np.asarray(roots, np.int64)]] / 2.0

    # -- ranks ---------------------------------------------------------------
    def _iterate(self, iters, eps, round_to=None):
        """PageRank as the program defines it, dangling mass spread
        uniformly. ``round_to`` rounds every stored vector to that dtype
        (the lower-precision control)."""
        rnd = ((lambda v: v.astype(round_to).astype(np.float64))
               if round_to is not None else (lambda v: v))
        pr = rnd(np.full(self.n, 1.0 / self.n))
        dangling = self.outdeg == 0
        safe = np.where(dangling, 1.0, self.outdeg)
        it, delta = 0, np.inf
        alpha = 0.85
        while delta > eps and it < iters:
            contrib = rnd(self.at @ rnd(pr / safe))
            mass = pr[dangling].sum()
            new = rnd(alpha * (contrib + mass / self.n) + (1 - alpha) / self.n)
            delta = np.abs(new - pr).sum()
            pr, it = new, it + 1
        return pr

    def pagerank(self, iters: int, eps: float = 0.0) -> np.ndarray:
        return self._iterate(iters, eps)

    # -- controls: the reference one step below the stated precision -----
    def pagerank_bf16(self, iters: int, eps: float = 0.0) -> np.ndarray:
        return self._iterate(iters, eps, round_to=ml_dtypes.bfloat16)

    def levels_truncated(self, sources) -> np.ndarray:
        """Levels of a traversal that stops one level early: the deepest
        level of each source is left unreached (the exact-answer control)."""
        lv = self.levels(sources)
        deepest = lv.max(axis=1, keepdims=True)
        return np.where((lv == deepest) & (deepest > 0), -1, lv)


# -- comparisons: each returns one number that a limit is set on -------------

def mismatches(got, want) -> int:
    """Entries that differ (a shape mismatch counts every entry)."""
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        return int(max(got.size, want.size))
    return int(np.sum(got != want))


def rel_err(got, want) -> float:
    """``max |got - want| / max |want|`` (non-finite ``got`` reads inf)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-300))
