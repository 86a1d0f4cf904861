"""The program's graph, built the way a user builds it, and its shapes."""

from __future__ import annotations

import time
from typing import Tuple

import jax
import numpy as np

from bench.lib.generators import generate

#: the backend every cell measures: Pallas kernels compiled by Mosaic
BACKEND = "b2sr_pallas"


def build(config: dict, seed: int, log):
    """``(graph, rows, cols, n)``: the configuration's graph from ``seed``,
    with both orientations' bucketed views and the degrees on the device."""
    from repro.core import GraphMatrix
    t0 = time.monotonic()
    rows, cols, n = generate(config, seed)
    t1 = time.monotonic()
    g = GraphMatrix.from_coo(rows, cols, n, n, tile_dim=config["tile_dim"],
                             backend=BACKEND)
    gt = g.transposed()
    jax.block_until_ready((g.buckets().bit_tiles, gt.buckets().bit_tiles,
                           g.degrees()))
    t2 = time.monotonic()
    log(f"[bench] graph n={n} nnz={rows.size} tiles={g.tiles.n_tiles}: "
        f"generate {t1 - t0:.3f} s, build {t2 - t1:.3f} s")
    return g, rows, cols, n


def bucket_shapes(view) -> Tuple[Tuple[int, int], ...]:
    """``(rows, width)`` of each bucket of a view's bucketed slabs."""
    b = view.buckets()
    return tuple(zip((int(r) for r in b.bucket_sizes),
                     (int(w) for w in b.bucket_widths)))


def degree_one_vertices(rows: np.ndarray, n: int) -> np.ndarray:
    """Vertices with at least one edge, ascending."""
    return np.flatnonzero(np.bincount(rows, minlength=n) > 0)
