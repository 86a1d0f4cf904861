"""The generators make the same simple symmetric graphs from the seed."""

from __future__ import annotations

import numpy as np
import pytest

from bench.lib.generators import generate

KRON = {"generator": "kronecker", "scale": 8, "edgefactor": 8, "A": 0.57,
        "B": 0.19, "C": 0.19, "permute_labels": False, "symmetric": True}


def _simple_symmetric(rows, cols, n):
    assert rows.min() >= 0 and max(rows.max(), cols.max()) < n
    assert not np.any(rows == cols)
    key = rows * n + cols
    assert np.unique(key).size == key.size
    assert np.array_equal(np.sort(key), np.sort(cols * n + rows))


@pytest.mark.parametrize("permute", [False, True])
def test_kronecker_is_simple_symmetric_and_seeded(permute):
    seed = 2 ** 33 + 5                   # wider than 32 bits
    cfg = dict(KRON, permute_labels=permute)
    r1, c1, n = generate(cfg, seed)
    r2, c2, _ = generate(cfg, seed)
    r3, _, _ = generate(cfg, seed + 1)
    assert n == 256
    _simple_symmetric(r1, c1, n)
    assert np.array_equal(r1, r2) and np.array_equal(c1, c2)
    assert not (r3.size == r1.size and np.array_equal(r3, r1))
    # 8 * 256 generated edges, both directions, less duplicates and loops
    assert 0.5 * 2 * 8 * n < r1.size <= 2 * 8 * n
    deg = np.bincount(r1, minlength=n)
    assert deg.max() > 8 * np.median(deg[deg > 0])   # power-law skew


def test_permuted_labels_relabel_the_same_graph():
    seed = 2 ** 31 + 17
    r0, c0, n = generate(KRON, seed)
    r1, c1, _ = generate(dict(KRON, permute_labels=True), seed)
    assert r1.size == r0.size
    d0 = np.bincount(r0, minlength=n)
    d1 = np.bincount(r1, minlength=n)
    assert np.array_equal(np.sort(d0), np.sort(d1))   # same degrees
    assert not np.array_equal(d0, d1)                  # on other labels
    # the hub no longer sits at label 0, and edges spread over more tiles
    assert np.argmax(d0) == 0 and np.argmax(d1) != 0

    def tiles(r, c):
        return np.unique((r // 8) * n + c // 8).size
    assert tiles(r1, c1) > tiles(r0, c0)
