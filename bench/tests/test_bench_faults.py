"""A run with its timed path broken underneath comes out not correct.

Each fault is planted in the program (never in the harness) and the whole
run is driven on the CPU at a tiny size, skipping only the look for a chip.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import pytest

import repro.algorithms
from bench.tests.tiny import run_tiny


def _altered_bfs(monkeypatch):
    orig = repro.algorithms.bfs

    def bfs(g, source, **kw):
        res = orig(g, source, **kw)
        far = jnp.argmax(res.levels)         # one answer altered
        return dataclasses.replace(res, levels=res.levels.at[far].add(1))
    monkeypatch.setattr(repro.algorithms, "bfs", bfs)


def _unchanged_bfs(monkeypatch):
    orig = repro.algorithms.bfs

    def bfs(g, source, **kw):
        res = orig(g, source, **kw)          # state left as it started
        lv = jnp.full_like(res.levels, -1).at[source].set(0)
        return dataclasses.replace(res, levels=lv)
    monkeypatch.setattr(repro.algorithms, "bfs", bfs)


def _altered_pagerank(monkeypatch):
    orig = repro.algorithms.pagerank

    def pagerank(g, **kw):
        res = orig(g, **kw)
        return dataclasses.replace(res, ranks=res.ranks.at[0].mul(1.01))
    monkeypatch.setattr(repro.algorithms, "pagerank", pagerank)


def _unchanged_pagerank(monkeypatch):
    orig = repro.algorithms.pagerank

    def pagerank(g, **kw):
        res = orig(g, **kw)
        return dataclasses.replace(
            res, ranks=jnp.full_like(res.ranks, 1.0 / res.ranks.size))
    monkeypatch.setattr(repro.algorithms, "pagerank", pagerank)


FAULTS = [
    ("graph500-s19.bfs", _altered_bfs),
    ("graph500-s19.bfs", _unchanged_bfs),
    ("graph500-s19.pagerank", _altered_pagerank),
    ("graph500-s19.pagerank", _unchanged_pagerank),
]


@pytest.mark.parametrize("cell,plant", FAULTS,
                         ids=[f"{c}-{f.__name__[1:]}" for c, f in FAULTS])
def test_broken_timed_path_is_not_correct(monkeypatch, cell, plant):
    plant(monkeypatch)
    out = run_tiny(cell)
    assert not out["correct"], out["checks"]
