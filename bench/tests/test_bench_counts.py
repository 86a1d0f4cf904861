"""Graph500 edge counts and kernel byte counts against hand counts."""

from __future__ import annotations

import numpy as np
import pytest

from bench.lib import kbytes
from bench.lib.peaks import peaks
from bench.lib.reference import Reference, mismatches, rel_err


def _sym(edges, n):
    r = [a for a, b in edges] + [b for a, b in edges]
    c = [b for a, b in edges] + [a for a, b in edges]
    return Reference(np.asarray(r), np.asarray(c), n)


def test_graph500_edges_count_the_roots_component():
    # a triangle {0,1,2} plus a pendant 3, a separate edge {4,5}, isolated 6
    ref = _sym([(0, 1), (1, 2), (0, 2), (2, 3), (4, 5)], 7)
    assert list(ref.graph500_edges([0, 3, 4, 5])) == [4, 4, 1, 1]
    assert list(ref.graph500_edges([6])) == [0]


def test_reference_levels_and_controls():
    ref = _sym([(0, 1), (1, 2), (2, 3)], 5)          # a path, 4 isolated
    assert ref.levels([0]).tolist() == [[0, 1, 2, 3, -1]]
    assert ref.levels_truncated([0]).tolist() == [[0, 1, 2, -1, -1]]
    assert mismatches(ref.levels_truncated([0]), ref.levels([0])) == 1


def test_pagerank_reference_is_stochastic_and_bf16_control_departs():
    rng = np.random.default_rng(0)
    e = {(int(a), int(b)) for a, b in rng.integers(0, 200, (800, 2))
         if a != b}
    ref = _sym(sorted(e), 200)
    pr = ref.pagerank(10)
    assert pr.sum() == pytest.approx(1.0)
    assert rel_err(ref.pagerank_bf16(10), pr) > 1e-3
    assert rel_err(pr * np.nan, pr) == float("inf")


def test_kernel_bytes_by_hand():
    # 3 tile-rows x 5 slots of 32x32 tiles: tiles 3*5*32*4 = 1920 B,
    # operand words 3*5*4 = 60 B, outputs 3*32*4 = 384 B
    assert kbytes.bits_call_bytes(3, 5, 32, pull=False) == 1920 + 60 + 384
    assert kbytes.bits_call_bytes(3, 5, 32, pull=True) == 1920 + 60 + 768
    # the value kernel reads 32 float32 operand values per slot
    assert kbytes.vals_call_bytes(3, 5, 32) == 1920 + 1920 + 384
    shapes = ((3, 5), (1, 2))
    assert kbytes.bits_pass_bytes(shapes, 32, False) == (
        kbytes.bits_call_bytes(3, 5, 32, False)
        + kbytes.bits_call_bytes(1, 2, 32, False))
    assert kbytes.vals_pass_bytes(shapes, 32) == (
        kbytes.vals_call_bytes(3, 5, 32) + kbytes.vals_call_bytes(1, 2, 32))


def test_peaks_table_names_v5e_and_refuses_unknown_kinds():
    assert peaks("TPU v5 lite")["hbm_bw"] == 819e9
    with pytest.raises(KeyError):
        peaks("TPU v99")
