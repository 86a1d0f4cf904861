"""Bytes that one call of a B2SR kernel needs, from the bucket shapes.

A bucketed view holds, per bucket, a slab of ``rows`` tile-rows by
``width`` tile slots of ``t x t`` bit tiles. One call of the bits kernel
(BFS push or pull) over that slab needs

- the slab's tiles: ``rows * width * t`` uint32 words,
- the operand word of every slot (the x word of the slot's tile column):
  ``rows * width`` uint32 words,
- its output, one int32 per bit-row: ``rows * t``, and in the pull
  schedule the same again for the allowed lanes it reads.

The value kernel (PageRank, PPR, SSSP) needs the tiles, ``t`` float32
operand values per slot (one per tile column bit), and one float32 output
per bit-row.

These are the bytes of the bucket shapes, not of the kernel grid: padding
a slab to the kernel's blocks is the kernel's cost, so it shows in the
kernel's roofline share.
"""

from __future__ import annotations

from typing import Iterable, Tuple

WORD = 4


def bits_call_bytes(rows: int, width: int, t: int, pull: bool) -> int:
    tiles = rows * width * t * WORD
    operand = rows * width * WORD
    out = rows * t * WORD
    return tiles + operand + out * (2 if pull else 1)


def vals_call_bytes(rows: int, width: int, t: int) -> int:
    tiles = rows * width * t * WORD
    operand = rows * width * t * WORD
    out = rows * t * WORD
    return tiles + operand + out


def bits_pass_bytes(buckets: Iterable[Tuple[int, int]], t: int,
                    pull: bool) -> int:
    """One traversal step: one bits call per bucket ``(rows, width)``."""
    return sum(bits_call_bytes(r, w, t, pull) for r, w in buckets)


def vals_pass_bytes(buckets: Iterable[Tuple[int, int]], t: int) -> int:
    """One value mxv: one value call per bucket ``(rows, width)``."""
    return sum(vals_call_bytes(r, w, t) for r, w in buckets)
