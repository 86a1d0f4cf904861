"""Pallas TPU kernel: dense 0/1 matrix -> packed bit tiles (+ bit transpose).

The conversion-time packing kernel (paper §III.B "bit-packing overhead"):
packs a dense [R*t, C*t] 0/1 block into uint32 words, one word per tile
bit-row, LSB-first. The transpose variant packs column-major (the
``__ballot_sync`` + ``__brev`` rotation of the paper, done here as a VPU
shift-reduce because TPU has no warp votes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _or_sum(bits, axis):
    """OR of disjoint shifted bits as a wrapping int32 sum (Mosaic has no
    unsigned reductions), reinterpreted as uint32 words."""
    total = jnp.sum(bits.astype(jnp.int32), axis=axis, dtype=jnp.int32)
    return jax.lax.bitcast_convert_type(total, jnp.uint32)


def _pack_kernel(x_ref, out_ref, *, t: int, col_major: bool):
    x = x_ref[...]                                 # [BRt, BCt] 0/1
    br = x.shape[0] // t
    bc = x.shape[1] // t
    tiles = x.reshape(br, t, bc, t).transpose(0, 2, 1, 3)   # [br, bc, t(row), t(col)]
    if col_major:
        tiles = jnp.swapaxes(tiles, -1, -2)
    shifts = jnp.arange(t, dtype=jnp.uint32)
    out_ref[...] = _or_sum(tiles.astype(jnp.uint32) << shifts, -1)


def pack_dense_pallas(x, *, t: int, block_r: int = 8, block_c: int = 8,
                      col_major: bool = False, interpret: bool = True):
    """x: [R*t, C*t] any-int/float 0/1 -> uint32[R, C, t]."""
    Rt, Ct = x.shape
    R, C = Rt // t, Ct // t
    assert Rt % t == 0 and Ct % t == 0
    assert R % block_r == 0 and C % block_c == 0
    grid = (R // block_r, C // block_c)
    return pl.pallas_call(
        functools.partial(_pack_kernel, t=t, col_major=col_major),
        grid=grid,
        in_specs=[pl.BlockSpec((block_r * t, block_c * t),
                               lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((block_r, block_c, t), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((R, C, t), jnp.uint32),
        interpret=interpret,
    )(x)


def _pack_rows_kernel(x_ref, out_ref, *, t: int):
    x = x_ref[...]                                 # [BR*t, BD] 0/1
    br = x.shape[0] // t
    tiles = x.reshape(br, t, -1).astype(jnp.uint32)
    shifts = jnp.arange(t, dtype=jnp.uint32)[None, :, None]
    out_ref[...] = _or_sum(tiles << shifts, 1)


def pack_rows_pallas(x, *, t: int, block_r: int = 1, block_d: int = 128,
                     interpret: bool = True):
    """x: [R*t, D] 0/1 -> uint32[R, D]: row-axis-only packing, LSB-first.

    The activation-packing twin of :func:`pack_dense_pallas` — feature
    columns stay unpacked words (the ``BitMatrix`` layout consumed by the
    bin·bin→full spmm rows), only the node axis collapses t-to-1.
    """
    Rt, D = x.shape
    R = Rt // t
    assert Rt % t == 0 and R % block_r == 0 and D % block_d == 0
    grid = (R // block_r, D // block_d)
    return pl.pallas_call(
        functools.partial(_pack_rows_kernel, t=t),
        grid=grid,
        in_specs=[pl.BlockSpec((block_r * t, block_d), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((block_r, block_d), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((R, D), jnp.uint32),
        interpret=interpret,
    )(x)


def _transpose_kernel(w_ref, out_ref, *, t: int):
    words = w_ref[...]                                    # [B, t]
    shifts = jnp.arange(t, dtype=jnp.uint32)
    bits = (words[:, :, None] >> shifts) & jnp.uint32(1)  # [B, t, t]
    bits_t = jnp.swapaxes(bits, -1, -2)
    out_ref[...] = _or_sum(bits_t << shifts, -1)


def bit_transpose_pallas(words, *, t: int, block: int = 64,
                         interpret: bool = True):
    """uint32[N, t] row-major tiles -> column-major packed tiles."""
    N = words.shape[0]
    assert N % block == 0
    return pl.pallas_call(
        functools.partial(_transpose_kernel, t=t),
        grid=(N // block,),
        in_specs=[pl.BlockSpec((block, t), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block, t), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, t), jnp.uint32),
        interpret=interpret,
    )(words)
