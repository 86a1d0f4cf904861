"""Pallas TPU kernels for BMV over B2SR slabs (paper Listing 1, TPU-native).

Layout (DESIGN.md §2). A slab of ``R`` tile-rows × ``K`` tile slots reaches
the kernels *word-major*, ``uint32[R*t, K]``: row ``r*t + a`` holds bit-row
``a`` of every tile of tile-row ``r``, one tile per lane. This is the
layout XLA already keeps a ``[R, K, t]`` slab in on the TPU, so the wrapper's
reshape is free, and every block is lane-dense. Operands indexed by tile
column (x words, x value tiles, frontier words) are gathered by the jitted
wrapper into the same lane order, so no kernel gathers: Mosaic lowers no
in-kernel 1-D gather, and a VMEM-resident operand table cannot hold a
real graph's vector panel anyway.

Grid: ``(row_blocks, k_blocks)``, k innermost (``arbitrary``). A row block
is ``ROW_LANES = 256`` bit-rows (``256 // t`` tile-rows). Partial results
fold into a VMEM accumulator; the last k step reduces its lanes (one
transpose, one sublane reduction) and writes one lane-dense row of 256
results, ``out[i, 0, :]``. AND+popcount on uint32 lanes replaces
``__popc``; accumulation is private per row block (no atomics).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: bit-rows per row block — every output block is one 256-lane row
ROW_LANES = 256
LANES = 128

_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


def row_block(t: int) -> int:
    """Tile-rows per row block (``ROW_LANES`` bit-rows)."""
    return ROW_LANES // t


def k_block(k: int) -> int:
    """K block for a slab padded to a multiple of ``LANES`` tile slots."""
    for bk in (512, 256):
        if k % bk == 0:
            return bk
    return LANES


def _expand(x, t: int):
    """``[BR, BK] -> [BR*t, BK]``: each tile-row's lane row, once per bit-row."""
    br, bk = x.shape
    return jnp.broadcast_to(x[:, None, :], (br, t, bk)).reshape(br * t, bk)


def _fold(v, op):
    """``[rows, BK] -> [rows, LANES]``: combine the lane-aligned column groups."""
    acc = v[:, :LANES]
    for j in range(1, v.shape[1] // LANES):
        acc = op(acc, v[:, j * LANES:(j + 1) * LANES])
    return acc


def _row(acc, reduce):
    """``[rows, L] -> [1, rows]``: reduce each row's lanes into one lane row."""
    return reduce(acc.T, axis=0, keepdims=True)


def _or_reduce(v, axis, keepdims):
    """Bitwise-OR reduction over sublanes (``axis=0``) by halving."""
    assert axis == 0 and keepdims
    while v.shape[0] > 1:
        h = v.shape[0] // 2
        v = v[:h] | v[h:]
    return v


def _row_hits(acc):
    return _row((acc != 0).astype(jnp.int32), jnp.max)


# ---------------------------------------------------------------------------
# bin·bin kernels: packed x words against packed tiles
# ---------------------------------------------------------------------------

def _bits_kernel(tiles_ref, xw_ref, *rest, t: int, count: bool, pull: bool):
    """``count``: ``Σ_k popcount(tile & x)`` per bit-row (bin·bin→full).
    Otherwise ``OR_k (tile & x) != 0`` (bin·bin→bin).

    ``pull`` (DESIGN.md §12) adds the allowed lanes: once every allowed
    bit-row of the block has a hit, the remaining k steps skip their
    compute. Bit-exact by construction — a skipped step could only set
    bits that are already set.
    """
    if pull:
        allowed_ref, out_ref, acc_ref = rest
    else:
        out_ref, acc_ref = rest
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def accumulate():
        v = tiles_ref[...] & _expand(xw_ref[...], t)
        if count:
            pc = jax.lax.population_count(v).astype(jnp.int32)
            acc_ref[...] += _fold(pc, jnp.add)
        else:
            acc_ref[...] |= _fold(v, jnp.bitwise_or)

    if pull:
        allowed = allowed_ref[0]                         # [1, rows] 0/1
        done = jnp.all((_row_hits(acc_ref[...]) | (1 - allowed)) != 0)
        pl.when(jnp.logical_not(done))(accumulate)
    else:
        accumulate()

    @pl.when(k == pl.num_programs(1) - 1)
    def _store():
        if count:
            out_ref[0] = _row(acc_ref[...], jnp.sum)
        elif pull:
            out_ref[0] = _row_hits(acc_ref[...]) & allowed
        else:
            out_ref[0] = _row_hits(acc_ref[...])


def bits_pallas(tiles_w, xw, allowed=None, *, t: int, count: bool,
                interpret: bool):
    """Word-major tiles ``[R*t, K]`` × gathered x words ``[R, K]`` ->
    ``int32[R // BR, 1, ROW_LANES]`` per-bit-row counts (``count``) or 0/1
    hits. ``allowed`` (``int32[R // BR, 1, ROW_LANES]``) selects the pull
    schedule and masks its output."""
    rt, kk = tiles_w.shape
    br = row_block(t)
    bk = k_block(kk)
    nb = rt // ROW_LANES
    assert rt % ROW_LANES == 0 and kk % bk == 0 and xw.shape == (nb * br, kk)
    pull = allowed is not None
    in_specs = [pl.BlockSpec((ROW_LANES, bk), lambda i, k: (i, k)),
                pl.BlockSpec((br, bk), lambda i, k: (i, k))]
    args = [tiles_w, xw]
    row_spec = pl.BlockSpec((1, 1, ROW_LANES), lambda i, k: (i, 0, 0))
    if pull:
        in_specs.append(row_spec)
        args.append(allowed)
    return pl.pallas_call(
        functools.partial(_bits_kernel, t=t, count=count, pull=pull),
        grid=(nb, kk // bk),
        in_specs=in_specs,
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((nb, 1, ROW_LANES), jnp.int32),
        scratch_shapes=[pltpu.VMEM((ROW_LANES, LANES),
                                   jnp.int32 if count else jnp.uint32)],
        compiler_params=_SEMANTICS,
        interpret=interpret,
    )(*args)


# ---------------------------------------------------------------------------
# bin·vec kernel: packed tiles against gathered per-column values
# ---------------------------------------------------------------------------

#: mode -> (⊕ on the accumulator, ⊗ of the edge value a and x)
_VAL_OPS = {
    "sum": (jnp.add, lambda a, x: x),            # a applied once, at the end
    "min_plus": (jnp.minimum, lambda a, x: a + x),
    "max_times": (jnp.maximum, lambda a, x: a * x),
    "or": (jnp.bitwise_or, lambda a, x: x),
}


def _vals_kernel(tiles_ref, xg_ref, out_ref, acc_ref, *, t: int, mode: str,
                 a_value: float, ident):
    """``y[r, a] = ⊕_{k, c : bit(r, k, a, c)} a ⊗ x[col(r, k)*t + c]``.

    ``xg`` block rows are ``c*BR + r`` (the wrapper's gather order), so
    column-bit ``c`` of every tile-row is one aligned ``[BR, BK]`` slice,
    broadcast over the tile-row's ``t`` bit-rows. Absent bits contribute
    the ⊕-identity through a select, never a multiply, so ±inf values
    cannot leak NaN through missing edges.
    """
    k = pl.program_id(1)
    add, mul = _VAL_OPS[mode]
    dtype = acc_ref.dtype
    identv = jnp.asarray(ident, dtype)
    av = jnp.asarray(a_value, dtype)
    br = tiles_ref.shape[0] // t

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.full(out_ref.shape, identv, dtype)

    acc_ref[...] = jnp.full(acc_ref.shape, identv, dtype)
    tiles = tiles_ref[...]

    def body(c, carry):
        bit = ((tiles >> c.astype(jnp.uint32)) & jnp.uint32(1)) != 0
        xc = xg_ref[pl.ds(pl.multiple_of(c * br, br), br), :]
        acc_ref[...] = add(acc_ref[...],
                           jnp.where(bit, mul(av, _expand(xc, t)), identv))
        return carry

    jax.lax.fori_loop(0, t, body, 0)
    part = _fold(acc_ref[...], add)
    if mode == "sum":
        red = jnp.sum
    elif mode == "min_plus":
        red = jnp.min
    elif mode == "max_times":
        red = jnp.max
    else:
        red = _or_reduce
    row = _row(part, red)
    if mode == "sum":
        row = av * row
    out_ref[0] = add(out_ref[0], row)


def vals_pallas(tiles_w, xg, *, t: int, mode: str, a_value: float = 1.0,
                ident=0.0, interpret: bool):
    """Word-major tiles ``[R*t, K]`` × gathered values ``[R*t, K]`` (rows
    ``c*BR + r`` within each row block) -> ``[R // BR, 1, ROW_LANES]`` of
    ``xg.dtype``."""
    rt, kk = tiles_w.shape
    bk = k_block(kk)
    nb = rt // ROW_LANES
    assert rt % ROW_LANES == 0 and kk % bk == 0 and xg.shape == (rt, kk)
    spec = pl.BlockSpec((ROW_LANES, bk), lambda i, k: (i, k))
    return pl.pallas_call(
        functools.partial(_vals_kernel, t=t, mode=mode, a_value=a_value,
                          ident=ident),
        grid=(nb, kk // bk),
        in_specs=[spec, spec],
        out_specs=pl.BlockSpec((1, 1, ROW_LANES), lambda i, k: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, 1, ROW_LANES), xg.dtype),
        scratch_shapes=[pltpu.VMEM((ROW_LANES, bk), xg.dtype)],
        compiler_params=_SEMANTICS,
        interpret=interpret,
    )(tiles_w, xg)
