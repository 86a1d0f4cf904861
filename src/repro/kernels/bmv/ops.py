"""Jitted wrappers around the Pallas BMV kernels (pad + dispatch + unpad)."""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.b2sr import B2SRBucketedEll, B2SREll, ceil_div
from repro.core.semiring import Semiring, ARITHMETIC
from repro.kernels import common
from repro.kernels.bmv import bmv as kernels


def slab(col: jax.Array, tiles: jax.Array):
    """Pad an ELL slab to the kernel grid and view its tiles word-major.

    Rows pad to whole row blocks and slots to whole lane groups (padding
    slots carry col ``-1`` and all-zero tiles, so they never contribute).
    ``[R, K, t] -> [R*t, K]`` is free on the TPU, where XLA keeps the slab
    in that order already.
    """
    t = tiles.shape[-1]
    col = common.pad_to(common.pad_to(col, 0, kernels.row_block(t), fill=-1),
                        1, kernels.LANES, fill=-1)
    tiles = common.pad_to(common.pad_to(tiles, 0, kernels.row_block(t)),
                          1, kernels.LANES)
    r, k = col.shape
    return col, jnp.swapaxes(tiles, 1, 2).reshape(r * t, k)


def gather_words(col: jax.Array, words: jax.Array) -> jax.Array:
    """Per-slot operand word ``words[col]`` (0 on padding slots): [R, K]."""
    safe = jnp.clip(col, 0, words.shape[0] - 1)
    return jnp.where(col >= 0, words[safe], jnp.uint32(0))


def gather_vals(col: jax.Array, x3: jax.Array) -> jax.Array:
    """Per-slot operand tiles ``x3[col]`` in the value kernel's row order.

    ``x3``: ``[n_tile_cols, t]``. Returns ``[R*t, K]`` whose row
    ``i*ROW_LANES + c*BR + r`` holds column-bit ``c`` of tile-row
    ``i*BR + r`` — padding slots read column 0, which their all-zero tiles
    never select.
    """
    r, k = col.shape
    t = x3.shape[1]
    br = kernels.row_block(t)
    # one row gather (t contiguous values per slot) — far cheaper on the
    # TPU than t element gathers, at the price of a transient [.., t]-minor
    # intermediate that the TPU pads to 128 lanes before the transpose
    g = x3[jnp.clip(col, 0, x3.shape[0] - 1)]                # [R, K, t]
    g = g.reshape(r // br, br, k, t).transpose(0, 3, 1, 2)   # [nb, t, br, K]
    return g.reshape(r * t, k)


def pack_hits(hits: jax.Array, t: int) -> jax.Array:
    """0/1 per bit-row ``[R*t]`` -> uint32 words ``[R]`` (LSB-first)."""
    shifts = jnp.arange(t, dtype=jnp.uint32)
    return jnp.sum(hits.reshape(-1, t).astype(jnp.uint32) << shifts, axis=1,
                   dtype=jnp.uint32)


def unpack_rows(words: jax.Array, t: int) -> jax.Array:
    """uint32 words ``[R]`` -> int32 0/1 per bit-row, ``[R // BR, 1, 256]``."""
    shifts = jnp.arange(t, dtype=jnp.uint32)
    bits = ((words[:, None] >> shifts) & jnp.uint32(1)).astype(jnp.int32)
    return bits.reshape(-1, 1, kernels.ROW_LANES)


@partial(jax.jit, static_argnames=("n_rows", "out_dtype", "interpret"))
def _bin_bin_full(col, tiles, x_words, n_rows, out_dtype, interpret):
    t = tiles.shape[-1]
    col, tiles_w = slab(col, tiles)
    out = kernels.bits_pallas(tiles_w, gather_words(col, x_words), t=t,
                              count=True, interpret=interpret)
    return out.reshape(-1)[:n_rows].astype(out_dtype)


def bmv_bin_bin_full(ell: B2SREll, x_packed: jax.Array,
                     out_dtype=jnp.float32,
                     interpret: Optional[bool] = None):
    """Neighbour counts ``y_i = |N(i) ∩ x|`` (bin·bin→full)."""
    interpret = common.interpret_default() if interpret is None else interpret
    return _bin_bin_full(ell.tile_col_idx, ell.bit_tiles, x_packed,
                         ell.n_rows, out_dtype, interpret)


@partial(jax.jit, static_argnames=("interpret",))
def _bin_bin_bin(col, tiles, x_words, interpret):
    t = tiles.shape[-1]
    n_words = col.shape[0]
    col, tiles_w = slab(col, tiles)
    hits = kernels.bits_pallas(tiles_w, gather_words(col, x_words), t=t,
                               count=False, interpret=interpret)
    return pack_hits(hits, t)[:n_words]


def bmv_bin_bin_bin(ell: B2SREll, x_packed: jax.Array,
                    mask_packed: Optional[jax.Array] = None,
                    complement: bool = True,
                    interpret: Optional[bool] = None):
    """Packed frontier -> packed frontier (bin·bin→bin); the §V mask is
    ANDed onto the packed words before they are returned."""
    interpret = common.interpret_default() if interpret is None else interpret
    n_words = ceil_div(ell.n_rows, ell.tile_dim)
    out = _bin_bin_bin(ell.tile_col_idx, ell.bit_tiles, x_packed,
                       interpret)[:n_words]
    if mask_packed is not None:
        out = out & (~mask_packed if complement else mask_packed)
    return out


@partial(jax.jit, static_argnames=("complement", "interpret"))
def _bin_bin_bin_pull(col, tiles, x_words, mask_words, complement,
                      interpret):
    t = tiles.shape[-1]
    n_words_out = mask_words.shape[0]
    col, tiles_w = slab(col, tiles)
    mask_pad = common.pad_to(mask_words, 0, col.shape[0])
    allowed = unpack_rows(~mask_pad if complement else mask_pad, t)
    hits = kernels.bits_pallas(tiles_w, gather_words(col, x_words), allowed,
                               t=t, count=False, interpret=interpret)
    return pack_hits(hits, t)[:n_words_out]


def bmv_bin_bin_bin_pull(ell: B2SREll, x_packed: jax.Array,
                         mask_packed: jax.Array, complement: bool = True,
                         interpret: Optional[bool] = None):
    """Fused pull traversal: a row block stops computing once every allowed
    lane is set.

    Unlike the push row, the mask is mandatory — pull without a visited
    set has nothing to exit on (the generic layer guarantees this; see
    ``dispatch.MASKED_ONLY_OPS``). Row-padding words beyond ``n_rows``
    get an all-zero mask slot, which under ``complement=True`` means
    "all lanes wanted" — harmless: padded rows have no tiles, and the
    words are sliced off.
    """
    interpret = common.interpret_default() if interpret is None else interpret
    return _bin_bin_bin_pull(ell.tile_col_idx, ell.bit_tiles, x_packed,
                             mask_packed, complement, interpret)


def bmv_bin_bin_bin_pull_bucketed(b: B2SRBucketedEll, x_packed: jax.Array,
                                  mask_packed: jax.Array,
                                  complement: bool = True,
                                  interpret: Optional[bool] = None):
    """Bucketed pull: per-bucket early-exit slabs with *gathered* masks.

    The push bucketed path ANDs the mask after the scatter-merge; pull
    cannot — the early exit needs the allowed lanes inside the kernel —
    so each bucket gathers its rows' mask words through the same row
    permutation used for the output scatter. Empty tile-rows are in no
    bucket and keep the zero word (OR-identity), which the post-AND also
    preserved, so the two mask placements stay bit-exact.
    """
    out = jnp.zeros((b.n_tile_rows,), jnp.uint32)
    for i, rows in enumerate(b.rows):
        e = common.bucket_ell(b, i)
        words = bmv_bin_bin_bin_pull(e, x_packed, mask_packed[rows],
                                     complement, interpret)
        out = out.at[rows].set(words)
    return out


_MODE = {"arithmetic": "sum", "min_plus": "min_plus", "max_times": "max_times"}


@partial(jax.jit, static_argnames=("n_rows", "mode", "a_value", "ident",
                                   "interpret"))
def _bin_full_full(col, tiles, x3, n_rows, mode, a_value, ident, interpret):
    t = tiles.shape[-1]
    col, tiles_w = slab(col, tiles)
    out = kernels.vals_pallas(tiles_w, gather_vals(col, x3), t=t, mode=mode,
                              a_value=a_value, ident=ident,
                              interpret=interpret)
    return out.reshape(-1)[:n_rows]


def bmv_bin_full_full(ell: B2SREll, x: jax.Array,
                      semiring: Semiring = ARITHMETIC, a_value: float = 1.0,
                      interpret: Optional[bool] = None):
    """General-semiring mxv with a full-precision vector. Every mode
    selects the ⊕-identity for absent edges, so ±inf vectors (SSSP
    distances) are safe on all of them."""
    interpret = common.interpret_default() if interpret is None else interpret
    if semiring.name not in _MODE:
        raise NotImplementedError(f"kernel path for semiring {semiring.name}")
    mode = _MODE[semiring.name]
    ident = float(semiring.add_identity) if mode != "sum" else 0.0
    t = ell.tile_dim
    n_tc = ell.n_tile_cols
    x_pad = jnp.pad(x, (0, n_tc * t - x.shape[0]),
                    constant_values=jnp.asarray(ident, x.dtype))
    return _bin_full_full(ell.tile_col_idx, ell.bit_tiles,
                          x_pad.reshape(n_tc, t), ell.n_rows, mode,
                          a_value, ident, interpret)


# ---------------------------------------------------------------------------
# Bucketed entry points: one pallas_call per bucket slab (grid sized by the
# bucket's own k_b), outputs scatter-merged through the row permutation.
# ---------------------------------------------------------------------------

def bmv_bin_bin_full_bucketed(b: B2SRBucketedEll, x_packed: jax.Array,
                              out_dtype=jnp.float32,
                              interpret: Optional[bool] = None):
    out = jnp.zeros((b.n_tile_rows, b.tile_dim), out_dtype)
    for i, rows in enumerate(b.rows):
        e = common.bucket_ell(b, i)
        vals = bmv_bin_bin_full(e, x_packed, out_dtype, interpret)
        out = out.at[rows].set(vals.reshape(-1, b.tile_dim))
    return out.reshape(-1)[: b.n_rows]


def bmv_bin_bin_bin_bucketed(b: B2SRBucketedEll, x_packed: jax.Array,
                             mask_packed: Optional[jax.Array] = None,
                             complement: bool = True,
                             interpret: Optional[bool] = None):
    out = jnp.zeros((b.n_tile_rows,), jnp.uint32)
    for i, rows in enumerate(b.rows):
        e = common.bucket_ell(b, i)
        out = out.at[rows].set(bmv_bin_bin_bin(e, x_packed,
                                               interpret=interpret))
    # the mask is ANDed after the scatter-merge — still before the caller's
    # store (§V); per-bucket in-kernel masking would need mask gathers
    if mask_packed is not None:
        out = out & (~mask_packed if complement else mask_packed)
    return out


def bmv_bin_full_full_bucketed(b: B2SRBucketedEll, x: jax.Array,
                               semiring: Semiring = ARITHMETIC,
                               a_value: float = 1.0,
                               interpret: Optional[bool] = None):
    if semiring.name not in _MODE:
        raise NotImplementedError(f"kernel path for semiring {semiring.name}")
    mode = _MODE[semiring.name]
    ident = float(semiring.add_identity) if mode != "sum" else 0.0
    out = jnp.full((b.n_tile_rows, b.tile_dim), jnp.asarray(ident, x.dtype))
    for i, rows in enumerate(b.rows):
        e = common.bucket_ell(b, i)
        vals = bmv_bin_full_full(e, x, semiring, a_value, interpret)
        out = out.at[rows].set(vals.reshape(-1, b.tile_dim))
    return out.reshape(-1)[: b.n_rows]


# ---------------------------------------------------------------------------
# Dispatch-registry entries: the "b2sr_pallas" mxv rows (DESIGN.md §10)
# ---------------------------------------------------------------------------

from repro.core.dispatch import apply_output_mask, register  # noqa: E402


@register("mxv", "dense", "full", "b2sr_pallas", bucketed=False, masked=False)
def _mxv_dense(g, x, call):
    return bmv_bin_full_full(g.ell, x, call.semiring, call.a_value)


@register("mxv", "dense", "full", "b2sr_pallas", bucketed=False, masked=True)
def _mxv_dense_masked(g, x, call):
    y = bmv_bin_full_full(g.ell, x, call.semiring, call.a_value)
    return apply_output_mask(y, call.mask, call.complement,
                             call.semiring.identity_for(y.dtype))


@register("mxv", "dense", "full", "b2sr_pallas", bucketed=True, masked=False)
def _mxv_dense_bucketed(g, x, call):
    return bmv_bin_full_full_bucketed(g.buckets(), x, call.semiring,
                                      call.a_value)


@register("mxv", "dense", "full", "b2sr_pallas", bucketed=True, masked=True)
def _mxv_dense_bucketed_masked(g, x, call):
    y = bmv_bin_full_full_bucketed(g.buckets(), x, call.semiring,
                                   call.a_value)
    return apply_output_mask(y, call.mask, call.complement,
                             call.semiring.identity_for(y.dtype))


@register("mxv", "bitvec", "bin", "b2sr_pallas", bucketed=False)
def _mxv_bitvec(g, xw, call):
    return bmv_bin_bin_bin(g.ell, xw, call.mask, call.complement)


@register("mxv", "bitvec", "bin", "b2sr_pallas", bucketed=True)
def _mxv_bitvec_bucketed(g, xw, call):
    return bmv_bin_bin_bin_bucketed(g.buckets(), xw, call.mask,
                                    call.complement)


@register("mxv_pull", "bitvec", "bin", "b2sr_pallas", bucketed=False,
          masked=True)
def _mxv_pull(g, xw, call):
    return bmv_bin_bin_bin_pull(g.ell, xw, call.mask, call.complement)


@register("mxv_pull", "bitvec", "bin", "b2sr_pallas", bucketed=True,
          masked=True)
def _mxv_pull_bucketed(g, xw, call):
    return bmv_bin_bin_bin_pull_bucketed(g.buckets(), xw, call.mask,
                                         call.complement)


@register("mxv", "bitvec", "full", "b2sr_pallas", bucketed=False, masked=False)
def _mxv_count(g, xw, call):
    return bmv_bin_bin_full(g.ell, xw, call.out_dtype)


@register("mxv", "bitvec", "full", "b2sr_pallas", bucketed=False, masked=True)
def _mxv_count_masked(g, xw, call):
    y = bmv_bin_bin_full(g.ell, xw, call.out_dtype)
    return apply_output_mask(y, call.mask, call.complement,
                             jnp.zeros((), call.out_dtype))


@register("mxv", "bitvec", "full", "b2sr_pallas", bucketed=True, masked=False)
def _mxv_count_bucketed(g, xw, call):
    return bmv_bin_bin_full_bucketed(g.buckets(), xw, call.out_dtype)


@register("mxv", "bitvec", "full", "b2sr_pallas", bucketed=True, masked=True)
def _mxv_count_bucketed_masked(g, xw, call):
    y = bmv_bin_bin_full_bucketed(g.buckets(), xw, call.out_dtype)
    return apply_output_mask(y, call.mask, call.complement,
                             jnp.zeros((), call.out_dtype))
