"""Jitted wrappers for the bitpack kernels."""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.bitpack import bitpack as kernels


@partial(jax.jit, static_argnames=("t", "col_major", "interpret"))
def _pack(x, t, col_major, interpret):
    return kernels.pack_dense_pallas(x, t=t, col_major=col_major,
                                     block_r=1, block_c=1,
                                     interpret=interpret)


def pack_dense(x: jax.Array, t: int, col_major: bool = False,
               interpret: Optional[bool] = None) -> jax.Array:
    """Dense 0/1 [n, m] -> uint32[ceil(n/t), ceil(m/t), t] packed tiles."""
    interpret = common.interpret_default() if interpret is None else interpret
    common.refuse_on_tpu("pack_dense", interpret,
                         "its in-kernel 4-D tile transpose is an "
                         "unsupported Mosaic shape cast")
    x = (x != 0).astype(jnp.uint32)
    x = common.pad_to(common.pad_to(x, 0, t), 1, t)
    return _pack(x, t, col_major, interpret)


@partial(jax.jit, static_argnames=("t", "interpret"))
def _pack_rows(x, t, interpret):
    return kernels.pack_rows_pallas(x, t=t, block_r=8,
                                    block_d=x.shape[1],
                                    interpret=interpret)


def pack_columns(x: jax.Array, t: int,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Dense [n, d] -> uint32[ceil(n/t), d] activation words (BitMatrix).

    Binarizes (``x != 0``) and packs the node axis LSB-first; feature
    columns stay one word each — the layout the bin·bin→full spmm rows
    consume. Traceable (interpret-mode Pallas), so serving plans can pack
    per-layer activations inside their jitted forward.
    """
    interpret = common.interpret_default() if interpret is None else interpret
    n_words = -(-x.shape[0] // t)
    x = (x != 0).astype(jnp.uint32)
    x = common.pad_to(x, 0, 8 * t)
    return _pack_rows(x, t, interpret)[:n_words]


@partial(jax.jit, static_argnames=("t", "interpret"))
def _transpose(words, t, interpret):
    return kernels.bit_transpose_pallas(words, t=t, block=8,
                                        interpret=interpret)


def bit_transpose(words: jax.Array, t: int,
                  interpret: Optional[bool] = None) -> jax.Array:
    interpret = common.interpret_default() if interpret is None else interpret
    flat = words.reshape(-1, t)
    out = _transpose(common.pad_to(flat, 0, 8), t, interpret)
    return out[: flat.shape[0]].reshape(words.shape)
