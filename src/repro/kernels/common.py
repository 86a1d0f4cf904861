"""Shared utilities for the Pallas kernel layer."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.b2sr import B2SRBucketedEll, B2SREll


def interpret_default() -> bool:
    """Pallas kernels compile through Mosaic on a TPU and interpret elsewhere.

    There is no override: on a TPU a kernel is interpreted only when its
    caller passes ``interpret=True``, so a kernel that Mosaic refuses fails
    loudly instead of quietly running in the interpreter.
    """
    return jax.default_backend() != "tpu"


def pad_to(x: jax.Array, axis: int, multiple: int, fill=0) -> jax.Array:
    n = x.shape[axis]
    target = -(-n // multiple) * multiple
    if target == n:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - n)
    return jnp.pad(x, pads, constant_values=fill)


def unpack_words(words: jax.Array, t: int, dtype=jnp.float32) -> jax.Array:
    """uint32[..., t] -> 0/1 [..., t, t] (row, col). Kernel-body safe."""
    shifts = jnp.arange(t, dtype=jnp.uint32)
    bits = (words[..., :, None] >> shifts) & jnp.uint32(1)
    return bits.astype(dtype)


def bucket_ell(b: B2SRBucketedEll, i: int) -> B2SREll:
    """Bucket ``i``'s slab as a standalone ELL view for the kernel wrappers.

    The slab's rows are a permuted subset of the original tile-rows, so
    ``n_rows`` is the slab's own row extent (rows_b × t); callers scatter
    the result back through ``b.rows[i]``.
    """
    col = b.col_idx[i]
    return B2SREll(
        tile_col_idx=col,
        bit_tiles=b.bit_tiles[i],
        row_n_tiles=jnp.sum((col >= 0).astype(jnp.int32), axis=1),
        tile_dim=b.tile_dim,
        n_rows=int(col.shape[0]) * b.tile_dim,
        n_cols=b.n_cols,
    )


def refuse_on_tpu(kernel: str, interpret: bool, reason: str) -> None:
    """Raise before a kernel that Mosaic does not lower is traced on a TPU."""
    if not interpret:
        raise NotImplementedError(
            f"{kernel}: no TPU (Mosaic) lowering — {reason}. Run it on the "
            f"'b2sr' backend, or pass interpret=True explicitly.")
