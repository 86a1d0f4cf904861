"""BitGNN aggregation layers: every neighborhood sum goes through g.mxm.

The one entry point models use is :func:`aggregate` — it wraps a
(possibly traced) :class:`~repro.core.b2sr.B2SREll` in a minimal
:class:`~repro.core.graphblas.GraphMatrix` and dispatches the registry's
``("mxm", "dense"|"bitmat", "full", backend, ...)`` row, so buckets,
backends, sharding and the plan/fault machinery apply to GNN aggregation
exactly as they do to traversal (DESIGN.md §15). The bespoke
``spmm_b2sr_shardmap`` call site that ``models/gnn/gcn.py`` used to carry
is gone: ``axes=...`` routes through the registry's ``sharded`` rows via
a prepared-graph cache instead.

Sharding note: ``GraphMatrix.shard`` partitions host-side (numpy), so a
sharded graph cannot be built from tracers inside a jitted train step.
:func:`prepare_sharded` is therefore called once, host-side, with the
concrete ELL; jitted calls that pass ``axes`` find the prepared graph in
the cache by the ELL's *static* signature (shapes + tile_dim + axes) and
close over its concrete arrays — correct for the full-graph training this
path serves, where the adjacency is a step-invariant constant. A cache
miss under trace falls back to the unsharded registry row (single-device
runs never need to prepare anything).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.b2sr import B2SREll
from repro.core.graphblas import GraphMatrix
from repro.core.operands import BitMatrix
from repro.gnn_bit import binarize as binarize_mod


def graph_from_ell(ell: B2SREll, backend: str = "b2sr",
                   use_buckets: bool = False) -> GraphMatrix:
    """Wrap an ELL view as a minimal mxm-capable GraphMatrix.

    Safe under trace: the wrapped rows touch only ``ell`` (and its lazily
    bucketed view — host-side, hence ``use_buckets`` defaults off here;
    pass a concrete ELL if you turn it on). ``nnz`` is unknowable from a
    traced ELL and never read by mxm; the CSR twin is absent, so only the
    b2sr backends dispatch (the csr fallback path builds real graphs).
    """
    return GraphMatrix(
        n_rows=ell.n_rows, n_cols=ell.n_cols, nnz=-1,
        tile_dim=ell.tile_dim, tiles=None, tiles_t=None, csr=None,
        csr_t=None, ell_cache=ell,
        backend=backend, use_buckets=use_buckets)


# -- prepared sharded graphs (host-side build, traced lookup) ---------------

_SHARDED_CACHE: Dict[tuple, GraphMatrix] = {}


def _signature(ell: B2SREll, axes: Tuple[str, ...], backend: str) -> tuple:
    return (ell.tile_dim, ell.n_rows, ell.n_cols,
            tuple(ell.tile_col_idx.shape), axes, backend)


def _default_mesh(axes: Tuple[str, ...]):
    devs = np.array(jax.devices())
    shape = (-1,) + (1,) * (len(axes) - 1)
    return jax.sharding.Mesh(devs.reshape(shape), axes)


def prepare_sharded(ell: B2SREll, axes, mesh=None, backend: str = "b2sr",
                    use_buckets: bool = False) -> GraphMatrix:
    """Row-partition a concrete ELL once; jitted ``aggregate`` calls hit it.

    Must run outside jit (partitioning is host-side numpy). ``mesh``
    defaults to all local devices on the first axis name.
    """
    axes = tuple(axes)
    if mesh is None:
        mesh = _default_mesh(axes)
    g = graph_from_ell(ell, backend=backend,
                       use_buckets=use_buckets).shard(mesh, axes)
    _SHARDED_CACHE[_signature(ell, axes, backend)] = g
    return g


def _resolve_graph(ell: B2SREll, axes, backend: str,
                   use_buckets: bool) -> GraphMatrix:
    if axes:
        g = _SHARDED_CACHE.get(_signature(ell, tuple(axes), backend))
        if g is not None:
            return g
    return graph_from_ell(ell, backend=backend, use_buckets=use_buckets)


# -- aggregation entry points -----------------------------------------------

def aggregate(ell: B2SREll, x: jax.Array, axes=(), backend: str = "b2sr",
              use_buckets: bool = False) -> jax.Array:
    """A @ x through the registry's spmm_bin_full_full row (GCN hot path)."""
    return _resolve_graph(ell, axes, backend, use_buckets).mxm(x)


def binary_aggregate(ell: B2SREll, bm: BitMatrix, out_dtype=None, axes=(),
                     backend: str = "b2sr",
                     use_buckets: bool = False) -> jax.Array:
    """A @ bits via the packed bin·bin→full row: popcount counts [n, d]."""
    return _resolve_graph(ell, axes, backend, use_buckets).mxm(
        bm, out_dtype=out_dtype)


def signed_aggregate(ell: B2SREll, x: jax.Array, rowsum: jax.Array,
                     axes=(), backend: str = "b2sr",
                     use_buckets: bool = False,
                     alpha: Optional[jax.Array] = None) -> jax.Array:
    """α-scaled ±1 aggregation without ever unpacking the activations.

    ``A @ (α·sign(x)) = α · (2·(A @ bits) − A·1)`` with ``bits = x > 0``:
    one packed popcount mxm plus a rank-1 epilogue (XNOR-Net style; the
    α·popcount reconstruction of DESIGN.md §15). Exact — not approximate —
    whenever ``x`` is already ±1, e.g. downstream of ``ste_sign``.
    ``rowsum`` is A's row-sum (neighbor count per node); α defaults to the
    per-feature mean|x| and can be pinned to 1 for pure sign aggregation.
    """
    if alpha is None:
        alpha = binarize_mod.alpha_scale(x)
    bm = binarize_mod.pack_activations(x, ell.tile_dim)
    counts = binary_aggregate(ell, bm, axes=axes, backend=backend,
                              use_buckets=use_buckets)
    return alpha[None, :] * (2.0 * counts - rowsum[:, None])
