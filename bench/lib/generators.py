"""Graph generators of the benchmark's configurations, made from the seed.

Each returns ``(rows, cols, n)``: a symmetric simple graph as COO with
both edge directions, no self loops and no duplicates. The configuration
file names the generator under ``"generator"`` and holds its parameters
at the top level; :func:`generate` dispatches.

The Kronecker (R-MAT) generator runs on the device as one jitted call:
``scale`` rounds of quadrant choices over all edges at once, the spec's
random relabelling of the vertices, then one two-key sort that symmetrises
and deduplicates. The same seed gives the same graph on any backend
(threefry bits, integer arithmetic, a total order).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A threefry key from any non-negative integer seed (64 bits and up)."""
    state = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(state), impl="threefry2x32")


@partial(jax.jit,
         static_argnames=("scale", "edgefactor", "a", "b", "c", "permute"))
def _kronecker_device(key, scale: int, edgefactor: int, a: float, b: float,
                      c: float, permute: bool):
    n = 1 << scale
    m = n * edgefactor
    key, perm_key = jax.random.split(key)
    keys = jax.random.split(key, scale)

    def level(i, rc):
        rows, cols = rc
        r = jax.random.uniform(keys[i], (m,), jnp.float32)
        row_bit = (r >= a + b).astype(jnp.uint32)
        col_bit = (((r >= a) & (r < a + b)) | (r >= a + b + c)).astype(
            jnp.uint32)
        return (rows << 1) | row_bit, (cols << 1) | col_bit

    zeros = jnp.zeros(m, jnp.uint32)
    rows, cols = jax.lax.fori_loop(0, scale, level, (zeros, zeros))
    if permute:                     # Graph500: vertex labels permuted
        perm = jax.random.permutation(perm_key, n).astype(jnp.uint32)
        rows, cols = perm[rows], perm[cols]
    loop = rows == cols
    r2 = jnp.concatenate([rows, cols])
    c2 = jnp.concatenate([cols, rows])
    drop = jnp.concatenate([loop, loop])
    sentinel = jnp.uint32(n)                    # sorts after every vertex
    r2 = jnp.where(drop, sentinel, r2)
    c2 = jnp.where(drop, sentinel, c2)
    r2, c2 = jax.lax.sort((r2, c2), num_keys=2)
    first = jnp.concatenate([jnp.ones(1, bool),
                             (r2[1:] != r2[:-1]) | (c2[1:] != c2[:-1])])
    return r2, c2, first & (r2 < n)


def kronecker(seed: int, config: dict):
    """Graph500 Kronecker graph: ``edgefactor * 2**scale`` generated edges,
    vertex labels permuted from the seed when ``permute_labels``,
    symmetrised; duplicates and self loops dropped."""
    if not config["symmetric"]:
        raise ValueError("only the symmetric graph is built")
    scale = int(config["scale"])
    r, c_, keep = _kronecker_device(
        seed_key(seed), scale=scale, edgefactor=int(config["edgefactor"]),
        a=float(config["A"]), b=float(config["B"]), c=float(config["C"]),
        permute=bool(config["permute_labels"]))
    keep = np.asarray(keep)
    rows = np.asarray(r)[keep].astype(np.int64)
    cols = np.asarray(c_)[keep].astype(np.int64)
    return rows, cols, 1 << scale


GENERATORS = {"kronecker": kronecker}


def generate(config: dict, seed: int):
    """``(rows, cols, n)`` for a configuration dict, from ``seed``."""
    gen = GENERATORS[config["generator"]]
    return gen(seed, config)
