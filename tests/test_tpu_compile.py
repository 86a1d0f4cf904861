"""Compile the main path's Pallas kernels for a described TPU v5e.

Nothing here runs on a chip: each test lowers a jitted kernel wrapper at
Graph500 scale-20 slab shapes (32x32 tiles, 2^15 tile columns) for one
chip of a described ``v5e:2x2`` topology and compiles it with the TPU
compiler, which refuses what Mosaic cannot lower (unaligned blocks,
in-kernel gathers, VMEM overflow) — faults the CPU interpret mode hides.
The topology is described inside a fixture (never at import), so several
test workers can collect this file and only the one that runs it loads
the TPU library.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import common
from repro.kernels.bitpack import bitpack as bp_kernels
from repro.kernels.bitpack import ops as bp_ops
from repro.kernels.bmv import ops as bmv_ops
from repro.kernels.spmm import ops as spmm_ops

T = 32
N_TILE_COLS = 1 << 15            # scale 20 at t = 32
#: bucket slabs of a scale-20 Kronecker graph: the merged narrow slab,
#: a mid-width slab, and a hub slab with an unaligned width
SLABS = [(16384, 256), (4096, 2048), (40, 18932)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                   # noqa: BLE001 — no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


@pytest.fixture(scope="module", autouse=False)
def no_cache():
    # compiles for a described chip cannot be read back from the
    # persistent cache; keep the cache out of these tests
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _slab(spec, rows, k):
    return (spec((rows, k), jnp.int32), spec((rows, k, T), jnp.uint32))


def _compile(fn, *args, **static):
    compiled = fn.lower(*args, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("rows,k", SLABS)
@pytest.mark.parametrize("row", ["push", "pull", "count"])
def test_bmv_bin_bin_compiles(spec, no_cache, rows, k, row):
    col, tiles = _slab(spec, rows, k)
    x = spec((N_TILE_COLS,), jnp.uint32)
    if row == "push":
        _compile(bmv_ops._bin_bin_bin, col, tiles, x, interpret=False)
    elif row == "pull":
        mask = spec((rows,), jnp.uint32)
        _compile(bmv_ops._bin_bin_bin_pull, col, tiles, x, mask,
                 complement=True, interpret=False)
    else:
        _compile(bmv_ops._bin_bin_full, col, tiles, x, n_rows=rows * T,
                 out_dtype=jnp.float32, interpret=False)


@pytest.mark.parametrize("mode,ident", [("sum", 0.0),
                                        ("min_plus", float("inf"))])
def test_bmv_bin_full_full_compiles(spec, no_cache, mode, ident):
    rows, k = SLABS[1]
    col, tiles = _slab(spec, rows, k)
    x3 = spec((N_TILE_COLS, T), jnp.float32)
    c = _compile(bmv_ops._bin_full_full, col, tiles, x3, n_rows=rows * T,
                 mode=mode, a_value=1.0, ident=ident, interpret=False)
    # the gathered value slab (as large as the tile slab) is the one large
    # temporary, plus its transient row-gather form padded to 128 lanes
    slab = rows * k * T * 4
    assert c.memory_analysis().temp_size_in_bytes <= 5 * slab


@pytest.mark.parametrize("rhs", ["frontier", "dense"])
def test_spmm_compiles(spec, no_cache, rhs):
    rows, k = SLABS[0]
    col, tiles = _slab(spec, rows, k)
    if rhs == "frontier":                    # msbfs / k-hop, 64 sources
        f3 = spec((N_TILE_COLS, T, 2), jnp.uint32)
        _compile(spmm_ops._spmm_bbb, col, tiles, f3, interpret=False)
    else:                                    # batched PPR, 32 seeds
        x3 = spec((N_TILE_COLS, T, 32), jnp.float32)
        _compile(spmm_ops._spmm, col, tiles, x3, n_rows=rows * T,
                 interpret=False)


def test_bitpack_compiles(spec, no_cache):
    _compile(bp_ops._pack_rows, spec((8 * T * 64, 256), jnp.uint32),
             t=T, interpret=False)
    _compile(bp_ops._transpose, spec((4096, T), jnp.uint32), t=T,
             interpret=False)


def test_mosaic_refusal_reaches_caller(spec, no_cache):
    # pack_dense's 4-D in-kernel transpose is refused by Mosaic: the error
    # surfaces from the compile, nothing falls back to the interpreter
    x = spec((8 * T, 8 * T), jnp.uint32)
    with pytest.raises(Exception, match="Mosaic|shape cast"):
        jax.jit(lambda a: bp_kernels.pack_dense_pallas(
            a, t=T, interpret=False)).lower(x).compile()


def test_tpu_default_never_interprets(monkeypatch):
    # on a TPU the kernels compile unless a caller asks for the
    # interpreter, and a kernel without a TPU lowering refuses loudly
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert common.interpret_default() is False
    with pytest.raises(NotImplementedError, match="no TPU"):
        bp_ops.pack_dense(jnp.ones((8, 8)), 8)
    out = bp_ops.pack_dense(jnp.ones((8, 8)), 8, interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.full((1, 1, 8), 255, np.uint32))
