"""Direction-optimizing traversal: the bit-exact differential harness.

A silently-wrong push/pull switch still returns *a* BFS tree, so every
(direction × backend × buckets × batch × sharded) combination is pinned
against a single oracle — forced-push on the plain b2sr backend — and the
per-iteration direction trace on the result object is asserted too: the
tests check *which* path ran, not just that the answer matched
(DESIGN.md §12).

Layout:
  - scheme-level parity: the registered ``mxv_pull`` rows (jnp, bucketed,
    Pallas early-exit kernel, csr) against the masked push row
  - algorithm differential: bfs / msbfs / cc under push / pull / auto
    across tile dims 4–32 × 3 backends × buckets on/off × batch widths
    1 / 8 / 33
  - the hysteresis property (hypothesis): auto == push oracle bit-exact
    and the trace is monotone (one pull regime, no flapping)
  - validation fixes: ``max_iters`` (0 and negative) handled identically
    on the single-source and batched paths
  - sharded parity: 8 forced host devices in a subprocess
    (test_partition.py pattern)
"""

from __future__ import annotations

import textwrap

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from conftest import (  # noqa: E402
    assert_marker, optional_hypothesis, run_script)

from repro.algorithms import direction as direction_mod  # noqa: E402
from repro.algorithms.bfs import BFSResult, bfs  # noqa: E402
from repro.algorithms.cc import connected_components  # noqa: E402
from repro.algorithms.direction import DirectionConfig  # noqa: E402
from repro.core.descriptor import Descriptor  # noqa: E402
from repro.core.graphblas import GraphMatrix  # noqa: E402
from repro.core.operands import BitVector  # noqa: E402
from repro.data import graphs as G  # noqa: E402
from repro.engine.queries import msbfs  # noqa: E402

TILE_DIMS = (4, 8, 16, 32)
#: (backend, use_buckets) — csr has no bucketed path (registered BOTH).
BACKEND_CASES = (("b2sr", False), ("b2sr", True),
                 ("b2sr_pallas", False), ("b2sr_pallas", True),
                 ("csr", False))
BATCH_WIDTHS = (1, 8, 33)
N = 72                                   # not a multiple of any tile dim


def mixed_graph(n, seed=0, rmat_degree=6, erdos_density=0.02):
    """rmat skew × erdős scatter — the density mix the heuristic sees."""
    r1, c1 = G.rmat_graph(n, avg_degree=rmat_degree, seed=seed)
    r2, c2 = G.dot_graph(n, density=erdos_density, seed=seed + 1)
    rows = np.concatenate([r1, r2])
    cols = np.concatenate([c1, c2])
    key = np.unique(rows.astype(np.int64) * n + cols)
    return key // n, key % n


def build(backend="b2sr", tile_dim=8, buckets=False, n=N, seed=0, **kw):
    rows, cols = mixed_graph(n, seed=seed, **kw)
    g = GraphMatrix.from_coo(rows, cols, n_rows=n, n_cols=n,
                             tile_dim=tile_dim, backend=backend)
    return g.with_buckets(buckets)


def assert_trace_well_formed(res, mode):
    assert len(res.directions) == res.n_iterations
    assert all(d in ("push", "pull") for d in res.directions)
    if mode == "push":
        assert set(res.directions) <= {"push"}, res.directions
    elif mode == "pull":
        assert set(res.directions) <= {"pull"}, res.directions
    else:
        assert direction_mod.check_monotone(res.directions), res.directions


# ---------------------------------------------------------------------------
# scheme-level parity: every registered pull row == the masked push row
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tile_dim", TILE_DIMS)
@pytest.mark.parametrize("backend,buckets", BACKEND_CASES)
def test_pull_row_parity(backend, buckets, tile_dim):
    g = build(backend, tile_dim, buckets, seed=tile_dim)
    rng = np.random.default_rng(tile_dim)
    x = BitVector.pack(jnp.asarray(rng.random(N) > 0.5, jnp.float32),
                       tile_dim, N)
    visited = BitVector.pack(jnp.asarray(rng.random(N) > 0.6, jnp.float32),
                             tile_dim, N)
    push = g.mxv(x, desc=Descriptor(mask=visited, complement=True))
    pull = g.mxv(x, desc=Descriptor(mask=visited, complement=True,
                                    direction="pull"))
    assert np.array_equal(np.asarray(push.words), np.asarray(pull.words))
    # non-complement masks ride the same row
    push = g.mxv(x, desc=Descriptor(mask=visited))
    pull = g.mxv(x, desc=Descriptor(mask=visited, direction="pull"))
    assert np.array_equal(np.asarray(push.words), np.asarray(pull.words))


def test_pull_pallas_kernel_against_oracle():
    """The early-exit kernel itself vs the densify-and-matmul oracle."""
    from repro.kernels.bmv import ops as bmv_ops, ref
    for t in TILE_DIMS:
        g = build("b2sr_pallas", t, False, seed=7 + t)
        rng = np.random.default_rng(t)
        x = BitVector.pack(jnp.asarray(rng.random(N) > 0.4, jnp.float32),
                           t, N)
        m = BitVector.pack(jnp.asarray(rng.random(N) > 0.5, jnp.float32),
                           t, N)
        for complement in (True, False):
            got = bmv_ops.bmv_bin_bin_bin_pull(g.ell, x.words, m.words,
                                               complement)
            want = ref.bmv_bin_bin_bin_pull(g.ell, x.words, m.words,
                                            complement)
            assert np.array_equal(np.asarray(got), np.asarray(want)), \
                (t, complement)


def test_pull_requires_masked_packed_row():
    g = build()
    x = BitVector.pack(jnp.ones(N, jnp.float32), 8, N)
    with pytest.raises(ValueError, match="masked packed"):
        g.mxv(x, desc=Descriptor(direction="pull"))       # no mask
    with pytest.raises(ValueError, match="direction"):
        g.mxv(x, desc=Descriptor(mask=x, direction="sideways"))
    with pytest.raises(ValueError, match="masked packed"):
        g.mxv(jnp.ones(N, jnp.float32),
              desc=Descriptor(mask=jnp.ones(N), direction="pull"))


def test_direction_config_validates():
    with pytest.raises(ValueError, match="mode"):
        DirectionConfig(mode="sideways")
    with pytest.raises(ValueError, match="positive"):
        DirectionConfig(alpha=-1.0)
    assert direction_mod.as_config(None).mode == "push"
    assert direction_mod.as_config("auto").mode == "auto"
    cfg = DirectionConfig(mode="pull", alpha=0.5)
    assert direction_mod.as_config(cfg) is cfg


# ---------------------------------------------------------------------------
# bfs differential: push oracle vs pull vs auto, all backends × tile dims
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tile_dim", TILE_DIMS)
@pytest.mark.parametrize("backend,buckets", BACKEND_CASES)
def test_bfs_direction_differential(backend, buckets, tile_dim):
    g = build(backend, tile_dim, buckets, seed=11)
    oracle = np.asarray(bfs(build("b2sr", tile_dim, False, seed=11), 0,
                            direction="push").levels)
    for mode in ("push", "pull", "auto"):
        res = bfs(g, 0, direction=mode)
        assert np.array_equal(np.asarray(res.levels), oracle), \
            (backend, buckets, tile_dim, mode)
        assert_trace_well_formed(res, mode)


def test_bfs_auto_actually_switches():
    """On a dense-frontier graph the heuristic must pick pull mid-run —
    otherwise the auto tests exercise nothing but push."""
    g = build("b2sr", 8, False, seed=3, rmat_degree=10, erdos_density=0.05)
    res = bfs(g, 0, direction="auto")
    assert "pull" in res.directions, res.directions
    assert res.directions[0] == "push", res.directions
    push = bfs(g, 0, direction="push")
    assert np.array_equal(np.asarray(res.levels), np.asarray(push.levels))


def test_bfs_custom_thresholds():
    g = build(seed=5)
    # alpha so large auto never leaves push; beta tiny keeps pull sticky
    never = bfs(g, 0, direction=DirectionConfig(mode="auto", alpha=1e9))
    assert set(never.directions) <= {"push"}
    eager = bfs(g, 0, direction=DirectionConfig(mode="auto", alpha=1e-9,
                                                beta=1e9))
    assert "pull" in eager.directions
    push = bfs(g, 0, direction="push")
    for res in (never, eager):
        assert np.array_equal(np.asarray(res.levels),
                              np.asarray(push.levels))


def test_bfs_row_chunk_direction_parity():
    g = build("b2sr", 8, False, seed=9)
    push = bfs(g, 0, direction="push", row_chunk=3)
    pull = bfs(g, 0, direction="pull", row_chunk=3)
    assert np.array_equal(np.asarray(push.levels), np.asarray(pull.levels))


# ---------------------------------------------------------------------------
# msbfs differential: batch widths 1 / 8 / 33, whole-batch switching
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("width", BATCH_WIDTHS)
@pytest.mark.parametrize("backend,buckets", BACKEND_CASES)
def test_msbfs_direction_differential(backend, buckets, width):
    g = build(backend, 8, buckets, seed=21)
    srcs = [int(s) for s in
            np.random.default_rng(width).choice(N, width, replace=False)]
    push = msbfs(g, srcs, direction="push")
    assert set(push.directions) <= {"push"}
    # columns of the push batch match the single-source push oracle
    for j in (0, width - 1):
        single = bfs(g, srcs[j], direction="push")
        assert np.array_equal(np.asarray(push.levels[:, j]),
                              np.asarray(single.levels))
    for mode in ("pull", "auto"):
        res = msbfs(g, srcs, direction=mode)
        assert np.array_equal(np.asarray(res.levels),
                              np.asarray(push.levels)), \
            (backend, buckets, width, mode)
        assert_trace_well_formed(res, mode)


def test_bfs_batched_routes_with_direction():
    g = build(seed=2)
    res = bfs(g, [0, 5, 9], direction="pull")
    assert set(res.directions) <= {"pull"}
    push = bfs(g, [0, 5, 9], direction="push")
    assert np.array_equal(np.asarray(res.levels), np.asarray(push.levels))


def test_msbfs_plan_keys_isolate_direction():
    """push / pull / auto loops are different XLA programs — they must
    never share a cached plan (the descriptor key carries the config)."""
    from repro.engine.planner import PlanCache
    pc = PlanCache()
    g = build(seed=4)
    for mode in ("push", "pull", "auto"):
        msbfs(g, [0, 1], direction=mode, planner=pc)
    assert len(pc) == 3
    # same mode, different thresholds: also distinct
    msbfs(g, [0, 1], planner=pc,
          direction=DirectionConfig(mode="auto", alpha=0.5))
    assert len(pc) == 4


# ---------------------------------------------------------------------------
# cc differential: orientation switching on the symmetric adjacency
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend,buckets", BACKEND_CASES)
def test_cc_direction_differential(backend, buckets):
    g = build(backend, 8, buckets, seed=31)
    oracle = connected_components(build("b2sr", 8, False, seed=31),
                                  direction="push")
    for mode in ("push", "pull", "auto"):
        res = connected_components(g, direction=mode)
        assert np.array_equal(np.asarray(res.labels),
                              np.asarray(oracle.labels)), \
            (backend, buckets, mode)
        assert_trace_well_formed(res, mode)


def test_cc_without_transpose_falls_back_to_push():
    rows, cols = mixed_graph(N, seed=31)
    g = GraphMatrix.from_coo(rows, cols, n_rows=N, n_cols=N, tile_dim=8,
                             with_transpose=False)
    res = connected_components(g, direction="auto")
    assert set(res.directions) <= {"push"}
    ref = connected_components(build("b2sr", 8, False, seed=31),
                               direction="push")
    assert np.array_equal(np.asarray(res.labels), np.asarray(ref.labels))


# ---------------------------------------------------------------------------
# the max_iters fix: single-source and batched paths validate identically
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("source", (5, [5, 7]))
def test_max_iters_zero_returns_zero_iteration_shape(source):
    g = build(seed=13)
    res = bfs(g, source, max_iters=0)
    assert res.n_iterations == 0
    assert res.directions == ()
    lv = np.asarray(res.levels)
    if np.ndim(source) > 0:
        assert lv.shape == (N, len(source))
        for j, s in enumerate(source):
            assert lv[s, j] == 0
        assert (lv >= 0).sum() == len(source)   # only the sources stamped
    else:
        assert lv.shape == (N,)
        assert lv[source] == 0 and (lv >= 0).sum() == 1


@pytest.mark.parametrize("source", (5, [5, 7]))
def test_max_iters_negative_raises(source):
    g = build(seed=13)
    with pytest.raises(ValueError, match="max_iters"):
        bfs(g, source, max_iters=-1)


def test_batched_row_chunk_still_raises():
    g = build(seed=13)
    with pytest.raises(ValueError, match="row_chunk"):
        bfs(g, [1, 2], row_chunk=4)


def test_max_iters_one_partial_levels():
    g = build(seed=13)
    one = bfs(g, 0, max_iters=1)
    full = bfs(g, 0)
    assert one.n_iterations == 1 and len(one.directions) == 1
    lv1, lvf = np.asarray(one.levels), np.asarray(full.levels)
    # exactly levels 0 and 1 are settled after one iteration
    assert np.array_equal(lv1[lv1 >= 0], lvf[lv1 >= 0])
    assert (lv1 >= 0).sum() == ((lvf >= 0) & (lvf <= 1)).sum()


# ---------------------------------------------------------------------------
# hypothesis: auto == push oracle + monotone trace across the density sweep
# ---------------------------------------------------------------------------

given, settings, st = optional_hypothesis()


@given(rmat_degree=st.integers(min_value=2, max_value=14),
       erdos_density=st.floats(min_value=0.0, max_value=0.12),
       seed=st.integers(min_value=0, max_value=2 ** 16))
@settings(max_examples=20, deadline=None, derandomize=True)
def test_auto_matches_push_and_trace_is_monotone(rmat_degree, erdos_density,
                                                 seed):
    g = build("b2sr", 8, False, n=64, seed=seed, rmat_degree=rmat_degree,
              erdos_density=erdos_density)
    push = bfs(g, int(seed) % 64, direction="push")
    auto = bfs(g, int(seed) % 64, direction="auto")
    assert np.array_equal(np.asarray(push.levels),
                          np.asarray(auto.levels)), \
        f"auto != push oracle; trace={auto.directions}"
    assert direction_mod.check_monotone(auto.directions), \
        f"direction flapping: {auto.directions}"
    assert len(auto.directions) == auto.n_iterations, \
        f"trace length mismatch: {auto.directions} vs {auto.n_iterations}"


# ---------------------------------------------------------------------------
# sharded parity: 8 forced host devices (test_partition.py pattern)
# ---------------------------------------------------------------------------

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax

    from repro.algorithms.bfs import bfs
    from repro.algorithms.cc import connected_components
    from repro.core.graphblas import GraphMatrix
    from repro.data import graphs as G
    from repro.engine.queries import msbfs
    from repro.launch.mesh import make_debug_mesh

    assert len(jax.devices()) == 8
    n = 128
    r1, c1 = G.rmat_graph(n, avg_degree=6, seed=17)
    r2, c2 = G.dot_graph(n, density=0.02, seed=18)
    key = np.unique(np.concatenate([r1, r2]).astype(np.int64) * n
                    + np.concatenate([c1, c2]))
    rows, cols = key // n, key % n
    mesh = make_debug_mesh(8, model=2)            # (data=4, model=2)

    for backend in ("b2sr", "b2sr_pallas"):
        for buckets in (False, True):
            g = GraphMatrix.from_coo(rows, cols, n_rows=n, n_cols=n,
                                     tile_dim=8, backend=backend
                                     ).with_buckets(buckets)
            gs = g.shard(mesh)
            oracle = np.asarray(bfs(g, 0, direction="push").levels)
            for mode in ("push", "pull", "auto"):
                res = bfs(gs, 0, direction=mode)
                assert np.array_equal(np.asarray(res.levels), oracle), \\
                    (backend, buckets, mode)
            auto = bfs(gs, 0, direction="auto")
            assert "pull" in auto.directions, auto.directions
    print("BFS_SHARDED_OK")

    g = GraphMatrix.from_coo(rows, cols, n_rows=n, n_cols=n, tile_dim=8)
    gs = g.shard(mesh)
    srcs = [0, 5, 9, 40]
    push = msbfs(g, srcs, direction="push")
    for mode in ("push", "pull", "auto"):
        res = msbfs(gs, srcs, direction=mode)
        assert np.array_equal(np.asarray(res.levels),
                              np.asarray(push.levels)), mode
    print("MSBFS_SHARDED_OK")

    ref = connected_components(g, direction="push")
    for mode in ("push", "pull", "auto"):
        res = connected_components(gs, direction=mode)
        assert np.array_equal(np.asarray(res.labels),
                              np.asarray(ref.labels)), mode
    print("CC_SHARDED_OK")
""")

MARKERS = ["BFS_SHARDED_OK", "MSBFS_SHARDED_OK", "CC_SHARDED_OK"]


@pytest.fixture(scope="module")
def sharded_direction_run():
    return run_script(_SCRIPT)


@pytest.mark.parametrize("marker", MARKERS)
def test_sharded_direction_parity(sharded_direction_run, marker):
    assert_marker(sharded_direction_run, marker)
